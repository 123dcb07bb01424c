"""Module API end-to-end (model: reference tests/python/unittest/test_module.py
+ tests/python/train/test_mlp.py — the minimum slice: MNIST-style MLP/LeNet via
Module.fit on synthetic data)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym, io
from mxnet_tpu.test_utils import assert_almost_equal


def _make_mlp():
    data = sym.Variable("data")
    fc1 = sym.FullyConnected(data, name="fc1", num_hidden=32)
    act1 = sym.Activation(fc1, name="relu1", act_type="relu")
    fc2 = sym.FullyConnected(act1, name="fc2", num_hidden=10)
    softmax = sym.SoftmaxOutput(fc2, name="softmax")
    return softmax


def _synthetic_blobs(n=256, seed=0):
    """Linearly separable blobs so a few epochs converge."""
    rng = np.random.RandomState(seed)
    centers = rng.uniform(-3, 3, (10, 16))
    labels = rng.randint(0, 10, n)
    data = centers[labels] + rng.normal(0, 0.3, (n, 16))
    return data.astype(np.float32), labels.astype(np.float32)


def test_module_bind_forward():
    net = _make_mlp()
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (8, 16))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params()
    batch = io.DataBatch(data=[nd.ones((8, 16))], label=[nd.zeros((8,))])
    mod.forward(batch, is_train=False)
    outs = mod.get_outputs()
    assert outs[0].shape == (8, 10)
    assert_almost_equal(outs[0].asnumpy().sum(axis=1), np.ones(8), rtol=1e-4)


def test_module_fit_convergence():
    data, labels = _synthetic_blobs(512)
    train_iter = io.NDArrayIter(data, labels, batch_size=32, shuffle=True)
    mod = mx.mod.Module(_make_mlp(), context=mx.cpu())
    mod.fit(train_iter, num_epoch=5, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            eval_metric="acc",
            initializer=mx.init.Xavier())
    train_iter.reset()
    score = mod.score(train_iter, "acc")
    assert score[0][1] > 0.9, "accuracy %s too low" % score[0][1]


def test_module_save_load_checkpoint(tmp_path):
    data, labels = _synthetic_blobs(64)
    train_iter = io.NDArrayIter(data, labels, batch_size=16)
    mod = mx.mod.Module(_make_mlp(), context=mx.cpu())
    mod.bind(data_shapes=train_iter.provide_data,
             label_shapes=train_iter.provide_label)
    mod.init_params()
    mod.init_optimizer()
    prefix = str(tmp_path / "mlp")
    mod.save_checkpoint(prefix, 1, save_optimizer_states=True)

    mod2 = mx.mod.Module.load(prefix, 1, context=mx.cpu())
    mod2.bind(data_shapes=train_iter.provide_data,
              label_shapes=train_iter.provide_label)
    batch = next(iter(train_iter))
    mod.forward(batch, is_train=False)
    mod2.forward(batch, is_train=False)
    assert_almost_equal(mod.get_outputs()[0].asnumpy(),
                        mod2.get_outputs()[0].asnumpy(), rtol=1e-5)


def test_module_predict():
    data, labels = _synthetic_blobs(64)
    it = io.NDArrayIter(data, labels, batch_size=16)
    mod = mx.mod.Module(_make_mlp(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    out = mod.predict(it)
    assert out.shape == (64, 10)


def test_module_lenet_conv():
    """LeNet on image-shaped synthetic data (the train_mnist.py example's shape)."""
    data = sym.Variable("data")
    conv1 = sym.Convolution(data, name="conv1", kernel=(3, 3), num_filter=8)
    act1 = sym.Activation(conv1, act_type="relu")
    pool1 = sym.Pooling(act1, kernel=(2, 2), stride=(2, 2), pool_type="max")
    flat = sym.Flatten(pool1)
    fc1 = sym.FullyConnected(flat, name="fc1", num_hidden=10)
    net = sym.SoftmaxOutput(fc1, name="softmax")

    rng = np.random.RandomState(0)
    X = rng.uniform(0, 1, (64, 1, 12, 12)).astype(np.float32)
    Y = rng.randint(0, 10, 64).astype(np.float32)
    it = io.NDArrayIter(X, Y, batch_size=16)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=2, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05})
    # just verify it ran and updated params
    args, _ = mod.get_params()
    assert not np.allclose(args["fc1_weight"].asnumpy(), 0)


def test_bucketing_module():
    def sym_gen(seq_len):
        data = sym.Variable("data")
        fc = sym.FullyConnected(data, name="fc", num_hidden=4)
        out = sym.SoftmaxOutput(fc, name="softmax")
        return out, ["data"], ["softmax_label"]

    mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=8,
                                 context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 8))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params()
    mod.init_optimizer()
    batch = io.DataBatch(data=[nd.ones((4, 8))], label=[nd.zeros((4,))],
                         bucket_key=8,
                         provide_data=[io.DataDesc("data", (4, 8))],
                         provide_label=[io.DataDesc("softmax_label", (4,))])
    mod.forward(batch, is_train=True)
    mod.backward()
    mod.update()
    assert mod.get_outputs()[0].shape == (4, 4)


def test_python_loss_module():
    """PythonLossModule: pass-through forward, softmax-CE input grad
    (reference module/python_module.py:243)."""
    from mxnet_tpu.module import PythonLossModule
    from mxnet_tpu.io import DataBatch
    m = PythonLossModule()
    m.bind(data_shapes=[("data", (4, 3))],
           label_shapes=[("softmax_label", (4,))])
    m.init_params()
    scores = nd.array(np.random.uniform(-1, 1, (4, 3)).astype(np.float32))
    labels = nd.array(np.array([0, 2, 1, 2], np.float32))
    m.forward(DataBatch(data=[scores], label=[labels]), is_train=True)
    out = m.get_outputs()[0]
    assert out.shape == (4, 3)
    m.backward()
    g = m.get_input_grads()[0].asnumpy()
    p = np.exp(scores.asnumpy()); p /= p.sum(1, keepdims=True)
    expect = p.copy()
    for i, l in enumerate([0, 2, 1, 2]):
        expect[i, l] -= 1
    np.testing.assert_allclose(g, expect, rtol=1e-5, atol=1e-6)


def test_python_loss_module_custom_grad():
    from mxnet_tpu.module import PythonLossModule
    from mxnet_tpu.io import DataBatch
    m = PythonLossModule(grad_func=lambda s, l: s * 0 + 7)
    m.bind(data_shapes=[("data", (2, 2))],
           label_shapes=[("softmax_label", (2,))])
    m.init_params()
    m.forward(DataBatch(data=[nd.zeros((2, 2))], label=[nd.zeros((2,))]),
              is_train=True)
    m.backward()
    assert (m.get_input_grads()[0].asnumpy() == 7).all()


def test_module_multi_device_training_matches_single():
    """Module bound on 4 devices with a local kvstore takes the same SGD
    trajectory as the single-device module (DataParallelExecutorGroup +
    CommDevice reduce semantics, tests/nightly/multi_lenet.py analog)."""
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    import mxnet_tpu as mx
    rng = np.random.RandomState(7)
    x = rng.normal(0, 1, (64, 10)).astype(np.float32)
    y = rng.randint(0, 3, (64,)).astype(np.float32)

    def make_mod(ctxs):
        data = mx.sym.var("data")
        out = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
        out = mx.sym.Activation(out, act_type="relu")
        out = mx.sym.FullyConnected(out, num_hidden=3, name="fc2")
        out = mx.sym.SoftmaxOutput(out, name="softmax")
        mod = mx.mod.Module(out, context=ctxs)
        it = mx.io.NDArrayIter(x, y, batch_size=16, label_name="softmax_label")
        mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
        mod.init_params(mx.init.Xavier(rnd_type="gaussian", magnitude=1),
                        force_init=True)
        return mod, it

    mod1, it1 = make_mod(mx.cpu())
    mod4, it4 = make_mod([mx.cpu(i) for i in range(4)])
    # identical starting params BEFORE init_optimizer (the kvstore snapshots
    # weights at init; set_params afterwards would desync, as the reference)
    p1, _ = mod1.get_params()
    mod4.set_params(p1, {}, force_init=True)
    for m in (mod1, mod4):
        m.init_optimizer(kvstore="local", optimizer="sgd",
                         optimizer_params=(("learning_rate", 0.1),))

    for _ in range(3):
        it1.reset(); it4.reset()
        for b1, b4 in zip(it1, it4):
            mod1.forward_backward(b1); mod1.update()
            mod4.forward_backward(b4); mod4.update()
    f1, _ = mod1.get_params()
    f4, _ = mod4.get_params()
    for k in f1:
        np.testing.assert_allclose(f1[k].asnumpy(), f4[k].asnumpy(),
                                   rtol=2e-4, atol=2e-5, err_msg=k)


def test_module_multi_device_scores():
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    import mxnet_tpu as mx
    rng = np.random.RandomState(0)
    x = rng.normal(0, 1, (32, 6)).astype(np.float32)
    w = rng.normal(0, 1, (6, 4)).astype(np.float32)
    y = x.dot(w).argmax(1).astype(np.float32)
    data = mx.sym.var("data")
    out = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=4), name="softmax")
    mod = mx.mod.Module(out, context=[mx.cpu(0), mx.cpu(1)])
    it = mx.io.NDArrayIter(x, y, batch_size=8, label_name="softmax_label")
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    mod.init_optimizer(optimizer_params=(("learning_rate", 0.5),))
    for _ in range(40):
        it.reset()
        for batch in it:
            mod.forward_backward(batch)
            mod.update()
    acc = mod.score(it, "acc")[0][1]
    assert acc > 0.9, "multi-device training failed to fit: acc=%s" % acc
