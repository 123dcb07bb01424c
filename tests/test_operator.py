"""Operator correctness (model: reference tests/python/unittest/test_operator.py).

Includes numeric-gradient checks against autodiff — the reference's
check_numeric_gradient strategy (python/mxnet/test_utils.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd
from mxnet_tpu.test_utils import assert_almost_equal, check_numeric_gradient


def test_fully_connected():
    x = nd.array(np.random.uniform(-1, 1, (4, 10)))
    w = nd.array(np.random.uniform(-1, 1, (5, 10)))
    b = nd.array(np.random.uniform(-1, 1, (5,)))
    out = nd.FullyConnected(x, w, b, num_hidden=5)
    expected = x.asnumpy().dot(w.asnumpy().T) + b.asnumpy()
    assert_almost_equal(out.asnumpy(), expected, rtol=1e-4, atol=1e-5)
    out2 = nd.FullyConnected(x, w, num_hidden=5, no_bias=True)
    assert_almost_equal(out2.asnumpy(), x.asnumpy().dot(w.asnumpy().T),
                        rtol=1e-4, atol=1e-5)


def test_fully_connected_flatten():
    x = nd.array(np.random.uniform(-1, 1, (2, 3, 4)))
    w = nd.array(np.random.uniform(-1, 1, (5, 12)))
    b = nd.zeros((5,))
    out = nd.FullyConnected(x, w, b, num_hidden=5)
    assert out.shape == (2, 5)


def test_convolution():
    x = nd.array(np.random.uniform(-1, 1, (2, 3, 8, 8)))
    w = nd.array(np.random.uniform(-1, 1, (4, 3, 3, 3)))
    b = nd.zeros((4,))
    out = nd.Convolution(x, w, b, kernel=(3, 3), num_filter=4)
    assert out.shape == (2, 4, 6, 6)
    out = nd.Convolution(x, w, b, kernel=(3, 3), num_filter=4, pad=(1, 1))
    assert out.shape == (2, 4, 8, 8)
    out = nd.Convolution(x, w, b, kernel=(3, 3), num_filter=4, stride=(2, 2),
                         pad=(1, 1))
    assert out.shape == (2, 4, 4, 4)


def test_convolution_vs_numpy():
    """1x1 conv is a matmul over channels."""
    x = np.random.uniform(-1, 1, (2, 3, 5, 5)).astype(np.float32)
    w = np.random.uniform(-1, 1, (4, 3, 1, 1)).astype(np.float32)
    out = nd.Convolution(nd.array(x), nd.array(w), kernel=(1, 1), num_filter=4,
                         no_bias=True)
    expected = np.einsum("nchw,oc->nohw", x, w[:, :, 0, 0])
    assert_almost_equal(out.asnumpy(), expected, rtol=1e-4, atol=1e-5)


def test_pooling():
    x = nd.array(np.random.uniform(-1, 1, (2, 3, 8, 8)))
    out = nd.Pooling(x, kernel=(2, 2), stride=(2, 2), pool_type="max")
    assert out.shape == (2, 3, 4, 4)
    expected = x.asnumpy().reshape(2, 3, 4, 2, 4, 2).max(axis=(3, 5))
    assert_almost_equal(out.asnumpy(), expected)
    out = nd.Pooling(x, kernel=(2, 2), stride=(2, 2), pool_type="avg")
    expected = x.asnumpy().reshape(2, 3, 4, 2, 4, 2).mean(axis=(3, 5))
    assert_almost_equal(out.asnumpy(), expected, rtol=1e-5)
    out = nd.Pooling(x, global_pool=True, pool_type="max", kernel=(1, 1))
    assert out.shape == (2, 3, 1, 1)


def test_batchnorm_inference():
    x = nd.array(np.random.uniform(-1, 1, (2, 3, 4, 4)))
    gamma = nd.ones((3,))
    beta = nd.zeros((3,))
    mean = nd.zeros((3,))
    var = nd.ones((3,))
    out, m, v = nd.BatchNorm(x, gamma, beta, mean, var, fix_gamma=False)
    assert_almost_equal(out.asnumpy(), x.asnumpy() / np.sqrt(1 + 1e-3),
                        rtol=1e-4)


def test_batchnorm_training_stats():
    x = nd.array(np.random.uniform(-1, 1, (8, 3, 4, 4)))
    gamma = nd.ones((3,))
    beta = nd.zeros((3,))
    mean = nd.zeros((3,))
    var = nd.ones((3,))
    with autograd.record(train_mode=True):
        out, m, v = nd.BatchNorm(x, gamma, beta, mean, var, fix_gamma=False)
    xn = x.asnumpy()
    assert_almost_equal(m.asnumpy(), xn.mean(axis=(0, 2, 3)), rtol=1e-4, atol=1e-5)
    # third output is the reference's INVERSE STD (batch_norm.cc:140-154)
    assert_almost_equal(v.asnumpy(),
                        1.0 / np.sqrt(xn.var(axis=(0, 2, 3)) + 1e-3),
                        rtol=1e-4, atol=1e-5)


def _bn_data(ratio, axis, seed=0):
    """float32 data with |mean|/std = ratio per channel, channels on
    ``axis`` of a 4-d tensor, and the axes the statistics run over."""
    rng = np.random.RandomState(seed)
    shape = (16, 8, 14, 14) if axis == 1 else (16, 14, 14, 8)
    x = rng.normal(ratio, 1.0, shape).astype(np.float32)
    return x, tuple(i for i in range(4) if i != axis % 4)


def _bn_two_pass(attrs, x, gamma, beta):
    """The formula BatchNorm had before PR 26 (jnp.mean, then the two-pass
    jnp.var), kept here as the reference for gradients and for the count."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import nn_ops
    axis = int(attrs.get("axis", 1)) % x.ndim
    axes = tuple(i for i in range(x.ndim) if i != axis)
    mean, var = jnp.mean(x, axis=axes), jnp.var(x, axis=axes)
    return (nn_ops._bn_apply(attrs, x, gamma, beta, mean, var), mean,
            1.0 / jnp.sqrt(var + attrs["eps"]))


def _bn_one_pass(attrs, x, gamma, beta):
    """The op itself, with a running mean 0.5 std off _bn_grad_args' data."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import nn_ops
    c = x.shape[int(attrs.get("axis", 1))]
    return nn_ops._batch_norm(attrs, x, gamma, beta,
                              jnp.full((c,), 2.5, x.dtype), jnp.ones((c,)))


def _bn_check_stats(axis, ratio, tracked, rtol_var):
    """(a) out, mean and invstd against a float64 two-pass reckoning."""
    eps = 1e-3
    x, axes = _bn_data(ratio, axis)
    x64 = x.astype(np.float64)
    mean64, var64 = x64.mean(axis=axes), x64.var(axis=axes)
    gamma = np.linspace(0.5, 1.5, 8).astype(np.float32)
    beta = np.linspace(-1, 1, 8).astype(np.float32)
    # a running mean that tracks the batch mean to 0.3 std, or a fresh one
    moving_mean = (mean64 + 0.3 if tracked else np.zeros(8)).astype(np.float32)
    with autograd.record(train_mode=True):
        out, m, inv = nd.BatchNorm(nd.array(x), nd.array(gamma),
                                   nd.array(beta), nd.array(moving_mean),
                                   nd.ones((8,)), fix_gamma=False, eps=eps,
                                   axis=axis)
    var = 1.0 / inv.asnumpy().astype(np.float64) ** 2 - eps
    assert np.max(np.abs(var - var64) / var64) < rtol_var
    assert_almost_equal(m.asnumpy(), mean64, rtol=1e-5, atol=1e-4)
    bshape = [1, 1, 1, 1]
    bshape[axis] = 8
    want = ((x64 - mean64.reshape(bshape)) / np.sqrt(var64 + eps).reshape(bshape)
            * gamma.reshape(bshape) + beta.reshape(bshape))
    assert_almost_equal(out.asnumpy(), want, rtol=1e-3,
                        atol=max(1e-4, 4 * rtol_var))


def _bn_check_constant():
    """(a) a constant input has variance 0, never below: the clamp."""
    eps = 1e-3
    x = np.full((16, 8, 14, 14), 1234.567, np.float32)
    with autograd.record(train_mode=True):
        out, m, inv = nd.BatchNorm(nd.array(x), nd.ones((8,)), nd.zeros((8,)),
                                   nd.zeros((8,)), nd.ones((8,)),
                                   fix_gamma=False, eps=eps)
    var = 1.0 / inv.asnumpy().astype(np.float64) ** 2 - eps
    assert np.all(np.isfinite(inv.asnumpy())) and np.all(var >= -1e-9)
    assert np.all(np.isfinite(out.asnumpy()))


def _bn_check_bfloat16():
    """(b) bfloat16 data: the sums run in float32, so the variance is that
    of the rounded input to 1e-3; a bfloat16 mean alone (8 bits, |mean|/std
    3) would miss by some 3%.  The op's outputs keep the data's dtype."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import nn_ops
    x, axes = _bn_data(3, 1)
    xb = jnp.asarray(x).astype(jnp.bfloat16)
    x32 = np.asarray(xb.astype(jnp.float32), np.float64)
    mean, var = nn_ops._bn_batch_stats(xb, 1, jnp.zeros((8,), jnp.bfloat16))
    assert mean.dtype == var.dtype == jnp.float32
    assert_almost_equal(np.asarray(var), x32.var(axis=axes), rtol=1e-3, atol=0)
    assert_almost_equal(np.asarray(mean), x32.mean(axis=axes), rtol=1e-5,
                        atol=1e-5)
    out, m, inv = nn_ops._batch_norm(
        {"_training": True, "fix_gamma": False, "eps": 1e-3}, xb,
        jnp.ones((8,), jnp.bfloat16), jnp.zeros((8,), jnp.bfloat16),
        jnp.zeros((8,), jnp.bfloat16), jnp.ones((8,), jnp.bfloat16))
    assert out.dtype == m.dtype == inv.dtype == jnp.bfloat16
    assert_almost_equal(np.asarray(inv.astype(jnp.float32)),
                        1 / np.sqrt(x32.var(axis=axes) + 1e-3), rtol=2 ** -7,
                        atol=0)


def _bn_grad_args(axis):
    import jax.numpy as jnp
    x, _ = _bn_data(3, axis, seed=1)
    rng = np.random.RandomState(2)
    args = (jnp.asarray(x), jnp.asarray(rng.uniform(0.5, 1.5, 8), jnp.float32),
            jnp.asarray(rng.uniform(-1, 1, 8), jnp.float32))
    # non-zero cotangents on all three outputs: out, mean and invstd
    cts = (jnp.asarray(rng.normal(size=x.shape), jnp.float32),
           jnp.asarray(rng.normal(size=8), jnp.float32),
           jnp.asarray(rng.normal(size=8), jnp.float32))
    return args, cts


def _bn_check_grad(axis):
    """(c) d/d(x, gamma, beta) against autodiff of the two-pass formula."""
    import jax
    attrs = {"_training": True, "fix_gamma": False, "eps": 1e-3, "axis": axis}
    args, cts = _bn_grad_args(axis)
    got = jax.vjp(lambda *a: _bn_one_pass(attrs, *a), *args)[1](cts)
    want = jax.vjp(lambda *a: _bn_two_pass(attrs, *a), *args)[1](cts)
    for g, w in zip(got, want):
        scale = float(np.max(np.abs(np.asarray(w))))
        assert_almost_equal(np.asarray(g), np.asarray(w), rtol=1e-4,
                            atol=2e-5 * scale)


def _bn_check_reduction_count():
    """(d) a count, which a CPU may report: the gradient of one
    training-mode BatchNorm with respect to its input reads the full-size
    tensor in 4 reductions (two sums forward; sum(g*inv) and sum(g*(x-mean))
    backward), where the two-pass formula needed 6 (mean, the mean inside
    var, the squares; and backward one more for the mean's gradient
    sum(x - mean), which is identically zero).  beta's gradient, sum(g),
    is one more in both."""
    import jax
    attrs = {"_training": True, "fix_gamma": False, "eps": 1e-3}
    args, cts = _bn_grad_args(1)

    def count(fn, wrt):
        """Full-size reductions in the gradient w.r.t. the first ``wrt``
        of (x, gamma, beta)."""
        text = jax.jit(lambda a, c: jax.vjp(
            lambda *lead: fn(attrs, *lead, *args[wrt:]), *a)[1](c)
        ).lower(args[:wrt], cts).as_text()
        return sum(1 for line in text.splitlines()
                   if "stablehlo.reduce" in line
                   and "(tensor<16x8x14x14xf32>" in line)

    assert count(_bn_two_pass, 1) == 6      # the count itself counts
    assert count(_bn_one_pass, 1) == 4
    assert count(_bn_two_pass, 3) == 7
    assert count(_bn_one_pass, 3) == 5


@pytest.mark.parametrize("check", [
    pytest.param(lambda: _bn_check_stats(1, 0, True, 1e-4), id="stats-nchw-0"),
    pytest.param(lambda: _bn_check_stats(1, 3, True, 1e-4), id="stats-nchw-3"),
    pytest.param(lambda: _bn_check_stats(1, 30, True, 1e-3), id="stats-nchw-30"),
    pytest.param(lambda: _bn_check_stats(-1, 0, True, 1e-4), id="stats-last-0"),
    pytest.param(lambda: _bn_check_stats(-1, 3, True, 1e-4), id="stats-last-3"),
    pytest.param(lambda: _bn_check_stats(-1, 30, True, 1e-3), id="stats-last-30"),
    # a fresh layer's running mean is 0: the textbook formula's cancellation
    pytest.param(lambda: _bn_check_stats(1, 3, False, 1e-3),
                 id="stats-nchw-3-fresh"),
    pytest.param(lambda: _bn_check_stats(1, 30, False, 2e-2),
                 id="stats-nchw-30-fresh"),
    pytest.param(_bn_check_constant, id="stats-constant"),
    pytest.param(_bn_check_bfloat16, id="bfloat16"),
    pytest.param(lambda: _bn_check_grad(1), id="grad-nchw"),
    pytest.param(lambda: _bn_check_grad(-1), id="grad-last"),
    pytest.param(_bn_check_reduction_count, id="reduction-count"),
])
def test_batchnorm_one_pass(check):
    """Training-mode BatchNorm takes its batch statistics in one pass
    (ops/nn_ops.py _bn_batch_stats)."""
    check()


def test_activation_ops():
    x = np.random.uniform(-2, 2, (3, 4)).astype(np.float32)
    a = nd.array(x)
    assert_almost_equal(nd.relu(a).asnumpy(), np.maximum(x, 0))
    assert_almost_equal(nd.sigmoid(a).asnumpy(), 1 / (1 + np.exp(-x)), rtol=1e-4)
    assert_almost_equal(nd.tanh(a).asnumpy(), np.tanh(x), rtol=1e-4)
    assert_almost_equal(nd.Activation(a, act_type="softrelu").asnumpy(),
                        np.log1p(np.exp(x)), rtol=1e-4, atol=1e-5)
    assert_almost_equal(nd.LeakyReLU(a, act_type="leaky", slope=0.1).asnumpy(),
                        np.where(x > 0, x, 0.1 * x), rtol=1e-5)


def test_softmax():
    x = np.random.uniform(-1, 1, (3, 5)).astype(np.float32)
    out = nd.softmax(nd.array(x))
    e = np.exp(x - x.max(1, keepdims=True))
    assert_almost_equal(out.asnumpy(), e / e.sum(1, keepdims=True), rtol=1e-4)
    lout = nd.log_softmax(nd.array(x))
    assert_almost_equal(lout.asnumpy(), np.log(e / e.sum(1, keepdims=True)),
                        rtol=1e-3, atol=1e-5)


def test_dropout_modes():
    x = nd.ones((100, 100))
    # inference: identity
    out = nd.Dropout(x, p=0.5)
    assert_almost_equal(out.asnumpy(), x.asnumpy())
    # training: ~half zeroed, scaled
    with autograd.record():
        out = nd.Dropout(x, p=0.5)
    frac = (out.asnumpy() == 0).mean()
    assert 0.3 < frac < 0.7
    nz = out.asnumpy()[out.asnumpy() != 0]
    assert_almost_equal(nz, np.full_like(nz, 2.0))


def test_sequence_mask():
    x = nd.ones((4, 2, 3))  # (T, B, ...)
    lengths = nd.array([2, 3])
    out = nd.SequenceMask(x, lengths, use_sequence_length=True, value=0.0)
    out_np = out.asnumpy()
    assert out_np[:2, 0].sum() == 6
    assert out_np[2:, 0].sum() == 0
    assert out_np[:3, 1].sum() == 9
    assert out_np[3:, 1].sum() == 0


def test_sequence_last_reverse():
    x = nd.array(np.arange(24).reshape(4, 2, 3))
    lengths = nd.array([2, 4])
    last = nd.SequenceLast(x, lengths, use_sequence_length=True)
    assert_almost_equal(last.asnumpy()[0], x.asnumpy()[1, 0])
    assert_almost_equal(last.asnumpy()[1], x.asnumpy()[3, 1])
    rev = nd.SequenceReverse(x, lengths, use_sequence_length=True)
    assert_almost_equal(rev.asnumpy()[0, 0], x.asnumpy()[1, 0])
    assert_almost_equal(rev.asnumpy()[1, 0], x.asnumpy()[0, 0])
    assert_almost_equal(rev.asnumpy()[2, 0], x.asnumpy()[2, 0])


def test_embedding():
    data = nd.array([[0, 2], [1, 3]], dtype="int32")
    weight = nd.array(np.random.uniform(-1, 1, (4, 5)))
    out = nd.Embedding(data, weight, input_dim=4, output_dim=5)
    assert out.shape == (2, 2, 5)
    assert_almost_equal(out.asnumpy()[0, 1], weight.asnumpy()[2])


def test_topk_sort():
    x = nd.array([[3.0, 1.0, 2.0], [0.5, 2.5, 1.5]])
    idx = nd.topk(x, k=2)
    assert_almost_equal(idx.asnumpy(), [[0, 2], [1, 2]])
    vals = nd.topk(x, k=2, ret_typ="value")
    assert_almost_equal(vals.asnumpy(), [[3, 2], [2.5, 1.5]])
    s = nd.sort(x, axis=1)
    assert_almost_equal(s.asnumpy(), np.sort(x.asnumpy(), axis=1))
    a = nd.argsort(x, axis=1)
    assert_almost_equal(a.asnumpy(), np.argsort(x.asnumpy(), axis=1))


def test_numeric_gradient_fc():
    check_numeric_gradient(
        lambda x, w: nd.FullyConnected(x, w, num_hidden=3, no_bias=True),
        [np.random.uniform(-1, 1, (2, 4)), np.random.uniform(-1, 1, (3, 4))],
        rtol=1e-2, atol=1e-3)


def test_numeric_gradient_conv():
    check_numeric_gradient(
        lambda x, w: nd.Convolution(x, w, kernel=(2, 2), num_filter=2,
                                    no_bias=True),
        [np.random.uniform(-1, 1, (1, 2, 4, 4)),
         np.random.uniform(-1, 1, (2, 2, 2, 2))],
        rtol=2e-2, atol=1e-3)


def test_numeric_gradient_elemwise():
    check_numeric_gradient(lambda x: nd.tanh(x) * nd.sigmoid(x),
                           [np.random.uniform(-1, 1, (3, 3))],
                           rtol=1e-2, atol=1e-3)


def test_layernorm():
    x = np.random.uniform(-1, 1, (2, 5)).astype(np.float32)
    g = np.random.uniform(0.5, 1.5, (5,)).astype(np.float32)
    b = np.random.uniform(-0.5, 0.5, (5,)).astype(np.float32)
    out = nd.LayerNorm(nd.array(x), nd.array(g), nd.array(b))
    mean = x.mean(-1, keepdims=True)
    std = np.sqrt(x.var(-1, keepdims=True) + 1e-5)
    assert_almost_equal(out.asnumpy(), (x - mean) / std * g + b, rtol=1e-4,
                        atol=1e-5)


def test_rnn_fused_shapes():
    T, B, I, H = 5, 3, 4, 6
    x = nd.array(np.random.uniform(-1, 1, (T, B, I)))
    nparams = (I * 4 * H + H * 4 * H) + 2 * 4 * H
    params = nd.array(np.random.uniform(-0.1, 0.1, (nparams,)))
    h0 = nd.zeros((1, B, H))
    c0 = nd.zeros((1, B, H))
    out = nd.RNN(x, params, h0, c0, state_size=H, num_layers=1, mode="lstm",
                 state_outputs=True)
    y, hT, cT = out
    assert y.shape == (T, B, H)
    assert hT.shape == (1, B, H)
    assert cT.shape == (1, B, H)


def test_optimizer_ops():
    w = nd.array([1.0, 2.0])
    g = nd.array([0.1, 0.2])
    out = nd.sgd_update(w, g, lr=0.5, wd=0.0)
    assert_almost_equal(out.asnumpy(), [0.95, 1.9], rtol=1e-5)
    mom = nd.zeros((2,))
    w2, m2 = nd.sgd_mom_update(w, g, mom, lr=0.5, momentum=0.9, wd=0.0)
    assert_almost_equal(w2.asnumpy(), [0.95, 1.9], rtol=1e-5)


def test_linalg():
    a = np.random.uniform(-1, 1, (3, 3)).astype(np.float32)
    spd = a.dot(a.T) + 3 * np.eye(3, dtype=np.float32)
    L = nd.linalg.potrf(nd.array(spd))
    assert_almost_equal(L.asnumpy().dot(L.asnumpy().T), spd, rtol=1e-3, atol=1e-4)
    g = nd.linalg.gemm2(nd.array(a), nd.array(a), transpose_b=True)
    assert_almost_equal(g.asnumpy(), a.dot(a.T), rtol=1e-4, atol=1e-5)


def test_random_ops():
    mx.random.seed(42)
    a = nd.random.uniform(0, 1, shape=(1000,))
    assert 0.4 < a.asnumpy().mean() < 0.6
    b = nd.random.normal(0, 1, shape=(1000,))
    assert abs(b.asnumpy().mean()) < 0.2
    mx.random.seed(42)
    a2 = nd.random.uniform(0, 1, shape=(1000,))
    assert_almost_equal(a.asnumpy(), a2.asnumpy())
    c = nd.random.randint(0, 10, shape=(100,))
    assert c.asnumpy().min() >= 0 and c.asnumpy().max() < 10


def test_where_clip():
    x = nd.array([-1.0, 0.5, 2.0])
    assert_almost_equal(nd.clip(x, -0.5, 1.0).asnumpy(), [-0.5, 0.5, 1.0])
    cond = nd.array([1.0, 0.0, 1.0])
    out = nd.where(cond, x, nd.zeros((3,)))
    assert_almost_equal(out.asnumpy(), [-1.0, 0.0, 2.0])


def test_pick():
    x = nd.array([[1.0, 2.0, 3.0], [4.0, 5.0, 6.0]])
    idx = nd.array([0, 2])
    out = nd.pick(x, idx, axis=1)
    assert_almost_equal(out.asnumpy(), [1.0, 6.0])


def test_upsampling():
    x = nd.array(np.arange(4).reshape(1, 1, 2, 2))
    out = nd.UpSampling(x, scale=2, sample_type="nearest")
    assert out.shape == (1, 1, 4, 4)
    assert_almost_equal(out.asnumpy()[0, 0, :2, :2],
                        [[0, 0], [0, 0]])


def test_deconvolution_shape():
    x = nd.array(np.random.uniform(-1, 1, (1, 3, 4, 4)))
    w = nd.array(np.random.uniform(-1, 1, (3, 2, 3, 3)))
    out = nd.Deconvolution(x, w, kernel=(3, 3), num_filter=2, stride=(2, 2))
    assert out.shape == (1, 2, 9, 9)


def test_adagrad_wd_outside_history():
    """wd must NOT enter the AdaGrad history (reference optimizer.py:
    history += grad^2; update adds wd*weight outside)."""
    from mxnet_tpu import optimizer as opt
    w_np = np.array([1.0, -2.0, 3.0], np.float32)
    g_np = np.array([0.1, 0.2, -0.3], np.float32)
    lr, wd, eps = 0.5, 0.1, 1e-7
    ada = opt.create("adagrad", learning_rate=lr, wd=wd, eps=eps)
    w = nd.array(w_np)
    state = ada.create_state(0, w)
    ada.update(0, w, nd.array(g_np), state)
    hist = g_np * g_np
    expect = w_np - lr * (g_np / np.sqrt(hist + eps) + wd * w_np)
    np.testing.assert_allclose(w.asnumpy(), expect, rtol=1e-5)
    np.testing.assert_allclose(state.asnumpy(), hist, rtol=1e-6)


def test_signum_wd_inside_momentum():
    """wd folds into the Signum momentum (reference SignumKernel)."""
    from mxnet_tpu import optimizer as opt
    w_np = np.array([1.0, -2.0, 0.5], np.float32)
    g_np = np.array([0.3, -0.1, 0.2], np.float32)
    lr, wd, mom_c = 0.1, 0.05, 0.9
    sgn = opt.create("signum", learning_rate=lr, momentum=mom_c, wd=wd)
    w = nd.array(w_np)
    state = sgn.create_state(0, w)
    sgn.update(0, w, nd.array(g_np), state)
    mom = -(1 - mom_c) * wd * w_np - (1 - mom_c) * g_np
    expect = w_np + lr * np.sign(mom)
    np.testing.assert_allclose(w.asnumpy(), expect, rtol=1e-5)
    np.testing.assert_allclose(state.asnumpy(), mom, rtol=1e-5)


def test_topk_mask():
    x = nd.array(np.array([[1.0, 5.0, 3.0, 2.0],
                           [9.0, 0.0, 4.0, 7.0]], np.float32))
    m = nd.topk(x, k=2, ret_typ="mask").asnumpy()
    np.testing.assert_array_equal(m, [[0, 1, 1, 0], [1, 0, 0, 1]])
    # along axis 0
    m0 = nd.topk(x, axis=0, k=1, ret_typ="mask").asnumpy()
    np.testing.assert_array_equal(m0, [[0, 1, 0, 0], [1, 0, 1, 1]])


def test_conv_pool_nhwc_layout_matches_nchw():
    """layout='NHWC' conv/pool equal the channel-first results — the
    TPU-preferred layout path (convolution.cc layout parameter)."""
    rng = np.random.RandomState(0)
    x = rng.normal(0, 1, (2, 8, 8, 3)).astype(np.float32)     # NHWC
    w = rng.normal(0, 1, (4, 3, 3, 3)).astype(np.float32)     # OHWI
    b = rng.normal(0, 1, (4,)).astype(np.float32)
    out_cl = nd.Convolution(nd.array(x), nd.array(w), nd.array(b),
                            kernel=(3, 3), pad=(1, 1), num_filter=4,
                            layout="NHWC").asnumpy()
    x_cf = x.transpose(0, 3, 1, 2)
    w_cf = w.transpose(0, 3, 1, 2)
    out_cf = nd.Convolution(nd.array(x_cf), nd.array(w_cf), nd.array(b),
                            kernel=(3, 3), pad=(1, 1), num_filter=4).asnumpy()
    np.testing.assert_allclose(out_cl.transpose(0, 3, 1, 2), out_cf,
                               rtol=1e-4, atol=1e-4)

    p_cl = nd.Pooling(nd.array(x), kernel=(2, 2), stride=(2, 2),
                      pool_type="max", layout="NHWC").asnumpy()
    p_cf = nd.Pooling(nd.array(x_cf), kernel=(2, 2), stride=(2, 2),
                      pool_type="max").asnumpy()
    np.testing.assert_allclose(p_cl.transpose(0, 3, 1, 2), p_cf, rtol=1e-5)

    g_cl = nd.Pooling(nd.array(x), global_pool=True, kernel=(1, 1),
                      pool_type="avg", layout="NHWC").asnumpy()
    g_cf = nd.Pooling(nd.array(x_cf), global_pool=True, kernel=(1, 1),
                      pool_type="avg").asnumpy()
    np.testing.assert_allclose(g_cl.transpose(0, 3, 1, 2), g_cf, rtol=1e-5)
