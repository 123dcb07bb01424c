"""NDArray basics (model: reference tests/python/unittest/test_ndarray.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.test_utils import assert_almost_equal, default_context


def test_creation():
    a = nd.zeros((3, 4))
    assert a.shape == (3, 4)
    assert a.dtype == np.float32
    b = nd.ones((2, 3), dtype="int32")
    assert b.asnumpy().sum() == 6
    c = nd.array([[1, 2], [3, 4]])
    assert_almost_equal(c.asnumpy(), np.array([[1, 2], [3, 4]], dtype=np.float32))
    d = nd.full((2, 2), 7.5)
    assert d.asnumpy().flat[0] == 7.5
    e = nd.arange(0, 10, 2)
    assert_almost_equal(e.asnumpy(), np.arange(0, 10, 2, dtype=np.float32))


def test_elementwise():
    a = nd.array([1.0, 2.0, 3.0])
    b = nd.array([4.0, 5.0, 6.0])
    assert_almost_equal((a + b).asnumpy(), [5, 7, 9])
    assert_almost_equal((a - b).asnumpy(), [-3, -3, -3])
    assert_almost_equal((a * b).asnumpy(), [4, 10, 18])
    assert_almost_equal((b / a).asnumpy(), [4, 2.5, 2])
    assert_almost_equal((a + 1).asnumpy(), [2, 3, 4])
    assert_almost_equal((1 + a).asnumpy(), [2, 3, 4])
    assert_almost_equal((2 - a).asnumpy(), [1, 0, -1])
    assert_almost_equal((a ** 2).asnumpy(), [1, 4, 9])
    assert_almost_equal((-a).asnumpy(), [-1, -2, -3])


def test_inplace():
    a = nd.ones((2, 2))
    a += 1
    assert_almost_equal(a.asnumpy(), np.full((2, 2), 2.0))
    a *= 3
    assert_almost_equal(a.asnumpy(), np.full((2, 2), 6.0))
    a[:] = 1.5
    assert_almost_equal(a.asnumpy(), np.full((2, 2), 1.5))


def test_indexing():
    a = nd.array(np.arange(12).reshape(3, 4))
    assert a[1].shape == (4,)
    assert_almost_equal(a[1].asnumpy(), [4, 5, 6, 7])
    assert a[1:3].shape == (2, 4)
    assert a[1, 2].asscalar() == 6
    a[0, 0] = 100.0
    assert a[0, 0].asscalar() == 100.0
    # view write-back
    v = a[2]
    v[:] = 0
    assert a[2].asnumpy().sum() == 0


def test_reshape_transpose():
    a = nd.array(np.arange(24).reshape(2, 3, 4))
    assert a.reshape((6, 4)).shape == (6, 4)
    assert a.reshape((-1, 4)).shape == (6, 4)
    assert a.reshape((0, -1)).shape == (2, 12)
    assert a.transpose().shape == (4, 3, 2)
    assert a.T.shape == (4, 3, 2)
    assert a.flatten().shape == (2, 12)
    assert a.expand_dims(0).shape == (1, 2, 3, 4)


def test_reduce():
    a = nd.array(np.arange(12).reshape(3, 4))
    assert a.sum().shape == (1,)
    assert a.sum().asscalar() == 66
    assert a.sum(axis=0).shape == (4,)
    assert a.mean(axis=1).shape == (3,)
    assert a.max().asscalar() == 11
    assert a.min().asscalar() == 0
    assert abs(a.norm().asscalar() - np.linalg.norm(np.arange(12))) < 1e-4


def test_dot():
    a = nd.array(np.random.uniform(size=(3, 4)))
    b = nd.array(np.random.uniform(size=(4, 5)))
    c = nd.dot(a, b)
    assert c.shape == (3, 5)
    assert_almost_equal(c.asnumpy(), a.asnumpy().dot(b.asnumpy()), rtol=1e-4)
    d = nd.dot(a, a, transpose_b=True)
    assert d.shape == (3, 3)


def test_comparison():
    a = nd.array([1.0, 2.0, 3.0])
    b = nd.array([3.0, 2.0, 1.0])
    assert_almost_equal((a == b).asnumpy(), [0, 1, 0])
    assert_almost_equal((a > b).asnumpy(), [0, 0, 1])
    assert_almost_equal((a >= 2).asnumpy(), [0, 1, 1])


def test_concat_stack_split():
    a = nd.ones((2, 3))
    b = nd.zeros((2, 3))
    c = nd.concat(a, b, dim=0)
    assert c.shape == (4, 3)
    s = nd.stack(a, b, axis=0)
    assert s.shape == (2, 2, 3)
    parts = nd.SliceChannel(c, num_outputs=2, axis=0)
    assert parts[0].shape == (2, 3)
    assert_almost_equal(parts[0].asnumpy(), np.ones((2, 3)))


def test_save_load(tmp_path):
    fname = str(tmp_path / "arrays.npz")
    a = nd.array(np.random.uniform(size=(3, 4)))
    b = nd.array(np.random.uniform(size=(5,)))
    nd.save(fname, {"a": a, "b": b})
    loaded = nd.load(fname)
    assert set(loaded.keys()) == {"a", "b"}
    assert_almost_equal(loaded["a"].asnumpy(), a.asnumpy())
    nd.save(fname, [a, b])
    loaded = nd.load(fname)
    assert isinstance(loaded, list)
    assert_almost_equal(loaded[1].asnumpy(), b.asnumpy())


def test_astype_copy():
    a = nd.ones((2, 2))
    b = a.astype("int32")
    assert b.dtype == np.int32
    c = a.copy()
    c[:] = 5
    assert a.asnumpy().sum() == 4


def test_take_onehot():
    a = nd.array(np.arange(20).reshape(4, 5))
    idx = nd.array([0, 2], dtype="int32")
    t = nd.take(a, idx)
    assert t.shape == (2, 5)
    oh = nd.one_hot(nd.array([1, 0, 2], dtype="int32"), 3)
    assert_almost_equal(oh.asnumpy(), np.eye(3)[[1, 0, 2]])


def test_broadcast():
    a = nd.ones((1, 3))
    b = a.broadcast_to((4, 3))
    assert b.shape == (4, 3)
    c = nd.ones((2, 1)) + nd.ones((1, 3))
    assert c.shape == (2, 3)


def test_wait_to_read():
    a = nd.ones((100, 100))
    b = nd.dot(a, a)
    b.wait_to_read()
    assert b.asnumpy()[0, 0] == 100


def test_view_observes_base_mutation():
    """Basic-index views alias bidirectionally (reference NDArray shares the
    Chunk): mutating the base must be visible through existing views."""
    x = nd.arange(12).reshape((3, 4))
    y = x[0]
    np.testing.assert_allclose(y.asnumpy(), [0, 1, 2, 3])
    x[:] = 0
    np.testing.assert_allclose(y.asnumpy(), [0, 0, 0, 0])
    # and write-through still works
    y[:] = 7
    np.testing.assert_allclose(x.asnumpy()[0], [7, 7, 7, 7])
    np.testing.assert_allclose(x.asnumpy()[1:], 0)


def test_waitall_fences_pending_work():
    x = nd.ones((64, 64))
    for _ in range(5):
        x = nd.dot(x, x) * 1e-3
    nd.waitall()  # must not raise and must leave x fully materialized
    assert np.isfinite(x.asnumpy()).all()


def test_nested_view_observes_base_mutation():
    x = nd.arange(12).reshape((3, 4))
    y = x[0:2]
    z = y[0]
    x[:] = 0
    np.testing.assert_allclose(z.asnumpy(), 0)


# ---------------------------------------------------------------------------
# device contexts: one default for the process, no stand-in devices
# ---------------------------------------------------------------------------

def test_default_context_is_jax_default_backends_first_device():
    import jax
    ctx = mx.current_context()
    assert ctx.jax_device() == jax.local_devices()[0]
    assert nd.zeros((2,))._data.devices() == {jax.local_devices()[0]}
    # ... and what takes no context takes that default
    assert mx.mod.Module(mx.sym.var("data") * 2, label_names=None)._context \
        == [ctx]


def test_new_thread_sees_the_same_default_context():
    import threading
    seen = {}

    def look(key):
        seen[key] = (mx.current_context(),
                     nd.ones((1,))._data.devices())

    t = threading.Thread(target=look, args=("plain",))
    t.start()
    t.join(10)
    assert not t.is_alive()
    assert seen["plain"][0] == mx.current_context()
    # a `with ctx:` block is the enclosing thread's own business
    with mx.cpu(3):
        t = threading.Thread(target=look, args=("scoped",))
        t.start()
        t.join(10)
        assert not t.is_alive()
        assert mx.current_context() == mx.cpu(3)
    assert seen["scoped"] == seen["plain"]
    assert mx.current_context() == seen["plain"][0]


@pytest.mark.parametrize("device_id", [0, 99])
def test_tpu_context_raises_without_that_chip(device_id):
    """A CPU-only process has no chip 0, and no process has chip 99: naming
    one is an error, never a CPU device or the last chip."""
    ctx = mx.tpu(device_id)
    with pytest.raises(mx.MXNetError, match="0 local accelerator"):
        ctx.jax_device()
    with pytest.raises(mx.MXNetError):
        nd.zeros((1,), ctx=ctx)
    with pytest.raises(mx.MXNetError):
        nd.zeros((1,), ctx=mx.gpu(device_id))


def test_decode_engines_hold_their_pools_on_the_device_asked_for():
    import jax
    from mxnet_tpu.serving.decode import DecodeEngine, TinyCausalLM
    engines = []
    try:
        for i in (2, 5):
            with mx.cpu(i):
                model = TinyCausalLM(vocab_size=20, hidden=16, num_layers=1,
                                     num_heads=2, max_len=24, seed=3)
                engines.append(DecodeEngine(
                    model, name="eng%d" % i, max_slots=2, block_size=4,
                    max_prompt_len=4, max_new_tokens=5, width_blocks=[4]))
        streams = [e.submit([1, 2, 3], max_new_tokens=4) for e in engines]
        for s in streams:
            assert s.wait(60) and s.status == "OK", (s.status, s.error)
        # same weights, same prompt: the device changes nothing else
        assert streams[0].tokens() == streams[1].tokens()
        for e, i in zip(engines, (2, 5)):
            want = jax.local_devices()[i]
            assert e.ctx == mx.cpu(i) and e.devices == (want,)
            assert e.routing_signals()["devices"] == [want.id]
            placed = e.placement()
            assert list(placed["params"]) == [want.id]
            assert list(placed["pools"]) == [want.id]
    finally:
        for e in engines:
            e.stop()
