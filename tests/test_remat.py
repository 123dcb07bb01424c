"""Backward-mirroring / rematerialization (reference: MXNET_BACKWARD_DO_MIRROR,
docs/faq/env_var.md:140-145 and docs/architecture/note_memory.md — re-execute
cheap forward ops during backward to shed activation memory).

TPU analog: ``hybridize(remat=True)`` (or the env knob) wraps the CachedOp's
traced forward in ``jax.checkpoint`` so the compiled vjp recomputes
activations instead of saving them.  Same math, less HBM."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd
from mxnet_tpu.gluon import nn


def _make_net(remat=None, seed=3):
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu"))
        net.add(nn.Dense(32, activation="relu"))
        net.add(nn.Dense(4))
    mx.random.seed(seed)  # init draws from the framework stream (round 5)
    net.initialize(mx.init.Xavier(), force_reinit=True)
    flags = {} if remat is None else {"remat": remat}
    net.hybridize(**flags)
    return net


def _grads(net, x_np):
    x = nd.array(x_np)
    net(x)  # materialize deferred shapes
    with autograd.record():
        out = net(x)
        loss = (out * out).sum()
    loss.backward()
    return {n[len(net.prefix):]: p.grad().asnumpy()
            for n, p in net.collect_params().items()}


def _forward_jaxpr(net, x):
    """The jaxpr (as text) of the CachedOp's forward as it is lowered, the
    remat policy applied."""
    import jax
    co = net._cached_op
    vals = tuple(net._cached_params[n].data()._data
                 for n in co._param_names) + (x._data, jax.random.PRNGKey(0))
    return str(jax.make_jaxpr(co._make_lowerable(training=True))(*vals))


def test_remat_grads_match():
    rng = np.random.RandomState(0)
    x = rng.uniform(-1, 1, (8, 16)).astype(np.float32)
    g_plain = _grads(_make_net(remat=None), x)
    g_remat = _grads(_make_net(remat=True), x)
    assert set(g_plain) == set(g_remat)
    for name in g_plain:
        # same math, but remat changes XLA's fusion schedule, so the last
        # float bit can differ — tight tolerance, not bitwise
        np.testing.assert_allclose(g_plain[name], g_remat[name],
                                   rtol=1e-6, atol=1e-6, err_msg=name)


def test_remat_appears_in_jaxpr():
    net = _make_net(remat=True)
    x = nd.zeros((2, 16))
    net(x)  # builds the CachedOp
    assert "remat" in _forward_jaxpr(net, x), \
        "jax.checkpoint not applied to the forward"
    # and the plain build must NOT carry it
    net2 = _make_net(remat=None)
    net2(x)
    assert "remat" not in _forward_jaxpr(net2, x)


def test_remat_env_knob(monkeypatch):
    """MXNET_BACKWARD_DO_MIRROR=1 turns remat on without a per-block flag."""
    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
    net = _make_net(remat=None)
    x = nd.zeros((2, 16))
    net(x)
    assert "remat" in _forward_jaxpr(net, x)


def test_remat_policy_knob():
    """Named jax.checkpoint_policies select what is still saved; bad names
    error out with the available surface."""
    from mxnet_tpu.base import MXNetError
    net = _make_net(remat=True)
    net.hybridize(remat=True, remat_policy="dots_saveable")
    x = nd.zeros((2, 16))
    out = net(x)
    assert out.shape == (2, 4)
    net.hybridize(remat=True, remat_policy="not_a_policy")
    with pytest.raises(MXNetError):
        net(x)


@pytest.mark.parametrize("asked", [("attn.out", 3), ["attn.out"], 3,
                                   ("attn.out", ("attn.lse",))])
def test_remat_policy_neither_a_name_nor_a_tuple_of_names(asked):
    from mxnet_tpu.base import MXNetError
    net = _make_net(remat=True)
    x = nd.zeros((2, 16))
    net.hybridize(remat=True, remat_policy=asked)
    with pytest.raises(MXNetError, match="unknown remat policy"):
        net(x)


def test_remat_policy_tuple_of_names_for_a_cached_op():
    """A tuple of names is save_only_these_names: the CachedOp's forward is
    still wrapped, and with no value under such a name the gradients are
    those of plain recomputation, to the bit."""
    rng = np.random.RandomState(0)
    x_np = rng.uniform(-1, 1, (8, 16)).astype(np.float32)
    net = _make_net(remat=True)
    net.hybridize(remat=True, remat_policy=("attn.out", "attn.lse"))
    g_names = _grads(net, x_np)
    g_remat = _grads(_make_net(remat=True), x_np)
    for name in g_remat:
        np.testing.assert_array_equal(g_names[name], g_remat[name], name)
    jaxpr = _forward_jaxpr(net, nd.array(x_np))
    assert "remat" in jaxpr and "save_only_these_names" in jaxpr


class _NamesItsHidden(mx.gluon.HybridBlock):
    """Two dense layers; the first one's output goes through
    ``checkpoint_name`` as "hidden"."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.first = nn.Dense(32, activation="relu", in_units=16)
            self.second = nn.Dense(32, activation="relu", in_units=32)

    def hybrid_forward(self, F, x):
        from jax.ad_checkpoint import checkpoint_name
        hidden = self.first(x)
        return self.second(nd.NDArray(checkpoint_name(hidden._data,
                                                      "hidden")))


def _eqns(jaxpr):
    import jax
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_remat_policy_tuple_of_names_for_a_child_block_inside_a_trace():
    """A hybridized child called inside someone else's trace keeps the value
    its forward named and recomputes the rest: one matrix product fewer in
    the gradient's jaxpr than with a name nothing carries, and the same
    gradients to the bit."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.gluon.block import functional_call
    net = nn.HybridSequential()
    with net.name_scope():
        child = _NamesItsHidden()
        net.add(child)
        net.add(nn.Dense(4, in_units=32))
    mx.random.seed(5)
    net.initialize(mx.init.Xavier(), force_reinit=True)
    values = {k: p.data()._data for k, p in net.collect_params().items()}
    x = jnp.asarray(np.random.RandomState(2).uniform(-1, 1, (8, 16)),
                    jnp.float32)

    def gradient(names):
        child.hybridize(remat=True, remat_policy=names)

        def f(v):       # a new function each time: jax caches traces by it
            return jnp.sum(functional_call(net, v, x, training=True)[0][0]
                           ** 2)
        jaxpr = jax.make_jaxpr(jax.grad(f))(values).jaxpr
        return jax.grad(f)(values), sum(
            eqn.primitive.name == "dot_general" for eqn in _eqns(jaxpr))

    kept, kept_products = gradient(("hidden",))
    plain, plain_products = gradient(("nothing_has_this_name",))
    assert kept_products == plain_products - 1
    assert gradient(None)[1] == plain_products
    for name in plain:
        np.testing.assert_array_equal(np.asarray(kept[name]),
                                      np.asarray(plain[name]), name)


def test_remat_convnet_bitwise():
    """Conv+BN net (aux state threaded) under remat: grads and updated
    running stats match the plain path to float precision."""
    rng = np.random.RandomState(1)
    x_np = rng.uniform(-1, 1, (2, 3, 16, 16)).astype(np.float32)

    def build(remat):
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Conv2D(8, 3, padding=1))
            net.add(nn.BatchNorm())
            net.add(nn.Activation("relu"))
            net.add(nn.GlobalAvgPool2D())
            net.add(nn.Dense(4))
        mx.random.seed(11)
        net.initialize(mx.init.Xavier(), force_reinit=True)
        net.hybridize(**({"remat": True} if remat else {}))
        return net

    results = {}
    for remat in (False, True):
        net = build(remat)
        x = nd.array(x_np)
        net(x)
        with autograd.record():
            loss = (net(x) ** 2).sum()
        loss.backward()
        results[remat] = {
            n[len(net.prefix):]: (p.grad().asnumpy() if p.grad_req != "null"
                                  else p.data().asnumpy())
            for n, p in net.collect_params().items()}
    for name in results[False]:
        np.testing.assert_allclose(results[False][name], results[True][name],
                                   rtol=1e-6, atol=1e-6, err_msg=name)
