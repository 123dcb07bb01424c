"""Tests for mxnet_tpu.parallel — the distribution layer that replaces the
reference's kvstore comm hierarchy (src/kvstore/comm.h) + ps-lite + NCCL
(SURVEY §2.5, §5).  Runs on the 8-device virtual CPU mesh from conftest."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu.parallel import (
    make_mesh, MeshConfig, data_parallel_spec, replicated_spec,
    allreduce, allgather, reduce_scatter, ppermute_ring,
    barrier_sync, axis_size,
    make_data_parallel_train_step, shard_batch,
    init_shard_update_state, padded_size, check_flat_state,
    ring_attention, sequence_parallel_attention)


def _ndev():
    return len(jax.devices())


# ---------------------------------------------------------------- mesh

def test_make_mesh_default_dp():
    mesh = make_mesh()
    assert mesh.axis_names == ("dp",)
    assert mesh.devices.size == _ndev()


def test_make_mesh_config_2d():
    n = _ndev()
    assert n >= 8, "conftest should provide 8 virtual devices"
    mesh = make_mesh(MeshConfig(dp=n // 2, tp=2))
    assert mesh.axis_names == ("dp", "tp")
    assert mesh.shape["tp"] == 2
    assert mesh.shape["dp"] == n // 2


def test_data_parallel_spec_places_batch_axis():
    mesh = make_mesh()
    sharding = data_parallel_spec(mesh)
    assert sharding.spec == P("dp")
    assert replicated_spec(mesh).spec == P()


# ---------------------------------------------------------- collectives

def _shmap(mesh, fn, in_spec, out_spec, *args):
    from jax import shard_map
    import functools
    wrapped = functools.partial(
        shard_map, mesh=mesh, in_specs=in_spec, out_specs=out_spec,
        check_vma=False)(fn)
    return wrapped(*args)


def test_allreduce_matches_sum_over_shards():
    n = _ndev()
    mesh = make_mesh()
    x = np.arange(n * 3, dtype=np.float32).reshape(n, 3)
    out = _shmap(mesh, lambda s: allreduce(s, "dp"), P("dp"), P("dp"), x)
    expected = np.tile(x.sum(axis=0), (n, 1))
    np.testing.assert_allclose(np.asarray(out), expected)


def test_allgather_reconstructs_global():
    n = _ndev()
    mesh = make_mesh()
    x = np.arange(n * 2, dtype=np.float32).reshape(n, 2)
    out = _shmap(mesh, lambda s: allgather(s, "dp", axis=0), P("dp"),
                 P("dp"), x)
    # each shard gathers the full array -> global result is n copies
    assert out.shape == (n * n, 2)
    np.testing.assert_allclose(np.asarray(out)[:n], x)


def test_reduce_scatter_is_sum_shard():
    n = _ndev()
    mesh = make_mesh()
    # each rank holds a full row of length n; psum_scatter leaves rank i with
    # element i of the sum
    x = np.ones((n, n), dtype=np.float32) * np.arange(n)[:, None]
    out = _shmap(mesh, lambda s: reduce_scatter(s[0], "dp")[None],
                 P("dp"), P("dp"), x)
    total = x.sum(axis=0)  # == arange-sum per column? rows identical: sum rows
    np.testing.assert_allclose(np.asarray(out).ravel(), total)


def test_ppermute_ring_rotates():
    n = _ndev()
    mesh = make_mesh()
    x = np.arange(n, dtype=np.float32).reshape(n, 1)
    out = _shmap(mesh, lambda s: ppermute_ring(s, "dp", shift=1),
                 P("dp"), P("dp"), x)
    # rank r receives the value of rank r-1
    np.testing.assert_allclose(np.asarray(out).ravel(),
                               np.roll(np.arange(n), 1))


def test_reduce_scatter_nondefault_scatter_dimension():
    """scatter_dimension=1: each rank keeps its own COLUMN block of the
    sum (the layout the flat [dp, padded] residual rows reduce along)."""
    n = _ndev()
    mesh = make_mesh()
    rng = np.random.RandomState(3)
    x = rng.randn(n, 2, n).astype(np.float32)
    out = _shmap(
        mesh, lambda s: reduce_scatter(s[0], "dp", scatter_dimension=1)[None],
        P("dp"), P("dp"), x)
    total = x.sum(axis=0)  # (2, n)
    got = np.asarray(out)  # (n, 2, 1): rank i holds column i of the sum
    for i in range(n):
        np.testing.assert_allclose(got[i, :, 0], total[:, i], rtol=1e-6)


def test_allgather_untiled_stacks_new_axis():
    """tiled=False keeps per-rank shards distinct along a NEW leading axis
    instead of concatenating — the debug-friendly layout for inspecting
    per-replica quantization codes."""
    n = _ndev()
    mesh = make_mesh()
    x = np.arange(n * 2, dtype=np.float32).reshape(n, 2)
    out = _shmap(mesh,
                 lambda s: allgather(s, "dp", tiled=False)[None],
                 P("dp"), P("dp"), x)
    got = np.asarray(out)
    assert got.shape == (n, n, 1, 2)
    for i in range(n):
        np.testing.assert_allclose(got[0, i, 0], x[i])


def test_ppermute_ring_wraparound_shifts():
    """shift wraps modulo the ring size, including negative shifts."""
    n = _ndev()
    mesh = make_mesh()
    x = np.arange(n, dtype=np.float32).reshape(n, 1)
    full = _shmap(mesh, lambda s: ppermute_ring(s, "dp", shift=n + 1),
                  P("dp"), P("dp"), x)
    # a full lap plus one == shift by one
    np.testing.assert_allclose(np.asarray(full).ravel(),
                               np.roll(np.arange(n), 1))
    back = _shmap(mesh, lambda s: ppermute_ring(s, "dp", shift=-1),
                  P("dp"), P("dp"), x)
    # rank r receives from rank r+1
    np.testing.assert_allclose(np.asarray(back).ravel(),
                               np.roll(np.arange(n), -1))
    lap = _shmap(mesh, lambda s: ppermute_ring(s, "dp", shift=n),
                 P("dp"), P("dp"), x)
    # a whole lap is the identity
    np.testing.assert_allclose(np.asarray(lap).ravel(), np.arange(n))


def test_axis_size_reports_dp_extent():
    n = _ndev()
    mesh = make_mesh()
    x = np.zeros((n, 1), np.float32)
    out = _shmap(mesh, lambda s: s + axis_size("dp"), P("dp"), P("dp"), x)
    np.testing.assert_allclose(np.asarray(out).ravel(), float(n))


def test_barrier_sync_single_host_is_noop():
    # single-process: must return promptly without raising
    assert barrier_sync() is None
    assert barrier_sync("named") is None


# ------------------------------------------------------- data parallel

def test_shard_batch_shards_leading_axis():
    mesh = make_mesh()
    n = _ndev()
    batch = (np.arange(n * 4, dtype=np.float32).reshape(n, 4),
             np.arange(n, dtype=np.int32))
    x, y = shard_batch(mesh, batch)
    assert isinstance(x.sharding, NamedSharding)
    assert x.sharding.spec == P("dp", None)
    np.testing.assert_allclose(np.asarray(x), batch[0])


def test_data_parallel_step_matches_single_device():
    """The compiled dp step must produce the same params as the plain
    single-device step on the same global batch (the reference's multi-GPU
    consistency property, tests/nightly/multi_lenet.py)."""
    n = _ndev()
    mesh = make_mesh()
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.normal(0, 0.1, (6, 4)).astype(np.float32)),
              "b": jnp.zeros((4,), jnp.float32)}
    batch_np = (rng.normal(0, 1, (n * 2, 6)).astype(np.float32),
                rng.normal(0, 1, (n * 2, 4)).astype(np.float32))

    def loss_fn(p, batch):
        x, y = batch
        pred = x @ p["w"] + p["b"]
        return jnp.mean((pred - y) ** 2)

    def sgd(grads, state, p):
        return ({k: p[k] - 0.1 * grads[k] for k in p}, state)

    step = make_data_parallel_train_step(loss_fn, sgd, mesh,
                                         donate_params=False)
    with mesh:
        new_p, _, loss = step(params, {}, shard_batch(mesh, batch_np))

    # single-device reference
    g = jax.grad(loss_fn)(params, batch_np)
    for k in params:
        np.testing.assert_allclose(np.asarray(new_p[k]),
                                   np.asarray(params[k] - 0.1 * g[k]),
                                   rtol=1e-5, atol=1e-6)
    assert np.isfinite(float(loss))


def test_data_parallel_step_with_tp_shardings():
    """param_shardings keeps a tp-sharded weight sharded through the step."""
    n = _ndev()
    mesh = make_mesh(MeshConfig(dp=n // 2, tp=2))
    rng = np.random.RandomState(1)
    params = {"w": jnp.asarray(rng.normal(0, 0.1, (6, 4)).astype(np.float32))}
    shardings = {"w": NamedSharding(mesh, P(None, "tp"))}
    params = {"w": jax.device_put(params["w"], shardings["w"])}

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((x @ p["w"] - y) ** 2)

    def sgd(grads, state, p):
        return ({k: p[k] - 0.1 * grads[k] for k in p}, state)

    step = make_data_parallel_train_step(loss_fn, sgd, mesh,
                                         donate_params=False,
                                         param_shardings=shardings)
    batch = shard_batch(mesh, (
        rng.normal(0, 1, (n, 6)).astype(np.float32),
        rng.normal(0, 1, (n, 4)).astype(np.float32)))
    with mesh:
        new_p, _, loss = step(params, {}, batch)
    assert new_p["w"].sharding.spec == P(None, "tp")
    assert np.isfinite(float(loss))


def test_data_parallel_loss_is_global_mean():
    """Loss returned equals the loss over the full (global) batch, not a
    single shard's."""
    n = _ndev()
    mesh = make_mesh()
    params = {"w": jnp.ones((1,), jnp.float32)}
    x = np.arange(n, dtype=np.float32).reshape(n, 1)

    def loss_fn(p, batch):
        return jnp.mean(batch * p["w"])

    def noop(grads, state, p):
        return p, state

    step = make_data_parallel_train_step(loss_fn, noop, mesh,
                                         donate_params=False)
    with mesh:
        _, _, loss = step(params, {}, shard_batch(mesh, x))
    np.testing.assert_allclose(float(loss), x.mean(), rtol=1e-6)


# ------------------------------------------------------ ring attention

@pytest.mark.parametrize("causal", [False, True])
def test_sequence_parallel_attention_matches_dense(causal):
    n = _ndev()
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    rng = np.random.RandomState(2)
    B, H, T, D = 2, 2, 4 * n, 8
    q, k, v = [jnp.asarray(rng.normal(0, 1, (B, H, T, D)).astype(np.float32))
               for _ in range(3)]
    with mesh:
        out = sequence_parallel_attention(mesh, q, k, v, causal=causal)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    if causal:
        mask = np.tril(np.ones((T, T), dtype=bool))
        s = jnp.where(mask[None, None], s, -1e30)
    ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


# ----------------------------------------------------- kvstore tpu_sync

def test_kvstore_tpu_sync_multi_value_push():
    """tpu_sync push of an N-value list reduces across all of them (the
    NCCL-kvstore semantics, kvstore_nccl.h:285)."""
    kv = mx.kv.create("tpu_sync")
    shape = (4, 3)
    kv.init("9", mx.nd.zeros(shape))
    vals = [mx.nd.ones(shape) * (i + 1) for i in range(_ndev())]
    kv.push("9", vals)
    out = mx.nd.zeros(shape)
    kv.pull("9", out=out)
    expected = sum(range(1, _ndev() + 1))
    np.testing.assert_allclose(out.asnumpy(), expected)


# ---------------------------------------------------------------------------
# pipeline parallelism
# ---------------------------------------------------------------------------

def _mlp_stage(p, h):
    import jax.numpy as jnp
    return jnp.tanh(h @ p["w"] + p["b"])


def test_pipeline_forward_matches_sequential():
    import jax.numpy as jnp
    from mxnet_tpu.parallel import make_pipeline_step
    from jax.sharding import Mesh
    import jax
    S, d, B, M = 4, 8, 16, 4
    mesh = Mesh(np.array(jax.devices())[:S], ("pp",))
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.normal(0, 0.5, (S, d, d)).astype(np.float32)),
              "b": jnp.asarray(rng.normal(0, 0.1, (S, d)).astype(np.float32))}
    x = jnp.asarray(rng.normal(0, 1, (B, d)).astype(np.float32))

    run = make_pipeline_step(_mlp_stage, mesh, n_microbatches=M)
    with mesh:
        y = np.asarray(run(params, x))

    h = np.asarray(x)
    for s in range(S):
        h = np.tanh(h @ np.asarray(params["w"][s]) + np.asarray(params["b"][s]))
    np.testing.assert_allclose(y, h, rtol=2e-4, atol=2e-5)


def test_pipeline_backward_matches_sequential():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from mxnet_tpu.parallel import make_pipeline_step
    S, d, B, M = 2, 6, 8, 4
    mesh = Mesh(np.array(jax.devices())[:S], ("pp",))
    rng = np.random.RandomState(1)
    params = {"w": jnp.asarray(rng.normal(0, 0.5, (S, d, d)).astype(np.float32)),
              "b": jnp.zeros((S, d), jnp.float32)}
    x = jnp.asarray(rng.normal(0, 1, (B, d)).astype(np.float32))
    tgt = jnp.asarray(rng.normal(0, 1, (B, d)).astype(np.float32))

    def loss_fn(y, labels):
        return jnp.mean((y - labels) ** 2)

    run = make_pipeline_step(_mlp_stage, mesh, n_microbatches=M,
                             loss_fn=loss_fn)
    with mesh:
        loss, grads = run(params, x, tgt)

    def ref_loss(p):
        h = x
        for s in range(S):
            h = jnp.tanh(h @ p["w"][s] + p["b"][s])
        return jnp.mean((h - tgt) ** 2)
    ref_l, ref_g = jax.value_and_grad(ref_loss)(params)
    np.testing.assert_allclose(float(loss), float(ref_l), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(grads["w"]), np.asarray(ref_g["w"]),
                               rtol=2e-3, atol=1e-5)


# ---------------------------------------------------------------------------
# Ulysses all-to-all sequence parallelism
# ---------------------------------------------------------------------------

def test_ulysses_matches_dense():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from mxnet_tpu.parallel import ulysses_parallel_attention
    n = 8
    mesh = Mesh(np.array(jax.devices())[:n], ("sp",))
    B, H, T, D = 2, 8, 64, 16
    rng = np.random.RandomState(2)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (B, H, T, D)).astype(np.float32))
               for _ in range(3))
    for causal in (False, True):
        with mesh:
            out = np.asarray(ulysses_parallel_attention(mesh, q, k, v,
                                                        causal=causal))
        s = np.einsum("bhqd,bhkd->bhqk", np.asarray(q), np.asarray(k)) / np.sqrt(D)
        if causal:
            mask = np.tril(np.ones((T, T), dtype=bool))
            s = np.where(mask[None, None], s, -1e30)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ref = np.einsum("bhqk,bhkd->bhqd", p, np.asarray(v))
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)


def test_ulysses_rejects_indivisible_heads():
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from mxnet_tpu.parallel import ulysses_parallel_attention
    n = len(jax.devices())
    if n == 1:
        pytest.skip("every head count divides a 1-device axis")
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    q = jnp.zeros((1, 2 * n - 1, 16, 4))  # 2n-1 is never divisible by n>1
    with pytest.raises(ValueError):
        ulysses_parallel_attention(mesh, q, q, q)


# ---------------------------------------------------------------------------
# expert-parallel MoE
# ---------------------------------------------------------------------------

def test_moe_matches_dense_dispatch():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from mxnet_tpu.parallel import make_expert_parallel_moe
    n, E, d, B = 4, 8, 16, 32
    mesh = Mesh(np.array(jax.devices())[:n], ("ep",))
    rng = np.random.RandomState(3)
    expert_params = {
        "w": jnp.asarray(rng.normal(0, 0.3, (E, d, d)).astype(np.float32))}
    gate_w = jnp.asarray(rng.normal(0, 1, (d, E)).astype(np.float32))
    x = jnp.asarray(rng.normal(0, 1, (B, d)).astype(np.float32))

    def expert_fn(p, tokens):
        return jnp.tanh(tokens @ p["w"])

    # generous capacity: nothing dropped -> must equal the dense reference
    moe = make_expert_parallel_moe(mesh, expert_fn, k=2, capacity_factor=8.0)
    with mesh:
        out = np.asarray(moe(expert_params, gate_w, x))

    gates = jax.nn.softmax(x @ gate_w, axis=-1)
    top2 = jax.lax.top_k(gates, 2)
    ref = np.zeros((B, d), np.float32)
    for t in range(B):
        vals = np.asarray(top2[0][t]); idx = np.asarray(top2[1][t])
        vals = vals / vals.sum()
        for j in range(2):
            e = int(idx[j])
            y = np.tanh(np.asarray(x[t]) @ np.asarray(expert_params["w"][e]))
            ref[t] += vals[j] * y
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-4)


def test_moe_capacity_drops_tokens():
    """Tiny capacity: overflow tokens contribute zero (Switch overflow rule),
    output stays finite and shaped."""
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from mxnet_tpu.parallel import make_expert_parallel_moe
    mesh = Mesh(np.array(jax.devices())[:2], ("ep",))
    rng = np.random.RandomState(4)
    E, d, B = 2, 8, 16
    expert_params = {"w": jnp.asarray(rng.normal(0, 0.3, (E, d, d)).astype(np.float32))}
    gate_w = jnp.asarray(np.zeros((d, E), np.float32))  # uniform gate -> expert 0 hot
    x = jnp.asarray(rng.normal(0, 1, (B, d)).astype(np.float32))

    def expert_fn(p, tokens):
        return tokens @ p["w"]

    moe = make_expert_parallel_moe(mesh, expert_fn, k=1, capacity_factor=0.25)
    with mesh:
        out = np.asarray(moe(expert_params, gate_w, x))
    assert out.shape == (B, d) and np.isfinite(out).all()
    assert (np.abs(out).sum(axis=1) == 0).any()  # some tokens dropped


def test_sequence_parallel_attention_grads_match_dense():
    """Long-context TRAINING through the ring: gradients flow through the
    ppermute ring (jax differentiates the collectives) and match the dense
    attention gradients — sp is usable in the training step, not just
    inference."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from mxnet_tpu.parallel import sequence_parallel_attention

    n = min(8, len(jax.devices()))
    mesh = Mesh(np.array(jax.devices()[:n]), ("sp",))
    rng = np.random.RandomState(0)
    B, H, T, D = 1, 2, 4 * n, 8
    q, k, v = (jnp.asarray(rng.normal(0, 1, (B, H, T, D)).astype(np.float32))
               for _ in range(3))

    def ring_loss(q_, k_, v_):
        with mesh:
            return jnp.sum(
                sequence_parallel_attention(mesh, q_, k_, v_, causal=True) ** 2)

    def dense_loss(q_, k_, v_):
        s = jnp.einsum("bhqd,bhkd->bhqk", q_, k_) / np.sqrt(D)
        mask = np.tril(np.ones((T, T), dtype=bool))
        s = jnp.where(mask[None, None], s, -1e30)
        out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v_)
        return jnp.sum(out ** 2)

    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_ring, g_dense, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


def test_ulysses_attention_grads_finite():
    """Gradients flow through the two all-to-alls of Ulysses sequence
    parallelism (head-sharded attention)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from mxnet_tpu.parallel import ulysses_parallel_attention

    n = min(8, len(jax.devices()))
    mesh = Mesh(np.array(jax.devices()[:n]), ("sp",))
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.normal(0, 1, (1, n, 4 * n, 8)).astype(np.float32))

    def loss(q_):
        with mesh:
            return jnp.sum(
                ulysses_parallel_attention(mesh, q_, q_, q_, causal=True) ** 2)

    g = jax.grad(loss)(x)
    assert bool(jnp.isfinite(g).all())


# ------------------------------------------------------- ZeRO sharded update

def _sq_loss(params, batch):
    import jax.numpy as jnp
    x, y = batch
    pred = x @ params["w"] + params["b"]
    return jnp.mean((pred - y) ** 2)


def _sgd_momentum(grads, opt_state, params):
    import jax
    new_m = jax.tree_util.tree_map(
        lambda m, g: 0.9 * m + g, opt_state, grads)
    new_p = jax.tree_util.tree_map(
        lambda p, m: p - 0.1 * m, params, new_m)
    return new_p, new_m


def _zero_fixture(dim=5, dtype=np.float32):
    rng = np.random.RandomState(7)
    params = {"w": jnp.asarray(rng.randn(dim).astype(dtype)),
              "b": jnp.asarray(rng.randn(1).astype(dtype))}
    n = _ndev()
    x = rng.randn(4 * n, dim).astype(dtype)
    y = rng.randn(4 * n).astype(dtype)
    return params, (x, y)


def test_init_shard_update_state_places_one_over_n():
    """The ZeRO memory contract, measured: each non-scalar optimizer-state
    leaf holds 1/N of its (padded) elements per device; scalars replicate;
    2-bit residual rows shard one row per replica."""
    mesh = make_mesh()
    n = _ndev()
    params, _ = _zero_fixture()
    opt = {"m": {"w": jnp.zeros(5), "b": jnp.zeros(1)},
           "step": jnp.zeros(())}
    state = init_shard_update_state(mesh, params, opt, wire_format="2bit")
    mw = state["opt"]["m"]["w"]
    assert mw.shape == (padded_size(5, n),)
    assert mw.addressable_shards[0].data.size * n == mw.size
    step_leaf = state["opt"]["step"]
    assert step_leaf.addressable_shards[0].data.size == step_leaf.size
    rw = state["residual"]["w"]
    assert rw.shape == (n, padded_size(5, n))
    assert rw.addressable_shards[0].data.shape[0] == 1
    # without a wire format there is no residual to carry
    plain = init_shard_update_state(mesh, params, opt)
    assert plain["residual"] is None


def test_sharded_update_step_matches_replicated_bitwise():
    """make_data_parallel_train_step(shard_update=True) vs the replicated
    step on the same mesh and batch: identical modules feed identical
    grads, and the elementwise update on 1/N slices IS the full update —
    loss and params must agree bitwise over several steps."""
    mesh = make_mesh()
    params, batch = _zero_fixture()
    opt = jax.tree_util.tree_map(jnp.zeros_like, params)

    rep = make_data_parallel_train_step(_sq_loss, _sgd_momentum, mesh,
                                        donate_params=False)
    shr = make_data_parallel_train_step(_sq_loss, _sgd_momentum, mesh,
                                        donate_params=False,
                                        shard_update=True)
    p_r, o_r = params, opt
    p_s = params
    s_s = init_shard_update_state(mesh, params, opt)
    b = shard_batch(mesh, batch)
    for _ in range(4):
        p_r, o_r, loss_r = rep(p_r, o_r, b)
        p_s, s_s, loss_s = shr(p_s, s_s, b)
        assert np.asarray(loss_r) == np.asarray(loss_s)
        for k in p_r:
            assert np.array_equal(np.asarray(p_r[k]), np.asarray(p_s[k])), k


def test_sharded_update_wire_residual_carries_across_steps():
    """wire_format='2bit' with a huge threshold: no code ever fires, so
    params sit still while the error-feedback residual accumulates the
    full gradient — proof the residual is carried in the step state, not
    recreated per call."""
    mesh = make_mesh()
    params, batch = _zero_fixture()
    opt = jax.tree_util.tree_map(jnp.zeros_like, params)
    step = make_data_parallel_train_step(
        _sq_loss, _sgd_momentum, mesh, donate_params=False,
        shard_update=True, wire_format="2bit", wire_threshold=1e9)
    state = init_shard_update_state(mesh, params, opt, wire_format="2bit")
    b = shard_batch(mesh, batch)
    p, s = params, state
    p, s, _ = step(p, s, b)
    r1 = np.asarray(s["residual"]["w"])
    p, s, _ = step(p, s, b)
    r2 = np.asarray(s["residual"]["w"])
    assert np.abs(r1).max() > 0
    np.testing.assert_allclose(r2, 2 * r1, rtol=1e-5)
    for k in params:
        assert np.array_equal(np.asarray(p[k]), np.asarray(params[k])), k


def test_sharded_update_wire_error_feedback_bounds_lag():
    """The EF accuracy contract (docs/PERF.md): with per-step gradients
    below the threshold, the quantized stream's delivered total lags the
    true total by at most one threshold per element, so after T plain-SGD
    steps on a CONSTANT gradient |p_q - p_f| <= lr * threshold."""
    n = _ndev()
    mesh = make_mesh()
    rng = np.random.RandomState(11)
    params = {"w": jnp.asarray(rng.randn(5).astype(np.float32))}
    x = rng.uniform(-1, 1, (4 * n, 5)).astype(np.float32)

    def linear_loss(p, batch):
        # constant gradient 0.2 * mean(x) per element, |g| < threshold
        return 0.2 * jnp.mean(batch[0] @ p["w"])

    def sgd(grads, opt_state, p):
        return (jax.tree_util.tree_map(
            lambda w, g: w - 0.1 * g, p, grads), opt_state)

    opt = jax.tree_util.tree_map(jnp.zeros_like, params)
    thr = 0.5
    fp = make_data_parallel_train_step(linear_loss, sgd, mesh,
                                       donate_params=False,
                                       shard_update=True)
    qt = make_data_parallel_train_step(
        linear_loss, sgd, mesh, donate_params=False,
        shard_update=True, wire_format="2bit", wire_threshold=thr)
    b = shard_batch(mesh, (x,))
    p_f, s_f = params, init_shard_update_state(mesh, params, opt)
    p_q, s_q = params, init_shard_update_state(mesh, params, opt,
                                               wire_format="2bit")
    for _ in range(10):
        p_f, s_f, _ = fp(p_f, s_f, (b[0],))
        p_q, s_q, _ = qt(p_q, s_q, (b[0],))
    np.testing.assert_allclose(np.asarray(p_q["w"]), np.asarray(p_f["w"]),
                               atol=0.1 * thr + 1e-6)


def test_shard_batch_indivisible_batch_raises_with_sizes():
    mesh = make_mesh()
    n = _ndev()
    bad = np.zeros((n + 1, 3), np.float32)
    with pytest.raises(ValueError) as e:
        shard_batch(mesh, bad)
    msg = str(e.value)
    assert str(n + 1) in msg and ("extent %d" % n) in msg


def test_check_flat_state_error_names_sizes():
    n = _ndev()
    with pytest.raises(ValueError) as e:
        check_flat_state("fc_weight", 7, 100, n)
    msg = str(e.value)
    assert "fc_weight" in msg and "7" in msg and "100" in msg


def test_wire_format_without_shard_update_raises():
    mesh = make_mesh()
    with pytest.raises(ValueError, match="shard_update"):
        make_data_parallel_train_step(_sq_loss, _sgd_momentum, mesh,
                                      wire_format="2bit")


# ---------------------------------------------------------------------------
# direct shard-level parity: ulysses_attention_local / ring_attention
# (the per-shard primitives the sharded decode path routes long-context
# prefill through — tested here against unsharded attention, not via the
# mesh-level convenience wrappers)
# ---------------------------------------------------------------------------

from mxnet_tpu.parallel import ulysses_attention_local


def _dense_attention(q, k, v, causal):
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        T = s.shape[-1]
        s = np.where(np.tril(np.ones((T, T), dtype=bool))[None, None],
                     s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", p, v)


def _run_seq_sharded(fn, q, k, v):
    """Run a per-shard attention primitive under shard_map with q/k/v
    sequence-sharded over an 8-way 'sp' axis."""
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    spec = P(None, None, "sp", None)
    return np.asarray(_shmap(mesh, fn, (spec, spec, spec), spec,
                             jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))


@pytest.mark.parametrize("T", [16, 40])
@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_local_matches_unsharded(T, causal):
    """Direct parity of the per-shard Ulysses primitive on mixed sequence
    lengths: two all-to-alls + local per-head-group attention must equal
    unsharded attention over the full sequence."""
    n = _ndev()
    rng = np.random.RandomState(5)
    q, k, v = (rng.normal(0, 1, (2, n, T, 8)).astype(np.float32)
               for _ in range(3))
    out = _run_seq_sharded(
        lambda q_, k_, v_: ulysses_attention_local(q_, k_, v_, "sp",
                                                   causal=causal), q, k, v)
    np.testing.assert_allclose(out, _dense_attention(q, k, v, causal),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("T", [16, 40])
@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_unsharded(T, causal):
    """Direct parity of the streaming-LSE ring primitive (K/V rotating via
    ppermute) against unsharded attention, mixed lengths; heads need not
    divide the axis (H=3)."""
    rng = np.random.RandomState(6)
    q, k, v = (rng.normal(0, 1, (1, 3, T, 8)).astype(np.float32)
               for _ in range(3))
    out = _run_seq_sharded(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, "sp", causal=causal),
        q, k, v)
    np.testing.assert_allclose(out, _dense_attention(q, k, v, causal),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("prim", ["ulysses", "ring"])
def test_sequence_parallel_masking_is_exact_zero(prim):
    """The first causal query attends only to itself with weight EXACTLY
    1.0 — masked future positions contribute exactly zero, so poisoning
    their values with 1e6 must leave out[..., 0, :] == v[..., 0, :]
    bitwise (the decode contract's exact-zero masking property, held
    through both sequence-parallel paths)."""
    n = _ndev()
    rng = np.random.RandomState(7)
    q, k, v = (rng.normal(0, 1, (1, n, 2 * n, 8)).astype(np.float32)
               for _ in range(3))
    v[:, :, 1:, :] = 1e6  # poison everything the first query must not see
    if prim == "ulysses":
        fn = lambda q_, k_, v_: ulysses_attention_local(q_, k_, v_, "sp",
                                                        causal=True)
    else:
        fn = lambda q_, k_, v_: ring_attention(q_, k_, v_, "sp", causal=True)
    out = _run_seq_sharded(fn, q, k, v)
    assert np.array_equal(out[:, :, 0, :], v[:, :, 0, :]), prim
