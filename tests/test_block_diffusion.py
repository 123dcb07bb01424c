"""Block-diffusion attention, the dropless expert layer for the experts held
here, and the model built of them, at small sizes on the CPU, against the
benchmark's plain reference (benchmark/reference/sdar_moe.py: float32,
``highest``, nothing of the program) and against the XLA attention reference
(the Pallas kernels run interpreted)."""
import copy
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler
from mxnet_tpu.gluon.block import functional_call
from mxnet_tpu.gluon.model_zoo import block_diffusion
from mxnet_tpu.ops import pallas_ops
from mxnet_tpu.parallel import moe

from benchmark.reference import common as reference
from benchmark.reference import sdar_moe

CONFIG = dict(
    reference="sdar_moe", optimizer="adam", hidden_size=64,
    num_attention_heads=8, num_key_value_heads=1, head_dim=16,
    moe_intermediate_size=24, num_experts=4, num_experts_per_tok=2,
    num_hidden_layers=2, vocab_size=96, rms_norm_eps=1e-6, rope_theta=1e6,
    block_length=4, deployment=dict(num_experts_total=8, first_expert=0))
L, BATCH = 32, 2


def _batch(seed=0, config=CONFIG):
    rng = np.random.default_rng(seed)
    mask_id = config["vocab_size"] - 1
    clean = rng.integers(0, mask_id, (BATCH, L), dtype=np.int32)
    t = np.repeat(rng.uniform(0.1, 1, (BATCH, L // 4)), 4, axis=1)
    masked = rng.random((BATCH, L)) < t
    tokens = np.concatenate([np.where(masked, mask_id, clean), clean], axis=1)
    return tokens.astype(np.int32), clean, (masked / t).astype(np.float32)


# -- the mask ---------------------------------------------------------------

@pytest.mark.parametrize("block_length", [4, 32])
def test_mask_follows_the_three_rules(block_length):
    length = 64
    mask = pallas_ops.block_diffusion_mask(length, block_length)
    rows = np.arange(2 * length)
    seen = pallas_ops.mask_visible(mask, rows[:, None], rows[None, :])
    for i in rows:
        for j in rows:
            bi, bj = (i % length) // block_length, (j % length) // block_length
            if i < length:      # a noised query
                want = bj == bi if j < length else bj < bi
            else:               # a clean query sees no noised key
                want = j >= length and bj <= bi
            assert seen[i, j] == want, (i, j)
    # about a quarter of the square: L * (L + block_length) pairs
    assert seen.sum() == length * (length + block_length)


def test_mask_refuses_a_block_that_does_not_divide_the_sequence():
    with pytest.raises(ValueError, match="does not divide"):
        pallas_ops.block_diffusion_mask(30, 4)


@pytest.mark.parametrize("block_length,tile", [(4, 32), (32, 64)])
def test_tiles_the_mask_empties_are_not_visited(block_length, tile):
    length = 128
    mask = pallas_ops.block_diffusion_mask(length, block_length)
    n = 2 * length // tile
    q_tile, k_tile, flag = pallas_ops._tile_tables(
        mask, 2 * length, 2 * length, n, n, tile, tile)
    rows = np.arange(2 * length)
    seen = pallas_ops.mask_visible(mask, rows[:, None], rows[None, :])
    tiles = seen.reshape(n, tile, n, tile)
    some, every = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
    state = flag & pallas_ops._STATE
    for qi in range(n):
        mine = q_tile == qi
        visited = dict(zip(k_tile[mine].tolist(), state[mine].tolist()))
        assert sorted(visited) == list(np.nonzero(some[qi])[0])
        assert all((f == 2) == bool(every[qi, k]) for k, f in visited.items())
    # an entry a visited tile and none besides: no row is padded to the longest
    assert len(flag) == (state > 0).sum() == some.sum()
    assert some.sum() <= n * n / 2      # half of the square and more is skipped


# -- the kernels against the XLA reference ----------------------------------

def _qkv(rng, heads, kv_heads, rows, dim=32):
    def normal(h):
        return jnp.asarray(rng.normal(0, 1, (1, h, rows, dim)), jnp.float32)
    return normal(heads), normal(kv_heads), normal(kv_heads)


@pytest.mark.parametrize("block_length,tiles", [(4, (32, 32)), (32, (64, 128)),
                                                (4, (64, 32))])
def test_block_mask_kernels_match_reference_8_heads_to_1(block_length, tiles):
    length, dim = 64, 32
    q, k, v = _qkv(np.random.RandomState(0), 8, 1, 2 * length, dim)
    mask = pallas_ops.block_diffusion_mask(length, block_length)

    def kernels(q, k, v):
        out = pallas_ops.block_mask_attention(
            q, k, v, length, block_length, precision="highest",
            interpret=True, block_q=tiles[0], block_k=tiles[1])
        return jnp.sum(jnp.sin(out)), out

    def oracle(q, k, v):
        out = pallas_ops._attention_reference(q, k, v, None, dim ** -0.5,
                                              mask=mask)
        return jnp.sum(jnp.sin(out)), out

    (_, out), grads = jax.value_and_grad(kernels, (0, 1, 2), has_aux=True)(
        q, k, v)
    (_, want), want_grads = jax.value_and_grad(oracle, (0, 1, 2),
                                               has_aux=True)(q, k, v)
    assert float(jnp.max(jnp.abs(out - want))) < 2e-5
    for got, ref in zip(grads, want_grads):
        assert got.shape == ref.shape
        assert float(jnp.max(jnp.abs(got - ref))) < 1e-4


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_backward_kernels_8_heads_to_1(causal):
    """No mask and the causal one: flash_attention's backward is the same
    blockwise kernels, and takes grouped-query heads."""
    dim = 32
    q, k, v = _qkv(np.random.RandomState(1), 8, 1, 160, dim)  # ragged: padded

    def kernels(q, k, v):
        return jnp.sum(pallas_ops.flash_attention(
            q, k, v, causal=causal, interpret=True) ** 2)

    def oracle(q, k, v):
        return jnp.sum(pallas_ops._attention_reference(
            q, k, v, causal, dim ** -0.5) ** 2)

    got = jax.grad(kernels, (0, 1, 2))(q, k, v)
    want = jax.grad(oracle, (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-3


def _pallas_calls(jaxpr):
    """The names of the ``pallas_call``s a jaxpr holds, at any depth."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _pallas_calls(sub)
    return names


@pytest.mark.parametrize("entry", ["block_mask_attention", "flash_attention"])
def test_backward_is_one_kernel(entry):
    """Both public entries: the backward pass alone (the forward's results
    handed in) holds one ``pallas_call``, ``attention_bwd``."""
    q, k, v = _qkv(np.random.RandomState(4), 4, 2, 64)
    if entry == "block_mask_attention":
        def f(q, k, v):
            return pallas_ops.block_mask_attention(
                q, k, v, 32, 4, interpret=True, block_q=32, block_k=32)
    else:
        def f(q, k, v):
            return pallas_ops.flash_attention(q, k, v, causal=True,
                                              interpret=True)
    out, vjp = jax.vjp(f, q, k, v)
    assert _pallas_calls(jax.make_jaxpr(vjp)(out).jaxpr) == ["attention_bwd"]
    whole = _pallas_calls(jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(f(q, k, v)), (0, 1, 2)))(q, k, v).jaxpr)
    assert sorted(whole) == ["attention_bwd", "attention_fwd"]


@pytest.mark.parametrize("heads_with_a_cotangent", [
    (5,), (0, 1, 2, 4, 5, 6)], ids=["one_head", "all_but_a_groups_last"])
def test_key_value_gradient_sums_over_its_query_heads(heads_with_a_cotangent):
    """8 query heads to 2: a key/value head's dk and dv are held in the
    kernel across its 4 query heads.  With the cotangent on one head of the
    second group the first group's gradients are zero and the second's that
    head's alone; with none on a group's last head they are still the sum
    of the other three (a block zeroed or written at the wrong head)."""
    length, dim = 64, 32
    q, k, v = _qkv(np.random.RandomState(5), 8, 2, 2 * length, dim)
    weight = np.zeros((1, 8, 2 * length, dim), np.float32)
    weight[:, list(heads_with_a_cotangent)] = np.random.RandomState(6).normal(
        0, 1, (1, len(heads_with_a_cotangent), 2 * length, dim))
    weight = jnp.asarray(weight)
    mask = pallas_ops.block_diffusion_mask(length, 4)

    def kernels(q, k, v):
        return jnp.sum(weight * pallas_ops.block_mask_attention(
            q, k, v, length, 4, precision="highest", interpret=True,
            block_q=32, block_k=64))

    def oracle(q, k, v):
        return jnp.sum(weight * pallas_ops._attention_reference(
            q, k, v, None, dim ** -0.5, mask=mask))

    got = jax.grad(kernels, (0, 1, 2))(q, k, v)
    want = jax.grad(oracle, (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4
    if heads_with_a_cotangent == (5,):
        assert not np.any(np.asarray(got[1][:, 0])) \
            and not np.any(np.asarray(got[2][:, 0]))
        assert float(jnp.max(jnp.abs(got[1][:, 1]))) > 1e-2


@pytest.mark.parametrize("case", ["block_mask_padded_both_ways",
                                  "one_query_tile_three_key_tiles"])
def test_ragged_lengths_with_grouped_heads(case):
    """Padding on both axes under the block mask (80 rows in tiles of 32),
    and a key length of three tiles (1,100 keys in 512s, padded) seen from
    one query tile of 24 rows, 4 query heads to 2."""
    dim = 32
    rng = np.random.RandomState(7)
    if case == "block_mask_padded_both_ways":
        length = 40
        q, k, v = _qkv(rng, 4, 2, 2 * length, dim)
        mask = pallas_ops.block_diffusion_mask(length, 4)

        def kernels(q, k, v):
            return pallas_ops.block_mask_attention(
                q, k, v, length, 4, precision="highest", interpret=True,
                block_q=32, block_k=32)

        def oracle(q, k, v):
            return pallas_ops._attention_reference(q, k, v, None,
                                                   dim ** -0.5, mask=mask)
    else:
        q = _qkv(rng, 4, 2, 24, dim)[0]
        _, k, v = _qkv(rng, 4, 2, 1100, dim)

        def kernels(q, k, v):
            return pallas_ops.flash_attention(q, k, v, causal="bottom",
                                              interpret=True)

        def oracle(q, k, v):
            return pallas_ops._attention_reference(q, k, v, "bottom",
                                                   dim ** -0.5)

    got = jax.grad(lambda *a: jnp.sum(jnp.sin(kernels(*a))), (0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(oracle(*a))), (0, 1, 2))(
        q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4


def test_bfloat16_gradients_are_accumulated_in_float32():
    """bfloat16 in, bfloat16 gradients out, each key/value gradient summed
    over 8 query heads x 4 query tiles in float32 and rounded once: it lies
    nearer the float32 reference than the same 32 contributions added up
    in bfloat16 do."""
    length, dim, tile = 64, 32, 32
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(
        np.random.RandomState(8), 8, 1, 2 * length, dim))
    mask = pallas_ops.block_diffusion_mask(length, 32)

    def kernels(q, k, v):
        return jnp.sum(pallas_ops.block_mask_attention(
            q, k, v, length, 32, interpret=True, block_q=tile,
            block_k=tile).astype(jnp.float32))

    got = jax.grad(kernels, (0, 1, 2))(q, k, v)
    assert all(x.dtype == jnp.bfloat16 for x in got)

    wide = [x.astype(jnp.float32) for x in (q, k, v)]

    @jax.jit
    def share(head, rows):
        """One query head's and query tile's share of the gradients."""
        weight = jnp.zeros((1, 8, 2 * length, 1)).at[0, head].set(
            ((jnp.arange(2 * length) // tile) == rows)[:, None] * 1.0)
        return jax.grad(lambda q, k, v: jnp.sum(
            weight * pallas_ops._attention_reference(
                q, k, v, None, dim ** -0.5, mask=mask)), (1, 2))(*wide)

    shares = [share(h, t) for h in range(8) for t in range(2 * length // tile)]
    for i, mine in enumerate(got[1:]):
        exact = sum(s[i] for s in shares)
        narrow = jnp.zeros_like(exact, jnp.bfloat16)
        for s in shares:
            narrow = (narrow + s[i].astype(jnp.bfloat16)).astype(jnp.bfloat16)
        gap = float(jnp.max(jnp.abs(mine.astype(jnp.float32) - exact)))
        narrow_gap = float(jnp.max(jnp.abs(
            narrow.astype(jnp.float32) - exact)))
        assert gap < 0.5 * narrow_gap, (gap, narrow_gap)


def test_default_precision_feeds_the_matrix_units_bfloat16():
    length = 32
    q, k, v = _qkv(np.random.RandomState(2), 2, 1, 2 * length)
    exact = pallas_ops.block_mask_attention(q, k, v, length, 4,
                                            precision="highest",
                                            interpret=True)
    default = pallas_ops.block_mask_attention(q, k, v, length, 4,
                                              interpret=True)
    gap = float(jnp.max(jnp.abs(exact - default)))
    assert 1e-5 < gap < 5e-2


def test_attention_counts_its_tiles():
    length = 128
    q, k, v = _qkv(np.random.RandomState(3), 4, 2, 2 * length)
    profiler.reset_spans()
    jax.grad(lambda q: jnp.sum(pallas_ops.block_mask_attention(
        q, k, v, length, 4, interpret=True, block_q=32, block_k=32)))(q)
    totals = profiler.totals()
    total, visited = (totals["attn.tiles_" + n]["count"]
                      for n in ("total", "visited"))
    # per head 8 x 8 tiles of 32 x 32 over 256 rows; tile i of the noised
    # rows sees itself and clean tiles 0..i, of the clean rows clean 0..i:
    # sum over i < 4 of (2 i + 3) = 24 of 64
    assert total == 2 * 4 * 64      # two grids: the forward's, the backward's
    assert visited * 64 == total * 24


# -- the dropless layer ------------------------------------------------------

def _experts(rng, experts=16, hidden=32, width=16):
    def normal(*shape, scale=0.3):
        return jnp.asarray(rng.normal(0, scale, shape), jnp.float32)
    return (normal(experts, hidden, scale=1.0), normal(experts, width, hidden),
            normal(experts, width, hidden), normal(experts, hidden, width))


def _dense_moe(x, router_w, gate_w, up_w, down_w, k, experts):
    weights, chosen = moe.route_top_k(jax.nn.softmax(x @ router_w.T, -1), k)
    out = jnp.zeros_like(x)
    for e in experts:
        w_e = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        y = (jax.nn.silu(x @ gate_w[e].T) * (x @ up_w[e].T)) @ down_w[e].T
        out = out + w_e[:, None] * y
    return out


def _family_moe(family, x, router_w, gate_w, up_w, down_w, k, experts):
    """The expert layer of a reference family (benchmark/reference/) over
    ``experts`` (a range) of the 16, as that family's ``moe`` computes it."""
    import importlib
    from benchmark.reference import common
    module = importlib.import_module("benchmark.reference." + family)
    part = slice(experts.start, experts.stop)
    sizes = {"held": len(experts), "experts": 16, "first": experts.start,
             "top_k": k, "width": gate_w.shape[1]}
    params = {"moe_router_weight": router_w,
              "moe_gate_weight": gate_w[part].reshape(-1, x.shape[1]),
              "moe_up_weight": up_w[part].reshape(-1, x.shape[1]),
              "moe_down_weight": down_w[part].reshape(-1, gate_w.shape[1])}
    return module.moe(sizes, common.Ops(), params, "", x, None, True)[0]


@pytest.mark.parametrize("tokens,held,family", [
    (64, 4, None), (512, 4, None), (64, 2, "keye_dsa"), (64, 2, "sdar_moe")])
def test_the_shares_add_up(tokens, held, family):
    """The shares' partial outputs of one layer of 16 experts (4 shares of 4
    experts, or 8 of 2) sum to the uncut layer's output, and each is its own
    experts' part: against this file's dense layer, or a reference family's
    own expert layer."""
    rng = np.random.RandomState(0)
    router_w, gate_w, up_w, down_w = _experts(rng)
    x = jnp.asarray(rng.normal(0, 1, (tokens, 32)), jnp.float32)
    k = 4
    dense = _dense_moe if family is None \
        else functools.partial(_family_moe, family)
    whole = dense(x, router_w, gate_w, up_w, down_w, k, range(16))
    total, pairs = 0, 0
    for first in range(0, 16, held):
        part = slice(first, first + held)
        out, load = moe.moe_held_apply(x, router_w, gate_w[part], up_w[part],
                                       down_w[part], k, first_expert=first)
        want = dense(x, router_w, gate_w, up_w, down_w, k,
                     range(first, first + held))
        assert float(jnp.max(jnp.abs(out - want))) < 1e-4
        total, pairs = total + out, pairs + float(load[0])
    assert float(jnp.max(jnp.abs(total - whole))) < 1e-4
    assert pairs == tokens * k            # every pair landed on one share


def test_every_token_to_one_expert_loses_none():
    rng = np.random.RandomState(1)
    router_w, gate_w, up_w, down_w = _experts(rng)
    # a router that prefers expert 2 for every token, whatever the token
    router_w = jnp.zeros_like(router_w).at[2].set(0.0)
    x = jnp.asarray(rng.normal(0, 1, (96, 32)), jnp.float32)
    router_w = router_w.at[2].set(50.0 * jnp.sign(x).mean(0))
    x = jnp.abs(x) * jnp.sign(x).mean(0)[None, :] + 1.0 * jnp.sign(
        jnp.sign(x).mean(0))[None, :]
    out, load = moe.moe_held_apply(x, router_w, gate_w[:4], up_w[:4],
                                   down_w[:4], 2)
    want = _dense_moe(x, router_w, gate_w, up_w, down_w, 2, range(4))
    assert float(load[1]) == 96         # all 96 tokens on the largest expert
    assert float(load[0]) >= 96
    assert float(jnp.max(jnp.abs(out - want))) < 1e-4
    assert float(jnp.min(jnp.sum(jnp.abs(out), axis=-1))) > 0   # none lost


@pytest.mark.parametrize("first", [0, 12])
def test_held_layer_gradients_match_the_dense_layer(first):
    rng = np.random.RandomState(2)
    router_w, gate_w, up_w, down_w = _experts(rng)
    x = jnp.asarray(rng.normal(0, 1, (48, 32)), jnp.float32)
    part = slice(first, first + 4)

    def held(x, router_w, gate_w, up_w, down_w):
        return jnp.sum(jnp.sin(moe.moe_held_apply(
            x, router_w, gate_w[part], up_w[part], down_w[part], 4,
            first_expert=first)[0]))

    def dense(x, router_w, gate_w, up_w, down_w):
        return jnp.sum(jnp.sin(_dense_moe(x, router_w, gate_w, up_w, down_w,
                                          4, range(first, first + 4))))

    args = (x, router_w, gate_w, up_w, down_w)
    got = jax.grad(held, (0, 1, 2, 3, 4))(*args)
    want = jax.grad(dense, (0, 1, 2, 3, 4))(*args)
    for a, b in zip(got, want):
        assert float(jnp.max(jnp.abs(a - b))) < 2e-4


def test_capacity_layer_and_dropless_layer_share_the_router():
    gates = jax.nn.softmax(jnp.asarray(
        np.random.RandomState(3).normal(0, 1, (8, 6)), jnp.float32), -1)
    weights, chosen = moe.route_top_k(gates, 2)
    assert np.allclose(np.asarray(weights.sum(-1)), 1.0, atol=1e-6)
    dispatch, combine = moe._one_hot_dispatch(gates, 2, capacity=8)
    for t in range(8):
        assert set(np.nonzero(np.asarray(dispatch[t]).sum(-1))[0]) \
            == set(np.asarray(chosen[t]))
        assert np.allclose(np.asarray(combine[t]).sum(-1)[np.asarray(
            chosen[t])], np.asarray(weights[t]), atol=1e-6)


# -- the model against the plain reference ----------------------------------

@pytest.fixture(scope="module")
def model():
    net = block_diffusion.build(CONFIG)
    net.initialize(mx.init.Zero(), ctx=mx.current_context())
    params, _ = reference.xavier_init(CONFIG, 7)
    return net, params


def _program_loss(net, values, batch):
    tokens, targets, weight = batch

    def loss(values):
        full = {net.prefix + k: v for k, v in values.items()}
        for name, p in net.collect_params().items():    # the load state
            full.setdefault(name, p.data()._data)
        outs, _ = functional_call(net, full, jnp.asarray(tokens),
                                  training=True)
        return block_diffusion.loss(
            [mx.nd.NDArray(o) for o in outs], mx.nd.NDArray(
                jnp.asarray(targets)),
            mx.nd.NDArray(jnp.asarray(weight)))._data.reshape(())
    return jax.value_and_grad(loss)(values)


def _reference_loss(config, params, batch):
    ops = reference.Ops()
    return jax.value_and_grad(lambda p: sdar_moe.loss(
        config, ops, p, {}, tuple(jnp.asarray(a) for a in batch))[0])(params)


def test_parameters_carry_the_reference_names(model):
    net, params = model
    held = {k[len(net.prefix):]: tuple(p.shape)
            for k, p in net.collect_params().items()}
    shapes = sdar_moe.param_shapes(CONFIG)
    assert {k: v for k, v in held.items() if not k.endswith("moe_load")} \
        == dict(shapes)
    assert sum(k.endswith("moe_load") for k in held) == 2


def test_loss_and_every_gradient_leaf_match_the_reference(model):
    net, params = model
    batch = _batch()
    loss, grads = _program_loss(net, params, batch)
    want, want_grads = _reference_loss(CONFIG, params, batch)
    assert abs(float(loss) - float(want)) < 1e-5 * abs(float(want))
    assert sorted(grads) == sorted(want_grads)
    for name in sorted(want_grads):
        scale = float(jnp.max(jnp.abs(want_grads[name]))) + 1e-12
        gap = float(jnp.max(jnp.abs(grads[name] - want_grads[name])))
        assert gap < 2e-4 * scale, (name, gap, scale)


@pytest.mark.parametrize("fault", ["drop_expert", "own_clean_block",
                                   "half_rows"])
def test_reference_faults_move_the_loss(model, fault):
    _, params = model
    batch = _batch()
    sound, _ = _reference_loss(CONFIG, params, batch)
    from benchmark.checks import faults_sdar
    with faults_sdar.planted(fault):
        broken, _ = _reference_loss(CONFIG, params, batch)
    assert abs(float(broken) - float(sound)) > 1e-4 * abs(float(sound))


def test_reference_overflow_is_a_nan_and_no_silent_drop(model, monkeypatch):
    _, params = model
    tokens = jnp.asarray(_batch()[0])
    with monkeypatch.context() as patched:      # a buffer of one pair
        patched.setattr(sdar_moe, "reference_pairs", lambda config, rows: 1)
        logits, _ = sdar_moe.forward(CONFIG, reference.Ops(), params, {},
                                     tokens, True)
    assert np.isnan(np.asarray(logits)).all()
    logits, _ = sdar_moe.forward(CONFIG, reference.Ops(), params, {}, tokens,
                                 True)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("block_length", [4, 32])
def test_reference_layouts_agree(model, block_length):
    """The layout flops.py counts (every chunk against its visible keys,
    every pair routed here against its expert) and the layout that is
    trained (one layer's, one chunk's and one expert's program, looped):
    the same logits and the same gradients."""
    _, params = model
    config = dict(CONFIG, block_length=block_length)
    tokens = jnp.asarray(_batch()[0])
    ops = reference.Ops()

    def total(looped):
        return jax.value_and_grad(lambda p: jnp.sum(jnp.tanh(
            sdar_moe.network(config, ops, p, tokens, looped))))(params)

    (a, ga), (b, gb) = total(False), total(True)
    assert abs(float(a) - float(b)) < 1e-4 * abs(float(a))
    for name in ga:
        scale = float(jnp.max(jnp.abs(ga[name]))) + 1e-12
        assert float(jnp.max(jnp.abs(ga[name] - gb[name]))) < 1e-4 * scale, name


def test_reference_counts_required_work():
    """flops.py's walk over the reference: by hand, for the tiny size."""
    from benchmark import flops

    class Cell:
        config, traffic = CONFIG, {"seq_len": L}
    d, hd, heads, f = 64, 16, 8, 24
    rows = 2 * L
    pairs = sdar_moe.reference_pairs(CONFIG, rows)
    assert pairs == rows * 2        # no even load asked for: every pair
    assert sdar_moe.reference_pairs(
        dict(CONFIG, moe_reference_load="even"), rows) == rows
    per_layer = rows * d * (heads * hd * 2 + 2 * hd) \
        + rows * 8 * d + pairs * 3 * d * f \
        + 2 * heads * hd * (L * L + L * (L + L))       # one chunk: L rows
    by_hand = 2 * per_layer + L * 96 * d
    assert flops.forward_macs(Cell) == by_hand


# -- through the compiled step: recomputation, counters, the load -----------

def test_compiled_step_trains_recomputes_and_records(model, monkeypatch):
    from mxnet_tpu.module.compiled_step import CompiledTrainStep
    profiler.reset_spans()
    net = block_diffusion.build(CONFIG)
    net.initialize(mx.init.Xavier(), ctx=mx.current_context())
    wrapped = []
    checkpoint = jax.checkpoint
    monkeypatch.setattr(jax, "checkpoint", lambda f, **kw: (
        wrapped.append(f.__name__), checkpoint(f, **kw))[1])
    step = CompiledTrainStep.from_block(
        net, block_diffusion.loss,
        mx.optimizer.create("adam", learning_rate=1e-3),
        n_inputs=block_diffusion.N_INPUTS)
    batch = tuple(mx.nd.array(a, dtype=a.dtype) for a in _batch())
    losses = [float(step.step(*batch).asnumpy()[0]) for _ in range(4)]
    assert losses[-1] < losses[0]
    # the two decoder layers: neither the embedding nor the head is
    # recomputed
    assert wrapped == ["pure", "pure"]
    totals = profiler.totals()
    # two routed layers were traced, each over the same rows and experts
    assert totals["moe.rows"]["count"] == 2 * totals["moe.rows"]["max"]
    assert totals["moe.experts_held"]["max"] == 4
    assert totals["moe.experts_held"]["count"] == 2 * 4
    loads = [v for k, v in totals.items() if k.startswith("moe.load.")
             and k.startswith("moe.load." + net.prefix)]
    assert len(loads) == 2
    for load in loads:      # pairs routed here, the largest expert's load
        assert 0 < load["max"] <= load["count"] <= BATCH * 2 * L * 2
        assert load["max"] >= load["count"] / 4


def test_remat_of_a_child_block_changes_no_gradient():
    plain = block_diffusion.BlockDiffusionMoEDecoder(CONFIG, prefix="same_")
    plain.initialize(mx.init.Xavier(), ctx=mx.current_context())
    values = {k: p.data()._data for k, p in plain.collect_params().items()}
    tokens = jnp.asarray(_batch()[0])

    def total(net):
        def f(values):
            outs, _ = functional_call(net, values, tokens, training=True)
            return jnp.sum(jnp.tanh(outs[0]))
        return jax.grad(f)(values)

    want = total(plain)
    for layer in plain.layers:
        layer.hybridize(remat=True)
    jaxpr = str(jax.make_jaxpr(lambda v: functional_call(
        plain, v, tokens, training=True)[0])(values))
    assert jaxpr.count("checkpoint") + jaxpr.count("remat") >= 2
    got = total(plain)
    for name in want:
        if not name.endswith("moe_load"):
            assert float(jnp.max(jnp.abs(got[name] - want[name]))) < 1e-5


# -- a recomputed layer keeps its attention kernel's out and lse -----------

NAMES = pallas_ops.ATTENTION_RESIDUALS


@pytest.fixture
def kernel_net(monkeypatch):
    """``loss(flags)``: (a loss through the two-layer network as a function
    of its parameters, the parameters), the attention by the Pallas kernels
    (interpreted) and every layer hybridized with ``flags`` (None: not)."""
    monkeypatch.setattr(pallas_ops, "block_mask_attention", functools.partial(
        pallas_ops.block_mask_attention, interpret=True, block_q=32,
        block_k=32))
    net = block_diffusion.BlockDiffusionMoEDecoder(CONFIG, prefix="kept_")
    net.initialize(mx.init.Xavier(), ctx=mx.current_context())
    values = {k: p.data()._data for k, p in net.collect_params().items()}
    tokens = jnp.asarray(_batch()[0])

    def loss(flags):
        for layer in net.layers:
            layer.hybridize(flags is not None, **(flags or {}))

        def f(values):  # a new function each time: jax caches traces by it
            outs, _ = functional_call(net, values, tokens, training=True)
            return jnp.sum(jnp.tanh(outs[0]))
        return f, values
    return loss


def _kernels(jaxpr, name):
    """How many ``pallas_call``s of that name the jaxpr holds, at any depth."""
    return _pallas_calls(jaxpr).count(name)


def test_kept_out_and_lse_change_no_bit_of_the_gradient(kernel_net):
    kept, plain, eager = (jax.grad(f)(values) for f, values in (
        kernel_net(dict(remat=True, remat_policy=NAMES)),
        kernel_net(dict(remat=True)), kernel_net(None)))
    for name in plain:
        assert bool(jnp.all(kept[name] == plain[name])), name
        if not name.endswith("moe_load"):
            assert float(jnp.max(jnp.abs(kept[name] - eager[name]))) < 1e-5


@pytest.mark.parametrize("flags,forward_kernels", [
    (None, 2), (dict(remat=True), 4), (dict(remat=True, remat_policy=NAMES), 2),
    (dict(remat=True, remat_policy=NAMES[:1]), 4),
    (dict(remat=True, remat_policy=("attn.other",)), 4)],
    ids=["no_recomputation", "plain", "both_kept", "out_alone", "other_names"])
def test_forward_kernel_runs_once_a_layer_with_both_results_kept(
        kernel_net, flags, forward_kernels):
    """Two layers: with ``out`` and ``lse`` kept the recomputed layer has no
    use for the forward kernel; with one of them missing it runs again.  The
    backward kernel runs once a layer whatever is kept."""
    f, values = kernel_net(flags)
    jaxpr = jax.make_jaxpr(jax.grad(f))(values).jaxpr
    assert _kernels(jaxpr, "attention_fwd") == forward_kernels
    assert _kernels(jaxpr, "attention_bwd") == 2


def test_build_keeps_the_attention_residuals():
    net = block_diffusion.build(CONFIG)
    assert [layer._flags for layer in net.layers] == \
        [dict(remat=True, remat_policy=("attn.out", "attn.lse", "moe.table"))] * 2


@pytest.mark.parametrize("causal", [False, True])
def test_naming_is_an_identity_outside_a_recomputed_region(monkeypatch,
                                                           causal):
    """``flash_attention`` shares ``_attention``: forward and gradient lower
    to the same text whether or not ``out`` and ``lse`` are named."""
    q, k, v = _qkv(np.random.default_rng(3), 4, 2, 64)

    def lowered():
        def f(q, k, v):     # a new function each time: see above
            return jnp.sum(jnp.tanh(pallas_ops.flash_attention(
                q, k, v, causal=causal, interpret=True)))
        # a private function's symbol ends in a counter that tracing the
        # names advances: @_where_52 for @_where_51
        return [re.sub(r"(@\w+?)_\d+\b", r"\1", jax.jit(g).lower(
            q, k, v).as_text()) for g in (f, jax.grad(f, (0, 1, 2)))]

    named = lowered()
    seen = []
    monkeypatch.setattr(jax.ad_checkpoint, "checkpoint_name",
                        lambda x, name: (seen.append(name), x)[1])
    assert lowered() == named
    assert sorted(set(seen)) == sorted(NAMES[:2])       # the attention's two


def test_gauge_is_read_when_totals_are_asked():
    reads = []
    profiler.gauge("test.gauge", lambda: (reads.append(1), (7.0, 3.0))[1])
    assert not reads
    assert profiler.totals()["test.gauge"] == {
        "count": 7.0, "wall_ns": 0, "cpu_ns": 0, "max": 3.0}
    profiler.gauge("test.gauge", lambda: 1 / 0)     # its state is gone
    assert "test.gauge" not in profiler.totals()


def test_operators_are_registered_for_nd_and_sym():
    for name in ("_contrib_rms_norm", "_contrib_rotary_embedding",
                 "_contrib_block_mask_attention",
                 "_contrib_moe_held_experts"):
        assert hasattr(mx.nd, name) and hasattr(mx.sym, name)
    x = mx.nd.array(np.random.RandomState(0).normal(0, 1, (2, 5, 8)))
    y = mx.nd._contrib_rms_norm(x, mx.nd.ones((8,)), eps=1e-6).asnumpy()
    assert np.allclose((y ** 2).mean(-1), 1.0, atol=1e-4)
    r = mx.nd._contrib_rotary_embedding(
        x, mx.nd.array([0, 1, 2, 3, 4], dtype="int32"), base=100.0).asnumpy()
    assert np.allclose(r[:, 0], x.asnumpy()[:, 0], atol=1e-6)   # position 0
    assert np.allclose((r ** 2).sum(-1), (x.asnumpy() ** 2).sum(-1),
                       atol=1e-4)                               # a rotation
