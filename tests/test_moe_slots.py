"""The held experts' layer over a table of slots (parallel/moe.py
``moe_held_apply``, ``slot_table``; ops/pallas_ops.py ``_Experts``): a row's
picked experts computed, the pairs sorted by expert into a fixed number of
slots, grouped products over whole tiles.  Held here against a plain loop
over the held experts, for every routing the table has to hold; the kernels
run interpreted, against the XLA path the CPU takes."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import profiler
from mxnet_tpu.ops import pallas_ops
from mxnet_tpu.parallel import moe

D, F = 16, 6


def _layer(rng, rows, total, held, first, steer):
    """Rows whose first feature is 1, and a router that adds ``steer``
    (total,) to every row's logits through it; a selection bias (a sigmoid
    router's) steered alike, since a sigmoid saturates."""
    x = jnp.asarray(rng.normal(0, 1, (rows, D)), jnp.float32).at[:, 0].set(1)
    router = jnp.asarray(rng.normal(0, 1, (total, D)), jnp.float32)
    router = router.at[:, 0].set(jnp.asarray(steer, jnp.float32))
    gate, up = (jnp.asarray(rng.normal(0, 0.4, (held, F, D)), jnp.float32)
                for _ in range(2))
    down = jnp.asarray(rng.normal(0, 0.4, (held, D, F)), jnp.float32)
    bias = jnp.asarray(rng.normal(0, 0.3, (total,)) + steer, jnp.float32)
    return x, router, gate, up, down, bias


def _loop(x, router, gate, up, down, bias, k, first, scoring):
    """The layer as a loop over the held experts, every row through each."""
    weights, picked = moe.route_tokens(
        x, router, k, scoring, 1.0, bias if scoring == "sigmoid" else None)
    out = jnp.zeros_like(x)
    for e in range(gate.shape[0]):
        w = jnp.sum(jnp.where(picked == first + e, weights, 0.0), axis=1)
        hidden = jax.nn.silu(x @ gate[e].T) * (x @ up[e].T)
        out = out + w[:, None] * (hidden @ down[e].T)
    return out


def _slots(x, router, gate, up, down, bias, k, first, scoring):
    return moe.moe_held_apply(
        x, router, gate, up, down, k, first_expert=first, scoring=scoring,
        bias=bias if scoring == "sigmoid" else None)


def _slot_out(*args):
    return _slots(*args)[0]


# (rows, experts in all, held, first held, per token, what the router is
# steered to: logits added to the experts named)
ROUTINGS = {
    "even": (48, 16, 4, 4, 2, {}),
    "every_row_to_the_same_held": (48, 16, 4, 4, 2, {4: 40, 6: 30}),
    "no_row_to_a_held": (48, 16, 4, 4, 2, {4: -40, 5: -40, 6: -40, 7: -40}),
    "one_held_unpicked": (48, 16, 4, 4, 2, {5: -40}),
    "fewer_held_than_picked": (40, 8, 2, 2, 4, {2: 40}),
    "as_many_held_as_picked": (40, 8, 3, 5, 3, {5: 40, 6: 30, 7: 20}),
    "rows_no_tile_divides": (37, 16, 4, 0, 3, {1: 5}),
    "one_expert_held": (24, 4, 1, 3, 2, {}),
}


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_slot_layer_is_the_loop_over_held_experts(routing, scoring):
    """Forward and every gradient leaf: rows, router, the three stacked
    leaves, and a selection bias that gets exactly 0."""
    rows, total, held, first, k, steer = ROUTINGS[routing]
    logits = np.zeros(total)
    for e, v in steer.items():
        logits[e] = v
    args = _layer(np.random.RandomState(len(routing)), rows, total, held,
                  first, logits)
    cot = jnp.cos(jnp.arange(rows * D, dtype=jnp.float32)).reshape(rows, D)
    (got, load), want = _slots(*args, k, first, scoring), _loop(
        *args, k, first, scoring)
    assert not bool(jnp.any(jnp.isnan(got)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    picked = np.asarray(moe.route_tokens(
        args[0], args[1], k, scoring, 1.0,
        args[5] if scoring == "sigmoid" else None)[1])
    per_expert = np.bincount(picked.ravel(), minlength=total)[
        first:first + held]
    assert load.tolist() == [per_expert.sum(), per_expert.max()]
    if routing == "every_row_to_the_same_held":
        assert per_expert.sum() == rows * k         # every slot of a pair full
    if routing == "no_row_to_a_held":
        assert per_expert.sum() == 0 and not bool(jnp.any(got != 0))
    if routing == "one_held_unpicked":
        assert per_expert[1] == 0 and per_expert.sum() > 0

    def scalar(layer):
        return lambda *a: jnp.sum(layer(*a, k, first, scoring) * cot)

    got = jax.grad(scalar(_slot_out), tuple(range(6)))(*args)
    want = jax.grad(scalar(_loop), tuple(range(6)))(*args)
    for name, a, b in zip(("rows", "router", "gate", "up", "down", "bias"),
                          got, want):
        assert not bool(jnp.any(jnp.isnan(a))), name
        np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-5, err_msg=name)
    assert not bool(jnp.any(got[5] != 0))           # the bias: exactly 0
    if routing == "no_row_to_a_held":
        assert all(not bool(jnp.any(g != 0)) for g in got[2:5])
    if routing == "one_held_unpicked":
        assert all(not bool(jnp.any(g[1] != 0)) for g in got[2:5])


def _table(picked, first, held, tm):
    return (np.asarray(a) for a in moe.slot_table(
        jnp.asarray(picked, jnp.int32), first, held, tm))


@pytest.mark.parametrize("rows,total,held,first,k,tm", [
    (64, 16, 4, 4, 2, 16), (64, 16, 4, 0, 2, 16), (50, 8, 2, 2, 4, 16),
    (33, 8, 8, 0, 3, 32), (16, 4, 1, 1, 2, 16)])
def test_slot_table_gives_every_held_pair_a_slot_in_its_expert_s_tiles(
        rows, total, held, first, k, tm):
    rng = np.random.RandomState(rows)
    routings = [np.stack([rng.permutation(total)[:k] for _ in range(rows)]),
                # every row to the first k experts from the first held on
                np.tile((first + np.arange(k)) % total, (rows, 1)),
                # no row to a held expert, where the others are enough
                np.tile([e for e in range(total)
                         if not first <= e < first + held][:k] or
                        np.arange(k), (rows, 1))]
    slots = -(-rows * min(k, held) // tm) * tm + held * tm
    for picked in routings:
        source, slot_of, tile_expert, here, per_expert = _table(
            picked, first, held, tm)
        assert tile_expert.shape == (slots // tm,)
        # a permutation and its inverse
        assert sorted(source) == list(range(len(source)))
        assert (source[slot_of] == np.arange(len(source))).all()
        # each expert's tiles consecutive, every expert with one at least
        assert (np.diff(tile_expert) >= 0).all()
        assert set(tile_expert) == set(range(held))
        local = picked - first
        assert (here == ((local >= 0) & (local < held))).all()
        assert per_expert.tolist() == [int((local == e).sum())
                                       for e in range(held)]
        # a held pair lies in a tile of its expert, and no two in one slot
        at = slot_of[:rows * k].reshape(rows, k)
        assert (at[here] < slots).all()
        assert (tile_expert[at[here] // tm] == local[here]).all()
        assert len(set(at[here])) == here.sum()
        # every other slot of the table holds padding or another chip's pair
        others = np.setdiff1d(np.arange(slots), at[here])
        assert ((source[others] >= rows * k)
                | ~here.ravel()[np.minimum(source[others], rows * k - 1)]
                ).all()


def test_slots_tiles_and_program_follow_from_the_shapes_alone():
    """Two routings, one program: no branch or loop on the load, the slot
    count the shapes' own, one compile."""
    rows, total, held, first, k = 96, 16, 4, 4, 2
    even = _layer(np.random.RandomState(0), rows, total, held, first,
                  np.zeros(total))
    one = _layer(np.random.RandomState(1), rows, total, held, first,
                 np.where(np.arange(total) == 5, 40.0, 0.0))
    step = jax.value_and_grad(
        lambda *a: jnp.sum(_slots(*a, k, first, "softmax")[0]), (0, 1, 2, 3, 4))
    before = dict(profiler.totals()).get("moe.slots", {"count": 0})["count"]
    runs = _runs()
    texts = {str(jax.make_jaxpr(step)(*a)) for a in (even, one)}
    assert _runs() == runs      # XLA's gather adds the slots up here
    assert len(texts) == 1
    text = texts.pop()
    assert "cond[" not in text and "while[" not in text
    tm = pallas_ops.slot_tile_rows(rows * k, held)
    slots = rows * k + held * tm
    # written when the layer is traced (a second trace may come from a cache)
    assert profiler.totals()["moe.slots"]["count"] - before in (slots,
                                                                2 * slots)
    loads = [float(_slots(*a, k, first, "softmax")[1][1]) for a in (even,
                                                                    one)]
    assert loads[1] == rows > loads[0]              # the routings do differ
    step = jax.jit(step)
    for a in (even, one):
        step(*a)
    assert step._cache_size() == 1


def test_tile_rows_follow_the_table_s_size():
    assert pallas_ops.slot_tile_rows(8192 * 8, 16) == 256
    assert pallas_ops.slot_tile_rows(16384 * 4, 8) == 256
    assert pallas_ops.slot_tile_rows(192, 4) == 48
    assert pallas_ops.slot_tile_rows(2, 8) == 16


@pytest.mark.parametrize("held,f,d,tm,tile_expert", [
    (3, 256, 128, 16, [0, 0, 1, 1, 1, 2, 2]),
    (2, 128, 256, 32, [0, 1, 1]),
    (2, 1536, 128, 16, [0, 0, 1]),      # two blocks of the weights' gradients
])
def test_experts_kernels_interpreted_match_the_xla_path(held, f, d, tm,
                                                        tile_expert):
    """The four kernels on bfloat16 operands against the per-tile XLA
    products on the same values in float32."""
    rng = np.random.RandomState(f)
    te = jnp.asarray(tile_expert, jnp.int32)
    S = len(tile_expert) * tm

    def bf(*shape, scale=1.0):
        return jnp.asarray(rng.normal(0, scale, shape),
                           jnp.float32).astype(jnp.bfloat16)

    def f32(a):
        return a.astype(jnp.float32)

    x, dy = bf(S, d), bf(S, d)
    w = jnp.asarray(rng.uniform(0, 1, (S,)), jnp.float32).at[-tm:].set(0)
    wg, wu, wd = bf(held, f, d, scale=.1), bf(held, f, d, scale=.1), \
        bf(held, d, f, scale=.1)
    ker = pallas_ops._Experts(te, tm, d, f, held, interpret=True)
    ref = pallas_ops._Experts(te, tm, d, f, held)
    assert ker.kernels and not ref.kernels

    def close(got, want, tol):
        assert len(got) == len(want)
        for a, b in zip(got, want):
            scale = float(jnp.max(jnp.abs(b))) or 1.0
            assert float(jnp.max(jnp.abs(f32(a) - b))) <= tol * scale

    hw, g, u = ker.hidden(x, w, wg, wu, keep=True)
    close((hw, g, u), ref.hidden(f32(x), w, f32(wg), f32(wu), keep=True),
          4e-3)
    assert hw.dtype == jnp.bfloat16 and g.dtype == jnp.float32
    assert not bool(jnp.any(hw[-tm:] != 0))         # weight 0: an empty slot
    only, = ker.hidden(x, w, wg, wu, keep=False)
    assert bool(jnp.all(only == hw))
    close((ker.down(hw, wd),), (ref.down(f32(hw), f32(wd)),), 1e-5)
    got = ker.backward(dy, g, u, w, wg, wu, wd)
    close(got, ref.backward(f32(dy), g, u, w, f32(wg), f32(wu), f32(wd)),
          4e-3)
    assert not bool(jnp.any(got[0][-tm:] != 0))     # and nothing flows back
    dx, dw, dg, du = got
    close(ker.weight_gradients(x, dy, dg, du, hw),
          ref.weight_gradients(f32(x), f32(dy), f32(dg), f32(du), f32(hw)),
          1e-5)


def _runs():
    return dict(profiler.totals()).get("moe.combine_runs",
                                       {"count": 0})["count"]


def test_layer_through_the_interpreted_kernels(monkeypatch):
    """The whole layer, forward and backward, with the kernels in place of
    the XLA products and of the gather that adds each row's slots (as on a
    TPU), against the loop: one bfloat16 pass; and against the same layer
    with XLA's gather-and-sum, which only the order of float32 additions
    tells apart."""
    real = pallas_ops._Experts
    monkeypatch.setattr(pallas_ops, "_Experts", lambda *a, **kw: real(
        *a, interpret=True, **kw))
    monkeypatch.setattr(pallas_ops, "SLOT_TILE_ROWS", 32)
    rows, total, held, first, k = 128, 8, 4, 2, 2
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.normal(0, 1, (rows, 128)), jnp.float32)
    router = jnp.asarray(rng.normal(0, 1, (total, 128)), jnp.float32)
    gate, up = (jnp.asarray(rng.normal(0, 0.1, (held, 128, 128)), jnp.float32)
                for _ in range(2))
    down = jnp.asarray(rng.normal(0, 0.1, (held, 128, 128)), jnp.float32)
    args = (x, router, gate, up, down, None)

    def step(layer):
        return jax.value_and_grad(lambda *a: jnp.sum(jnp.sin(
            layer(*a, None, k, first, "softmax"))), (0, 1, 2, 3, 4))(
                *args[:5])

    before = _runs()
    got = step(_slot_out)
    # a tile of 120 rows and a held expert a grid step: 128 rows are 2 tiles
    assert pallas_ops.combine_tile_rows(rows) == 120
    assert _runs() - before == 2 * held
    want = step(_loop)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        gap = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert gap < 1e-2, gap

    gather = moe.from_slots
    monkeypatch.setattr(moe, "from_slots", lambda experts, *a: gather(
        types.SimpleNamespace(kernels=False), *a))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(step(_slot_out))):
        gap = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert gap < 1e-6, gap


def _combine_case(rows, total, held, first, k, picked, values, seed):
    """A table of slots as the layer leaves it (``values`` in the slots of
    held pairs, 0 in every other) and its rows' sums by the interpreted
    kernel and by XLA's gather."""
    tm = 16
    source, slot_of, te, here, _ = moe.slot_table(
        jnp.asarray(picked, jnp.int32), first, held, tm)
    S = te.shape[0] * tm
    held_pairs = np.asarray(slot_of[:rows * k]).reshape(rows, k)[
        np.asarray(here)]
    full = np.zeros((S, 1), bool)
    full[held_pairs] = True
    a_s = jnp.asarray(np.where(full, values(np.random.RandomState(seed), S),
                               0), jnp.float32)
    ker = pallas_ops._Experts(te, tm, 128, 128, held, interpret=True)
    xla = pallas_ops._Experts(te, tm, 128, 128, held)
    assert ker.kernels and not xla.kernels
    return (np.asarray(moe.from_slots(e, a_s, slot_of, here))
            for e in (ker, xla)), np.asarray(here)


def _normal(rng, S):
    return rng.normal(0, 1, (S, 128))


def _just_above_one(rng, S):
    # 1 + 2**-20 times -2, -1, 1 or 2: no bfloat16 holds them, and every
    # sum of three of them is exact in float32
    return (1 + 2.0 ** -20) * rng.choice([-2, -1, 1, 2], (S, 128))


def _picks(rng, rows, total, k, steer):
    """Each row's ``k`` distinct experts: those of ``steer`` first, then
    others at random, never ``-e`` for an ``-e`` in ``steer``."""
    first = [e for e in steer if e >= 0]
    rest = [e for e in range(total) if e not in first and -e not in steer]
    return np.stack([first + list(rng.permutation(rest)[:k - len(first)])
                     for _ in range(rows)])


# (rows, experts in all, held, first held, per token, the picks' steer,
# values)
COMBINES = {
    "picks_equal_held": (240, 16, 4, 4, 4, (), _normal),
    "picks_fewer_than_held": (240, 16, 8, 0, 3, (), _normal),
    "rows_no_tile_divides": (250, 16, 4, 4, 2, (), _normal),
    "one_expert_every_row": (250, 16, 4, 4, 2, (6,), _normal),
    "a_held_expert_without_pair": (250, 16, 4, 4, 2, (-5,), _normal),
    "pairs_held_elsewhere": (130, 16, 4, 4, 3, (0, 15), _normal),
    "values_bfloat16_cannot_hold": (250, 16, 4, 4, 3, (), _just_above_one),
}


@pytest.mark.parametrize("case", sorted(COMBINES))
def test_slot_combine_kernel_is_xla_s_gather_and_sum(case):
    """The kernel (interpreted) against XLA's form, row by row, to 1e-6 of
    the row: the same float32 values added in another order.  Where every
    partial sum is exact, bit for bit, so a value rounded through bfloat16
    anywhere fails."""
    rows, total, held, first, k, steer, values = COMBINES[case]
    picked = _picks(np.random.RandomState(len(case)), rows, total, k, steer)
    (got, want), here = _combine_case(rows, total, held, first, k, picked,
                                      values, len(case))
    assert got.shape == want.shape == (rows, 128)
    gap = np.linalg.norm(got - want, axis=1)
    assert (gap <= 1e-6 * np.linalg.norm(want, axis=1)).all(), gap.max()
    assert ((want == 0) == (got == 0)).all()
    if case == "one_expert_every_row":
        assert here[:, 0].all()
    if case == "a_held_expert_without_pair":
        assert not (picked == 5).any()
    if case == "pairs_held_elsewhere":
        assert not here[:, :2].any() and here[:, 2].any()
    if case == "values_bfloat16_cannot_hold":
        assert (got == want).all()
        assert (got[here.any(1)] != np.asarray(jnp.asarray(
            got[here.any(1)]).astype(jnp.bfloat16).astype(jnp.float32))
                ).any()
    if case in ("picks_equal_held", "rows_no_tile_divides"):
        tiles = -(-rows // pallas_ops.combine_tile_rows(rows))
        assert tiles == (2 if rows == 240 else 3)
        assert pallas_ops._Experts(
            jnp.zeros(4, jnp.int32), 16, 128, 128, held,
            interpret=True).combine_runs(rows) == tiles * held
