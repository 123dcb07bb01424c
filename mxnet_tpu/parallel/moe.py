"""Expert parallelism: mixture-of-experts layers for an 'ep' axis.

Two layers share the router's choice (``route_top_k``: the ``k`` experts
ranked highest, their weights normalised over the picked ``k``; softmax
probabilities that rank and weigh alike, or sigmoid scores ranked with a
selection bias that stays out of the weights):

* ``moe_apply`` (below, first): the capacity layer.  Static shapes from a
  fixed per-expert capacity, tokens over capacity dropped, the exchange by
  ``all_to_all`` inside ``shard_map``.
* ``moe_held_apply`` (end of the file): the dropless layer for the experts
  one chip holds.  The router scores all experts; a row's pairs that land on
  a held expert are sorted by expert into a table of slots sized for any
  routing (a row picks distinct experts, so ``rows x min(k, held)`` slots
  and a tile of alignment an expert hold them all), and grouped products
  run over the table's tiles, every tile one expert's: nothing to drop,
  whatever the load, and the same work whatever the load.  What the experts
  held elsewhere would add is their chips' to compute; on one chip the
  layer runs with no exchange and nothing stands in for the absent chips.

The capacity layer:

The reference's closest capability is row-sparse embedding sharding across
parameter servers (SURVEY §2.5.6); it has no MoE.  This module supplies the
'ep' mesh axis promised by the parallel layer's design: experts live on
different devices, tokens are routed to expert owners with ``all_to_all``,
and the whole layer (gate → dispatch → expert FFN → combine) is one
compiled SPMD program.

Scheme (GShard/Switch dense-dispatch):
  * top-k softmax gate per token, with a fixed per-expert capacity C so all
    shapes are static (XLA requirement — no data-dependent shapes);
  * dispatch one-hot (T, E, C) built from a cumulative-sum position;
    tokens beyond capacity are dropped (their combine weight is zero),
    exactly the Switch-Transformer overflow rule;
  * ``all_to_all`` groups the (E, C, d) dispatched block by expert owner,
    each device applies its E/n local experts, a reverse ``all_to_all``
    brings results home, and the combine einsum restores (T, d).
"""
from __future__ import annotations

import functools


SIGMOID_NORM_EPS = 1e-6    # under a sigmoid router's normalisation


def route_top_k(scores, k, rank_by=None, eps=0.0, scale=1.0):
    """The router's choice, shared by both layers: of each token's scores
    ``(T, E)`` the ``k`` experts that ``rank_by`` ranks highest (the scores
    themselves where it is None; ties to the lower id), weighted by their
    scores over the sum of the picked ``k`` (+ ``eps``), times ``scale``
    (``norm_topk_prob``).  With softmax probabilities and the defaults that
    is Switch/GShard's renormalised top-k; a sigmoid router ranks by ``scores
    + bias`` and weighs by the scores alone (DeepSeek-V3's and LFM2's
    selection bias).  Returns (weights, expert ids), each ``(T, k)``."""
    import jax
    import jax.numpy as jnp
    if rank_by is None:
        vals, idx = jax.lax.top_k(scores, k)
    else:
        _, idx = jax.lax.top_k(rank_by, k)
        vals = jnp.take_along_axis(scores, idx, axis=-1)
    total = jnp.sum(vals, axis=-1, keepdims=True)
    # an ``eps`` of 0 and a ``scale`` of 1 add no operation: the softmax
    # router's traced program stays what it was
    weights = vals / (total + eps if eps else total)
    return (weights * scale if scale != 1.0 else weights), idx


def _one_hot_dispatch(gates, k, capacity):
    """Build dispatch/combine tensors from gate probs (T, E).

    Returns dispatch (T, E, C) float {0,1} and combine (T, E, C) floats.
    """
    import jax
    import jax.numpy as jnp

    T, E = gates.shape
    topk_vals, topk_idx = route_top_k(gates, k)          # (T, k)

    dispatch = jnp.zeros((T, E, capacity), dtype=gates.dtype)
    combine = jnp.zeros((T, E, capacity), dtype=gates.dtype)
    # running per-expert fill count across the k choices
    fill = jnp.zeros((E,), dtype=jnp.int32)
    for j in range(k):
        e_j = topk_idx[:, j]                              # (T,)
        onehot = jax.nn.one_hot(e_j, E, dtype=jnp.int32)  # (T, E)
        pos_in_e = jnp.cumsum(onehot, axis=0) - 1 + fill[None, :]
        pos = jnp.sum(pos_in_e * onehot, axis=1)          # (T,)
        keep = pos < capacity
        pos_c = jnp.clip(pos, 0, capacity - 1)
        upd = jax.nn.one_hot(e_j, E)[:, :, None] * \
            jax.nn.one_hot(pos_c, capacity)[:, None, :]
        upd = upd * keep[:, None, None]
        dispatch = dispatch + upd
        combine = combine + upd * topk_vals[:, j][:, None, None]
        fill = fill + jnp.sum(onehot, axis=0)
    return dispatch, combine


def moe_apply(expert_fn, expert_params, gate_w, x, axis_name="ep",
              k=2, capacity_factor=2.0):
    """Run inside shard_map: tokens x (T_local, d), experts 'ep'-sharded.

    expert_params: pytree, leaves with leading LOCAL expert axis (E/n).
    gate_w: (d, E) replicated router weights.
    expert_fn(params_for_one_expert, tokens (C', d)) -> (C', d_out); it is
    vmapped over the local expert axis.
    """
    import jax
    import jax.numpy as jnp
    from .collectives import all_to_all, axis_size

    n = axis_size(axis_name)
    T, d = x.shape
    E_local = jax.tree_util.tree_leaves(expert_params)[0].shape[0]
    E = E_local * n
    C = max(1, int(-(-T * k * capacity_factor // E)))  # ceil(T*k*cf/E)

    gates = jax.nn.softmax(x @ gate_w, axis=-1)           # (T, E)
    dispatch, combine = _one_hot_dispatch(gates, k, C)

    # (T, E, C) x (T, d) -> (E, C, d)
    dispatched = jnp.einsum("tec,td->ecd", dispatch, x)
    # group by owner: (n, E/n, C, d); all_to_all over the owner axis sends
    # my block for expert-group g to device g, receiving every device's
    # block for MY experts stacked on a new leading axis
    dispatched = dispatched.reshape((n, E_local, C, d))
    exchanged = all_to_all(dispatched, axis_name, split_axis=0,  # mxshard: reshard-ok(MoE dispatch: route capacity blocks to their expert owners)
                           concat_axis=0, tiled=False)  # (n, E/n, C, d)
    # fold senders into the capacity axis and run the local experts
    tokens = jnp.swapaxes(exchanged, 0, 1).reshape((E_local, n * C, d))
    outs = jax.vmap(expert_fn)(expert_params, tokens)      # (E/n, n*C, d_out)
    d_out = outs.shape[-1]
    outs = jnp.swapaxes(outs.reshape((E_local, n, C, d_out)), 0, 1)
    # route results back to their senders
    returned = all_to_all(outs, axis_name, split_axis=0,  # mxshard: reshard-ok(MoE combine: return expert outputs to their senders)
                          concat_axis=0, tiled=False)  # (n, E/n, C, d_out)
    expert_out = returned.reshape((E, C, d_out))
    return jnp.einsum("tec,ecd->td", combine, expert_out)


def make_expert_parallel_moe(mesh, expert_fn, axis_name="ep", k=2,
                             capacity_factor=2.0):
    """Build a jitted MoE layer over ``mesh``.

    Returns ``moe(expert_params, gate_w, x)`` with
      expert_params leaves: leading GLOBAL expert axis, 'ep'-sharded;
      gate_w (d, E) replicated; x (B, d) sharded over 'ep' on the batch
      (tokens ride the same axis the experts live on — the standard
      dp==ep co-located layout).
    """
    import jax
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    n = int(mesh.shape[axis_name])

    def run(expert_params, gate_w, x):
        E = jax.tree_util.tree_leaves(expert_params)[0].shape[0]
        if E % n:
            raise ValueError(
                "expert-parallel moe: expert count of %d is not divisible "
                "by the mesh %r axis extent %d" % (E, axis_name, n))
        if x.shape[0] % n:
            raise ValueError(
                "expert-parallel moe: token batch of %d is not divisible "
                "by the mesh %r axis extent %d" % (x.shape[0], axis_name, n))
        p_specs = jax.tree_util.tree_map(
            lambda l: P(axis_name, *([None] * (l.ndim - 1))), expert_params)
        fn = shard_map(
            functools.partial(moe_apply, expert_fn, axis_name=axis_name,
                              k=k, capacity_factor=capacity_factor),
            mesh=mesh,
            in_specs=(p_specs, P(), P(axis_name)),
            out_specs=P(axis_name), check_vma=False)
        return fn(expert_params, gate_w, x)

    return jax.jit(run)


# ---------------------------------------------------------------------------
# the dropless layer for the experts held here
# ---------------------------------------------------------------------------

def route_tokens(x, router_w, k, scoring="softmax", scale=1.0, bias=None,
                 eps=SIGMOID_NORM_EPS):
    """The router of ``moe_held_apply``: (weights, expert ids), each (T, k),
    of tokens ``x`` (T, d) over all the experts of ``router_w`` (E, d), from
    float32 logits at precision 'highest'; ``scoring``, ``scale``, ``bias``
    and ``eps`` as there."""
    import jax
    import jax.numpy as jnp
    logits = jnp.dot(x.astype(jnp.float32), router_w.T.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if scoring == "softmax":
        return route_top_k(jax.nn.softmax(logits, axis=-1), k, scale=scale)
    if scoring != "sigmoid":
        raise ValueError("a router scores by softmax or sigmoid, not %r"
                         % (scoring,))
    scores = jax.nn.sigmoid(logits)
    return route_top_k(scores, k, None if bias is None
                       else scores + bias.astype(jnp.float32), eps, scale)


def slot_table(experts, first_expert, held, tm):
    """Where every (row, pick) pair of ``experts`` (T, k) goes in the table
    of slots that the held experts' products run over, and what each slot
    holds.  The table has ``S = ceil(T min(k, held) / tm) tm + held tm``
    slots in tiles of ``tm``: a row picks distinct experts, so at most
    ``min(k, held)`` of its pairs land on the ``held`` experts here, whatever
    the router does, and each expert's group is padded to whole tiles (at
    most ``tm`` slots an expert; an expert with no pair keeps one empty
    tile, so that its gradient is written).  ``S`` and the number of tiles
    follow from the shapes alone.

    One sort of the ``T k`` pairs and the padding entries (``N`` in all, at
    least ``S``) by (expert | not held here, padding last, then the entry's
    own number) lays them out: an entry's position in the sorted order is its slot.  The pairs of
    experts held elsewhere and the padding no group needs fill the tail,
    which belongs to the last held expert and carries weight 0; what lies
    past ``S`` is in no tile.  A second sort inverts the permutation.
    Returns ``source`` (N,): the entry at each position, a pair ``t k + j``
    or a number from ``T k`` up for padding; ``slot_of`` (N,): each entry's
    position; ``tile_expert`` (S / tm,): each tile's held expert; ``here``
    (T, k): whether the pair's expert is held here; and the held experts'
    loads (held,)."""
    import jax
    import jax.numpy as jnp
    T, k = experts.shape
    pairs = T * k
    slots = -(-T * min(k, held) // tm) * tm + held * tm
    padding = max(slots - pairs, held * tm)
    local = experts - first_expert
    here = (local >= 0) & (local < held)
    group = jnp.where(here, local, held).reshape(pairs)
    per_expert = jnp.sum(group[:, None] == jnp.arange(held), axis=0)
    # what completes each group's last tile; a whole tile for an empty group
    fill = jnp.where(per_expert == 0, tm, -per_expert % tm)
    pad = jnp.arange(padding)
    pad_group = jnp.minimum(pad // tm, held - 1)
    needed = (pad < held * tm) & (pad % tm < fill[pad_group])
    keys = jnp.concatenate([2 * group, jnp.where(
        needed, 2 * pad_group, 2 * held) + 1]).astype(jnp.int32)
    entries = pairs + padding
    entry = jnp.arange(entries, dtype=jnp.int32)
    # every key made its entry's own, so that no sort here need be stable:
    # XLA's stable sort of 70 k pairs is 2.5 MB of program and half a minute
    # of compiling, an unstable one of single numbers 0.9 MB and 2.5 s
    if (2 * held + 2) * entries < 2 ** 31:
        both = jax.lax.sort(keys * entries + entry, is_stable=False)
        keys, source = both // entries, both % entries
    else:
        keys, source = jax.lax.sort((keys, entry), num_keys=2,
                                    is_stable=False)
    slot_of = _moved(source, entry)
    tile_expert = jnp.minimum(keys[:slots:tm] // 2, held - 1)
    return source, slot_of, tile_expert, here, per_expert


def _moved(by, values):
    """``values`` in the order of the permutation ``by``'s inverse:
    ``out[by[i]] = values[i]``, by a sort on ``by`` (whose numbers differ, so
    an unstable one): a scatter or a gather of as many single numbers takes
    the TPU six times as long."""
    import jax
    return jax.lax.sort((by, values), num_keys=1, is_stable=False)[1]


def _rows_of(a, index):
    return a.at[index].get(mode="promise_in_bounds")


def from_slots(experts, a_s, slot_of, here):
    """(T, width) float32: the values of each row's slots of float32 ``a_s``
    (S, width) added up, for the slot table of ``slot_table`` (``slot_of``,
    ``here`` (T, k) as it gives them) whose empty slots hold 0.  Where
    ``experts`` (``ops.pallas_ops._Experts``) runs its kernels, its
    ``combine``: a tile of rows' slots with an expert are one run of
    consecutive slots, read as a slab at the memory's bandwidth, and a pair
    of an expert held elsewhere is never read.  Elsewhere XLA's form, which
    is also the kernel's oracle: every (row, pick) pair's slot gathered and
    the picks added; a pair of an expert held elsewhere sits in an empty
    slot or past the table, where the table's last slot stands in, which
    is empty, since the tail never is.  The two differ only in the order of
    their float32 additions."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope("moe.combine"):
        if experts.kernels:
            return experts.combine(a_s, slot_of, here)
        T, k = here.shape
        slot_of_pair = jnp.minimum(slot_of[:T * k], a_s.shape[0] - 1)
        return jnp.sum(_rows_of(a_s, slot_of_pair.reshape(T, k).T), axis=0)


def _held_experts(x, weights, gate_w, up_w, down_w, source, slot_of,
                  tile_expert, here, tm, act="swiglu"):
    """``sum_j weights[t, j] expert_j(x[t])`` over each row's pairs that
    ``here`` marks, through the slot table of ``slot_table``: rows gathered
    to slots, the grouped products of ``ops.pallas_ops._Experts`` over whole
    tiles, and each row's slots added up (``from_slots``: on a TPU one
    kernel that reads each tile of rows' run of slots with each expert).
    Rows go to slots by gathers by ``source``, and a value a slot or a pair
    moves the other way by a sort on ``source`` or ``slot_of`` (the
    permutation and its inverse): the backward pass is written out, since
    the transpose of a gather is a scatter-add.  ``act`` as
    ``ops.pallas_ops._Experts`` takes it (``gate_w`` None for relu²)."""
    import jax
    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name
    from ..ops.pallas_ops import SLOT_TABLE, _Experts

    (T, d), k = x.shape, weights.shape[1]
    held, f, _ = up_w.shape
    pairs, entries = T * k, source.shape[0]
    slots = tile_expert.shape[0] * tm

    def to_slots(experts, x, weights, source, slot_of, here):
        with jax.named_scope("moe.sort"):
            row_of_slot = jnp.where(source < pairs, source // k, 0)[:slots]
            w_entry = jnp.pad(jnp.where(here, weights, 0.0).reshape(pairs),
                              (0, entries - pairs))
            return row_of_slot, checkpoint_name(
                _moved(slot_of, w_entry)[:slots], SLOT_TABLE), \
                _rows_of(experts.cast(x), row_of_slot)

    def forward(keep, x, weights, gate_w, up_w, down_w, source, slot_of,
                tile_expert, here):
        experts = _Experts(tile_expert, tm, d, f, held, act=act)
        row_of_slot, w_s, x_s = to_slots(experts, x, weights, source,
                                         slot_of, here)
        with jax.named_scope("moe.experts"):
            matrices = tuple(None if w is None else experts.cast(w)
                             for w in (gate_w, up_w, down_w))
            hw, *kept = experts.hidden(x_s, w_s, *matrices[:2], keep=keep)
            if gate_w is None and kept:         # relu² keeps no gate
                kept = [None] + kept
            y_s = experts.down(hw, matrices[2])
        return from_slots(experts, y_s, slot_of, here).astype(x.dtype), (
            row_of_slot, w_s, x_s, hw, *kept, *matrices, source, slot_of,
            tile_expert, here)

    @jax.custom_vjp
    def layer(*args):
        return forward(False, *args)[0]

    def layer_bwd(kept, dout):
        row_of_slot, w_s, x_s, hw, g, u, gate_w, up_w, down_w, source, \
            slot_of, tile_expert, here = kept
        experts = _Experts(tile_expert, tm, d, f, held, act=act)
        with jax.named_scope("moe.sort"):
            dy_s = _rows_of(experts.cast(dout), row_of_slot)
        with jax.named_scope("moe.experts"):
            dx_s, dw_s, dg, du = experts.backward(dy_s, g, u, w_s, gate_w,
                                                  up_w, down_w)
            d_matrices = experts.weight_gradients(x_s, dy_s, dg, du, hw)
            if gate_w is None:
                d_matrices = (None,) + d_matrices
        dx = from_slots(experts, dx_s, slot_of, here).astype(dout.dtype)
        with jax.named_scope("moe.sort"):
            dw_entry = _moved(source, jnp.pad(dw_s, (0, entries - slots)))
            d_weights = jnp.where(here, dw_entry[:pairs].reshape(T, k), 0.0)
        return (dx, d_weights.astype(dout.dtype),
                *(None if m is None else m.astype(dout.dtype)
                  for m in d_matrices),
                None, None, None, None)

    layer.defvjp(functools.partial(forward, True), layer_bwd)
    return layer(x, weights, gate_w, up_w, down_w, source, slot_of,
                 tile_expert, here)


def moe_held_apply(x, router_w, gate_w, up_w, down_w, k, first_expert=0,
                   scoring="softmax", scale=1.0, bias=None, act="swiglu",
                   eps=SIGMOID_NORM_EPS):
    """The held experts' part of a mixture-of-experts layer, no token
    dropped.

    x: (T, d) tokens.  router_w: (E, d), the router over ALL experts.
    gate_w, up_w: (E_held, f, d); down_w: (E_held, d, f): the matrices of
    experts ``first_expert .. first_expert + E_held - 1``, which live here.
    ``act`` is what an expert computes: "swiglu", ``down(silu(gate y) * up
    y)``, or "relu2", ``down(relu(up y)^2)`` with ``gate_w`` None.

    The router's form is the model's: ``scoring`` "softmax" (probabilities
    over all E, the ``k`` largest, renormalised) or "sigmoid" (each logit's
    sigmoid; the ``k`` largest of ``score + bias`` where a ``bias`` (E,) is
    given, weighted by the scores without it over their sum + ``eps``,
    ``SIGMOID_NORM_EPS`` unless given); either times ``scale``.  The bias
    enters the selection alone, so no gradient reaches it.

    A row's picked experts are computed, not every held one.  The (row,
    pick) pairs are sorted by expert into a table of ``T min(k, E_held)``
    slots plus a tile of alignment an expert (``slot_table``; the tile's
    rows from ``ops.pallas_ops.slot_tile_rows``), which holds every pair of
    every possible routing, since a row picks distinct experts: nothing is
    dropped and nothing overflows, one expert taking every row included.
    Every tile is one expert's and **every slot is computed every step**, an
    empty one with weight 0: the number of slots, tiles and grid steps
    follows from the shapes alone, so a step's time does not depend on the
    data.  That is half the expert rows of computing every held expert for
    every row (``T E_held``: the form until PR 36, 40 ms a layer at 8,192
    rows of 16 experts of 768 on one v5e and 107.5 at 16,384 rows of 8 of
    1536) and still ``E / k`` / 2 = 8 times what an even load fills:
    skipping the empty tiles would follow the load, and a load-following
    layer's time follows the router, which from seeded weights collapses by
    the seed (PR 29 measured windows of sorted pairs at 444 to 38,892 pairs
    a layer: 2.5% between seeds); PERF.md §6 and §7 have this form's
    numbers and what has to change before the empty tiles can go.  The
    table's sorts are named ``ops.pallas_ops.SLOT_TABLE`` for a recomputed
    layer's policy: a sort of 70 k keys is 1 to 1.5 MB of compiled program.

    Returns ``(out, load)``: ``out`` (T, d) is
    ``sum_e w_e expert_e(x)`` over each token's chosen experts that are held
    here (``w`` the router's weights, from float32 scores over all E);
    ``load`` is float32 ``[pairs routed here, the largest held expert's
    load]``."""
    import jax
    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name
    from .. import profiler
    from ..ops.pallas_ops import SLOT_TABLE, _Experts, slot_tile_rows

    T, d = x.shape
    E, held = router_w.shape[0], up_w.shape[0]
    tm = slot_tile_rows(T * min(k, held), held)
    profiler.count("moe.experts_held", held)
    profiler.count("moe.rows", T)

    with jax.named_scope("moe.route"):
        weights, experts = route_tokens(x, router_w, k, scoring, scale, bias,
                                        eps)
    with jax.named_scope("moe.sort"):
        source, slot_of, tile_expert, here, per_expert = slot_table(
            experts, first_expert, held, tm)
        # named, so that a recomputed layer can keep the sorts' results
        # (hybridize(remat_policy=ATTENTION_RESIDUALS)); an identity elsewhere
        source, slot_of, tile_expert = (checkpoint_name(a, SLOT_TABLE)
                                        for a in (source, slot_of, tile_expert))
        load = jnp.stack([jnp.sum(per_expert),
                          jnp.max(per_expert)]).astype(jnp.float32)
    profiler.count("moe.slots", tile_expert.shape[0] * tm)
    profiler.count("moe.combine_runs", _Experts(
        tile_expert, tm, d, up_w.shape[1], held).combine_runs(T))
    return _held_experts(x, weights.astype(x.dtype), gate_w, up_w, down_w,
                         source, slot_of, tile_expert, here, tm, act), load
