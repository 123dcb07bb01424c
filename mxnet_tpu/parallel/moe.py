"""Expert parallelism: mixture-of-experts layers for an 'ep' axis.

Two layers share the router's choice (``route_top_k``: the ``k`` experts
ranked highest, their weights normalised over the picked ``k``; softmax
probabilities that rank and weigh alike, or sigmoid scores ranked with a
selection bias that stays out of the weights):

* ``moe_apply`` (below, first): the capacity layer.  Static shapes from a
  fixed per-expert capacity, tokens over capacity dropped, the exchange by
  ``all_to_all`` inside ``shard_map``.
* ``moe_held_apply`` (end of the file): the dropless layer for the experts
  one chip holds.  The router scores all experts; every held expert is
  computed for every token and weighted by the router's weight for that
  token, 0 where the token did not choose it: nothing to drop, whatever the
  load.  What the experts held elsewhere would add is their chips' to
  compute; on one chip the layer runs with no exchange and nothing stands in
  for the absent chips.

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

def route_tokens(x, router_w, k, scoring="softmax", scale=1.0, bias=None):
    """The router of ``moe_held_apply``: (weights, expert ids), each (T, k),
    of tokens ``x`` (T, d) over all the experts of ``router_w`` (E, d), from
    float32 logits at precision 'highest'; ``scoring``, ``scale`` and
    ``bias`` as there."""
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
                       else scores + bias.astype(jnp.float32),
                       SIGMOID_NORM_EPS, scale)


def moe_held_apply(x, router_w, gate_w, up_w, down_w, k, first_expert=0,
                   scoring="softmax", scale=1.0, bias=None):
    """The held experts' part of a mixture-of-experts layer, no token
    dropped.

    x: (T, d) tokens.  router_w: (E, d), the router over ALL experts.
    gate_w, up_w: (E_held, f, d); down_w: (E_held, d, f): the matrices of
    experts ``first_expert .. first_expert + E_held - 1``, which live here.
    Expert e computes ``down(silu(gate y) * up y)``.

    The router's form is the model's: ``scoring`` "softmax" (probabilities
    over all E, the ``k`` largest, renormalised) or "sigmoid" (each logit's
    sigmoid; the ``k`` largest of ``score + bias`` where a ``bias`` (E,) is
    given, weighted by the scores without it over their sum +
    ``SIGMOID_NORM_EPS``); either times ``scale``.  The bias enters the
    selection alone, so no gradient reaches it.

    Every held expert's hidden units are computed for every token, as one
    feed-forward of width ``E_held * f`` (three plain products on the MXU),
    and a token's hidden units of an expert it did not choose are multiplied
    by 0, of one it chose by the router's weight.  The work is the same
    whatever the router does: no sort, no gather or scatter, no buffer that
    a load can overflow, and a step's time does not depend on the data.
    That is ``E / k`` times the products an even load needs (16 times with 8
    of 128 experts per token), so it is the slower form wherever the load is
    near even: sorted pairs through grouped products were probed at 2.8 ms a
    layer against this form's 40 at 8,192 tokens on one v5e (PERF.md, PR 29).
    It is here because a load-following layer's time follows the router, and
    from seeded weights the router collapses (one expert takes most rows, by
    the seed), where this form's time is the same for every seed; PERF.md §7
    has what has to change before the sorted form can be measured.

    Returns ``(out, load)``: ``out`` (T, d) is
    ``sum_e w_e expert_e(x)`` over each token's chosen experts that are held
    here (``w`` the router's weights, from float32 scores over all E);
    ``load`` is float32 ``[pairs routed here, the largest held expert's
    load]``."""
    import jax
    import jax.numpy as jnp
    from .. import profiler

    T, d = x.shape
    E, (held, f, _) = router_w.shape[0], gate_w.shape
    profiler.count("moe.layers")
    profiler.count("moe.experts_held", held)
    profiler.count("moe.experts_total", E)
    profiler.count("moe.rows", T)

    with jax.named_scope("moe.route"):
        weights, experts = route_tokens(x, router_w, k, scoring, scale, bias)
        # (T, held): the weight of each held expert for each token, 0 where
        # the token did not choose it
        chosen = (experts - first_expert)[:, :, None] == jnp.arange(held)
        gates = jnp.sum(jnp.where(chosen, weights[:, :, None], 0.0), axis=1)
        per_expert = jnp.sum(chosen, axis=(0, 1))
        load = jnp.stack([jnp.sum(per_expert),
                          jnp.max(per_expert)]).astype(jnp.float32)

    with jax.named_scope("moe.experts"):
        gate = jnp.dot(x, gate_w.reshape(held * f, d).T)       # (T, held*f)
        up = jnp.dot(x, up_w.reshape(held * f, d).T)
        hidden = (jax.nn.silu(gate) * up).reshape(T, held, f)
    with jax.named_scope("moe.combine"):
        hidden = hidden * gates.astype(x.dtype)[:, :, None]
        return jnp.einsum("tef,edf->td", hidden, down_w), load

