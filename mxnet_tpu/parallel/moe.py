"""Expert parallelism: a mixture-of-experts layer sharded over an 'ep' axis.

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


def _one_hot_dispatch(gates, k, capacity):
    """Build dispatch/combine tensors from gate probs (T, E).

    Returns dispatch (T, E, C) float {0,1} and combine (T, E, C) floats.
    """
    import jax
    import jax.numpy as jnp

    T, E = gates.shape
    topk_vals, topk_idx = jax.lax.top_k(gates, k)        # (T, k)
    # renormalize the selected gates (Switch/GShard convention)
    topk_vals = topk_vals / jnp.sum(topk_vals, axis=-1, keepdims=True)

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
