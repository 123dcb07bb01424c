"""Ulysses-style sequence parallelism: all-to-all head/sequence exchange.

Complement to ring attention (ring_attention.py): instead of rotating K/V
blocks around the ring, ONE ``all_to_all`` re-shards the activations from
sequence-sharded (every device holds all heads for T/n tokens) to
head-sharded (every device holds H/n heads for ALL tokens), runs exact
local attention per head group, and a second ``all_to_all`` restores the
sequence sharding.  (DeepSpeed-Ulysses scheme; on TPU the all_to_alls are
single ICI collectives.)

Trade-off vs ring: 2 all-to-alls of the full activations instead of N-1
K/V ppermutes — better when H >= n and the sequence is only moderately
long; ring wins at extreme sequence lengths where K/V never fit.  The
reference has neither (SURVEY §5: no long-context mechanism exists).
"""
from __future__ import annotations

import functools

import numpy as _np


def ulysses_attention_local(q, k, v, axis_name="sp", causal=False, scale=None):
    """Run inside shard_map with q,k,v (B, H, T_local, D), T-sharded.

    Requires H % n == 0 (validated eagerly at trace time; the tiled
    all_to_all would otherwise fail with an opaque shape error).
    """
    import jax.numpy as jnp
    from .collectives import all_to_all, axis_size

    n = axis_size(axis_name)
    if q.shape[1] % n:
        raise ValueError(
            "ulysses_attention_local: head count of %d is not divisible by "
            "the mesh %r axis extent %d; use ring attention instead"
            % (q.shape[1], axis_name, n))
    if scale is None:
        scale = 1.0 / _np.sqrt(q.shape[-1])

    # (B, H, T/n, D) -> (B, H/n, T, D): split heads, gather sequence
    def fwd(x):
        return all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)  # mxshard: reshard-ok(Ulysses T->H re-shard: one a2a instead of N-1 K/V ppermutes)

    def rev(x):
        return all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                          tiled=True)  # mxshard: reshard-ok(Ulysses H->T re-shard restoring the sequence sharding)

    qh, kh, vh = fwd(q), fwd(k), fwd(v)
    s = jnp.einsum("bhqd,bhkd->bhqk", qh.astype(jnp.float32),
                   kh.astype(jnp.float32)) * scale
    if causal:
        T = s.shape[-1]
        mask = jnp.tril(jnp.ones((T, T), dtype=bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vh.astype(jnp.float32))
    return rev(o.astype(q.dtype))


def ulysses_parallel_attention(mesh, q, k, v, causal=False, axis_name="sp"):
    """Convenience wrapper: (B, H, T, D) tensors sharded over ``axis_name``
    on the T axis, exact attention via the two-all-to-all scheme."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    n = mesh.shape[axis_name]
    if q.shape[1] % n:
        raise ValueError("ulysses needs heads (%d) divisible by %s axis (%d); "
                         "use ring attention instead" % (q.shape[1], axis_name, n))
    if q.shape[2] % n:
        raise ValueError(
            "ulysses: sequence length of %d is not divisible by the mesh %r "
            "axis extent %d" % (q.shape[2], axis_name, n))
    spec = P(None, None, axis_name, None)

    @functools.partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    def run(q_, k_, v_):
        return ulysses_attention_local(q_, k_, v_, axis_name=axis_name,
                                       causal=causal)

    return run(q, k, v)
