"""Ring attention: sequence/context parallelism over a mesh axis.

The reference has NO long-context mechanism (SURVEY §5 "Long-context /
sequence parallelism: None exists") — this module is the TPU-native design
that makes the sequence axis a first-class mesh dimension:

  * queries stay resident on their shard;
  * key/value blocks rotate around the ring via ``ppermute`` (one ICI hop per
    step), overlapping the next block's transfer with the current block's
    flash-attention compute;
  * softmax is computed in the streaming (log-sum-exp accumulator) form so the
    result is exact, not approximate.

This is the Liu et al. ring-attention scheme expressed with shard_map +
lax.ppermute; XLA overlaps the collective-permute with the matmuls.
"""
from __future__ import annotations

import functools

import numpy as _np


def _block_attention(q, k, v, m_prev, l_prev, o_prev, scale, causal_mask=None):
    """One block of streaming softmax attention.

    q: (B, H, Tq, D); k,v: (B, H, Tk, D); accumulators m,l,o.
    Returns updated (m, l, o)."""
    import jax.numpy as jnp
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale            # MXU matmul
    if causal_mask is not None:
        s = jnp.where(causal_mask, s, -1e30)
    m_cur = jnp.max(s, axis=-1)                                 # (B,H,Tq)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new[..., None])
    l_cur = jnp.sum(p, axis=-1)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + l_cur
    o_new = o_prev * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return m_new, l_new, o_new


def ring_attention(q, k, v, axis_name="sp", causal=False, scale=None):
    """Exact attention with K/V sharded over ``axis_name``.

    Call inside shard_map with q,k,v already sharded on the sequence axis:
    q: (B, H, T_local, D).  Rotates K/V around the ring; N-1 ppermutes total.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from .collectives import axis_size, ppermute

    if scale is None:
        scale = 1.0 / _np.sqrt(q.shape[-1])
    n = axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    B, H, Tq, D = q.shape
    Tk = k.shape[2]

    m = jnp.full((B, H, Tq), -1e30, dtype=jnp.float32)
    l = jnp.zeros((B, H, Tq), dtype=jnp.float32)
    o = jnp.zeros((B, H, Tq, D), dtype=jnp.float32)

    def make_mask(kv_idx):
        if not causal:
            return None
        q_pos = my_idx * Tq + jnp.arange(Tq)
        k_pos = kv_idx * Tk + jnp.arange(Tk)
        return q_pos[:, None] >= k_pos[None, :]

    def body(i, carry):
        m_, l_, o_, k_, v_ = carry
        kv_idx = (my_idx - i) % n
        mask = make_mask(kv_idx)
        mask_b = None if mask is None else mask[None, None]
        m2, l2, o2 = _block_attention(q.astype(jnp.float32),
                                      k_.astype(jnp.float32),
                                      v_.astype(jnp.float32),
                                      m_, l_, o_, scale, mask_b)
        # rotate kv to the next rank; overlaps with next iteration's compute
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_next = ppermute(k_, axis_name, perm)  # mxshard: reshard-ok(ring rotation: one K block per hop, N-1 hops total, overlapped with compute)
        v_next = ppermute(v_, axis_name, perm)  # mxshard: reshard-ok(ring rotation: one V block per hop, N-1 hops total, overlapped with compute)
        return m2, l2, o2, k_next, v_next

    m, l, o, _, _ = lax.fori_loop(0, n, body, (m, l, o, k, v))
    out = o / l[..., None]
    return out.astype(q.dtype)


def sequence_parallel_attention(mesh, q, k, v, causal=False):
    """Convenience wrapper: shard (B, H, T, D) tensors over the 'sp' axis on T
    and run ring_attention under shard_map."""
    import jax
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    n = int(mesh.shape["sp"])
    if q.shape[2] % n:
        raise ValueError(
            "ring attention: sequence length of %d is not divisible by the "
            "mesh 'sp' axis extent %d" % (q.shape[2], n))
    spec = P(None, None, "sp", None)

    @functools.partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    def run(q_, k_, v_):
        return ring_attention(q_, k_, v_, axis_name="sp", causal=causal)

    return run(q, k, v)
