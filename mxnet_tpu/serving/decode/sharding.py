"""Tensor-sharded decode: compute-parallel Megatron kernels over a mesh.

A model whose K/V pool or weights exceed one device serves through
:class:`ShardedDecodeModel`, a wrapper that satisfies the SAME contract
as the model it wraps (model.py docstring) but keeps both storage AND
compute on the shard over a ``tp`` mesh axis:

* **paged K/V pools are head-sharded device arrays** — the pool keeps the
  contract layout ``[layers, blocks, block_size, heads, dim]`` but the
  heads axis is split ``heads/tp`` per device (page tables and the
  block-0 trash-block convention are replicated, so the PagedKVCache
  host-side accounting is untouched);
* **weights are sharded per the model's ``partition_specs()``** — the
  Megatron recipe those specs already encode: qkv/up projections
  column-parallel (``P(None, 'tp')``), wo/down row-parallel
  (``P('tp', None)``), embedding/positions column-sharded;
* **every contract fn runs as a ``shard_map``** of a compute-parallel
  kernel: each device contracts its LOCAL weight shard against the
  replicated residual stream, runs paged attention over its LOCAL head
  slice of the pool (the new K/V never leave their shard — no gather at
  all), and each Megatron half-block ends in exactly ONE psum of the
  row-parallel partial products.  A decode step's whole collective bill
  is ``2 * num_layers + 2`` psums (one exact scatter-assembly psum for
  the column-sharded embedding, two block psums per layer, one for the
  weight-tied unembedding) and ZERO all_gathers — the PR 15
  gather-at-use wrapper paid 16 gathers per step for bitwise math; this
  kernel deletes that tax.

**Exactness policy** (the documented bitwise relaxation): psum member
order differs from the single-device serial reduction, so sharded logits
are ALLCLOSE — not bitwise — to the unsharded reference.  Greedy token
streams stay token-identical (the engine gate), sampled streams replay
token-identically through the host-side float64 sampler, and any two
runs of the SAME sharded geometry remain bitwise because XLA's reduction
order is deterministic per executable.  The two psums whose inputs have
exactly one nonzero contributor per element (embedding assembly) stay
order-free and bitwise-exact by construction.

**Quantized wire** (opt-in): ``ShardedDecodeModel(..., wire="2bit",
wire_threshold=t)`` routes the per-block psums through the PR 10
error-feedback sign codec (``gradient_compression.quantize_2bit``) in
its stateless serving instantiation — ±1 int8 codes at ``|y| >= t``,
psum of the codes on the wire (4x fewer bytes than fp32), dequantized
``* t`` on arrival.  Fixed-shape decode steps cannot carry a residual,
so the codec runs residual-free and is LOSSY: an accuracy envelope, not
an exactness gate.  The embedding-assembly and unembedding psums stay
exact fp32 so the argmax surface is never quantized.

Long-context attention routes through the dormant ``parallel/`` kernels:
:func:`long_context_attention` is an inside-``shard_map`` router that
splits the sequence over an ``sp`` axis and dispatches Ulysses all-to-all
head sharding (`ulysses.py`) when heads divide the axis, streaming ring
attention (`ring_attention.py`) otherwise, then gathers the full output
back.  MoE feed-forward layers shard experts the same way through
:func:`expert_sharded_ffn` (`moe.py`).  Both are *fused* paths outside
the decode-step psum budget; a model that sets ``context_attention``
cannot wrap in :class:`ShardedDecodeModel` (the compute-parallel kernels
run head-local attention and do not route the fused path).

Sharding-shape validation happens HERE, eagerly, with ValueErrors naming
both extents (the `shard_batch` convention) — never as a shape error
inside ``shard_map``.
"""
from __future__ import annotations

import numpy as _np

from .model import _rms, _softmax

__all__ = ["ShardedDecodeModel", "decode_mesh", "long_context_attention",
           "expert_sharded_ffn", "check_tp_divisible",
           "check_pool_matches_mesh", "POOL_HEAD_AXIS"]

# contract pool layout [layers, blocks, block_size, heads, dim]: the axis
# the 'tp' shards split
POOL_HEAD_AXIS = 3

# the canonical decode-model parameter schema the compute-parallel
# kernels are written against (TinyCausalLM and the Gluon adapter both
# emit it): per-layer dense roles plus "embed"/"pos"
_DENSE_ROLES = ("wq", "wk", "wv", "wo", "w1", "w2")


def check_tp_divisible(name, extent, tp, what="head count", axis="tp"):
    """Raise ValueError naming both extents unless ``extent % tp == 0``."""
    if int(extent) % int(tp):
        raise ValueError(
            "%s: %s of %d is not divisible by the mesh %r axis extent %d"
            % (name, what, int(extent), axis, int(tp)))
    return int(extent) // int(tp)


def check_pool_matches_mesh(name, pool_shape, mesh):
    """A K/V pool is head-shardable over ``mesh`` iff its head axis
    divides the 'tp' extent; raise naming both extents otherwise."""
    tp = int(mesh.shape["tp"])
    if len(pool_shape) != 5:
        raise ValueError(
            "%s: pool shape %r is not the contract layout "
            "[layers, blocks, block_size, heads, dim]"
            % (name, tuple(pool_shape)))
    check_tp_divisible(name, pool_shape[POOL_HEAD_AXIS], tp,
                       what="pool head axis")
    return tp


def decode_mesh(tp, sp=1, devices=None):
    """Build the ('tp', 'sp') serving mesh over EXACTLY tp*sp devices.

    ``make_mesh`` folds leftover devices into the leading axis — right
    for training (use everything), wrong for serving where a tp=2 engine
    must consume exactly 2 devices so the fleet can place others on the
    rest.  Without ``devices`` the mesh starts at the current context's
    device and takes the following local devices (wrapping), so two
    meshes built under different contexts hold different chips — which
    is how ``FleetRouter`` places sharded replicas.  Raises ValueError
    naming both extents when the machine cannot honor the request."""
    import jax
    from jax.sharding import Mesh
    from ...context import current_context
    tp, sp = int(tp), int(sp)
    if tp < 1 or sp < 1:
        raise ValueError("decode_mesh: tp=%d, sp=%d must both be >= 1"
                         % (tp, sp))
    need = tp * sp
    if devices is None:
        first_dev = current_context().jax_device()
        local = jax.local_devices(backend=first_dev.platform)
        first = local.index(first_dev)
        devices = (local[first:] + local[:first])[:need]
    if len(devices) < need:
        raise ValueError(
            "decode_mesh: tp=%d x sp=%d needs %d device(s); only %d "
            "available" % (tp, sp, need, len(devices)))
    dev = _np.array(devices[:need]).reshape(tp, sp)
    return Mesh(dev, ("tp", "sp"))


# ---------------------------------------------------------------------------
# fused long-context / MoE paths (inside-shard_map helpers)
# ---------------------------------------------------------------------------

def long_context_attention(q, k, v, causal=True, axis_name="sp",
                           fallback=None):
    """Sequence-parallel attention for use INSIDE a shard_map body.

    Takes the FULL ``[B, H, T, D]`` operands (replicated across the
    ``sp`` members), splits the sequence so each member computes its T/n
    slice through the Ulysses all-to-all kernel when ``H % n == 0`` — one
    head group per member, full sequence per head — or the streaming ring
    kernel otherwise, then all-gathers the slices back to the full output
    every member returns.  Numerically allclose (NOT bitwise) to dense
    masked attention: both kernels mask with -1e30 and the ring streams
    its softmax.  T must divide the axis extent; when it does not (short
    prompt buckets) the call routes to ``fallback(q, k, v)`` if given —
    the model's own dense attention — and raises the ValueError naming
    both extents otherwise."""
    import jax
    from ...parallel import allgather, axis_size, ring_attention, \
        ulysses_attention_local
    n = axis_size(axis_name)
    T = q.shape[2]
    if fallback is not None and (n == 1 or T % n):
        return fallback(q, k, v)
    loc = check_tp_divisible("long_context_attention", T, n,
                             what="sequence length", axis=axis_name)
    i = jax.lax.axis_index(axis_name)
    ql, kl, vl = (jax.lax.dynamic_slice_in_dim(x, i * loc, loc, axis=2)
                  for x in (q, k, v))
    if q.shape[1] % n == 0:
        out = ulysses_attention_local(ql, kl, vl, axis_name=axis_name,
                                      causal=causal)
    else:
        out = ring_attention(ql, kl, vl, axis_name=axis_name,
                             causal=causal)
    return allgather(out, axis_name, axis=2, tiled=True)  # mxshard: gather-ok(restore the full T axis every sp member returns; allclose fused path, not bitwise)


def expert_sharded_ffn(expert_fn, expert_params, gate_w, x, axis_name="sp",
                       k=2, capacity_factor=2.0):
    """Expert-parallel MoE feed-forward for use INSIDE a shard_map body.

    ``x`` is a ``[tokens, hidden]`` batch replicated across the axis
    members; experts dispatch through ``moe_apply`` (GShard dense
    dispatch, Switch overflow) with the expert set spread over the axis.
    The token count must divide the axis extent (moe_apply shards the
    token batch; ValueError names both extents here, not inside the
    collective)."""
    from ...parallel import axis_size
    from ...parallel.moe import moe_apply
    n = axis_size(axis_name)
    check_tp_divisible("expert_sharded_ffn", x.shape[0], n,
                       what="token count", axis=axis_name)
    check_tp_divisible("expert_sharded_ffn", gate_w.shape[-1], n,
                       what="expert count", axis=axis_name)
    return moe_apply(expert_fn, expert_params, gate_w, x,
                     axis_name=axis_name, k=k,
                     capacity_factor=capacity_factor)


# ---------------------------------------------------------------------------
# compute-parallel kernels (inside shard_map; every operand is the LOCAL
# shard, the residual stream h is replicated)
# ---------------------------------------------------------------------------

class _Geometry:
    """Static per-model facts the compute-parallel kernels close over."""

    __slots__ = ("num_layers", "num_heads", "local_heads", "head_dim",
                 "hidden", "hidden_local", "vocab_size", "max_len", "tp",
                 "gluon", "wire", "wire_threshold")

    def __init__(self, **kw):
        for name in self.__slots__:
            setattr(self, name, kw[name])


def _contract_local(geom, p):
    """Normalize local weight shards to the contract layout.

    Gluon dense layers store ``[units, in]`` — the transpose of the
    contract's ``[in, units]``.  Transposition swaps the sharded dim too,
    so the transpose of a Gluon LOCAL shard is exactly the contract
    layout's local shard: layout is erased device-locally, zero
    collectives."""
    if not geom.gluon:
        return p
    out = dict(p)
    for l in range(geom.num_layers):
        for role in _DENSE_ROLES:
            key = "l%d_%s" % (l, role)
            out[key] = out[key].T
    return out


def _assemble_replicated(geom, part):
    """Exact replicated assembly of a column-sharded activation.

    ``part`` is this member's ``hidden/tp`` column slice (embedding +
    positions read from the column-sharded tables).  Scatter it into a
    zeros-backed full-width buffer at the member's offset and psum: every
    element has exactly ONE nonzero contributor, so the reduction is
    order-free and bitwise-exact.  Deliberately a psum rather than an
    all_gather — it keeps the decode region inside the psum-only budget
    and XLA lowers a one-hot all-reduce to the same ICI traffic."""
    import jax
    import jax.numpy as jnp
    from ...parallel import allreduce
    i = jax.lax.axis_index("tp")
    full = jnp.zeros(part.shape[:-1] + (geom.hidden,), part.dtype)
    full = jax.lax.dynamic_update_slice_in_dim(
        full, part, i * geom.hidden_local, axis=part.ndim - 1)
    return allreduce(full, "tp")  # mxshard: allclose-ok(scatter-assembly psum: one nonzero contributor per element, order-free and bitwise-exact by construction)


def _block_psum(geom, y):
    """The ONE collective of a Megatron half-block: sum the row-parallel
    partial products (attention output after wo, MLP output after w2).
    Psum member order differs from the single-device serial sum, so the
    result is allclose — greedy token streams stay token-identical (the
    engine gate).  ``wire="2bit"`` reroutes through the sign codec."""
    from ...parallel import allreduce
    if geom.wire == "2bit":
        return _psum_2bit(geom, y)
    return allreduce(y, "tp")  # mxshard: allclose-ok(Megatron row-parallel reduction: psum member order differs from the single-device serial sum; logits allclose, greedy tokens identical)


def _psum_2bit(geom, y):
    """Quantized block psum: the PR 10 2-bit error-feedback codec
    (``gradient_compression.quantize_2bit``) in its stateless serving
    instantiation.  Fixed-shape decode steps cannot carry a residual
    across calls, so the codec runs residual-free: ±1 int8 codes where
    ``|y| >= wire_threshold``, int8 codes summed on the wire (4x fewer
    bytes than the fp32 partials), dequantized ``* wire_threshold`` on
    arrival.  Lossy by design — the accuracy envelope is documented in
    docs/SERVING.md and gated by tests, not by the bitwise contract."""
    import jax.numpy as jnp
    from ...gradient_compression import quantize_2bit
    from ...parallel import allreduce
    thr = geom.wire_threshold
    codes, _ = quantize_2bit(y, jnp.zeros_like(y), thr)
    total = allreduce(codes, "tp")  # mxshard: allclose-ok(2-bit EF wire: +-1 int8 sign codes at wire_threshold on the wire; opt-in lossy envelope, exact paths keep fp32)
    return total.astype(y.dtype) * thr


def _logits_psum(y):
    """Weight-tied unembedding reduction: each member contracts its local
    hidden columns against its embedding shard; the psum completes the
    ``[.., V]`` logits.  Always exact fp32 — even under ``wire="2bit"``
    the argmax surface is never quantized."""
    from ...parallel import allreduce
    return allreduce(y, "tp")  # mxshard: allclose-ok(row-parallel tied-unembed reduction: member order differs from the serial sum; kept exact fp32 even under wire=2bit so the argmax surface is never quantized)


def _local_cols(geom, x):
    """This member's ``hidden/tp`` column slice of a replicated
    full-width activation (the row-parallel contraction input)."""
    import jax
    i = jax.lax.axis_index("tp")
    return jax.lax.dynamic_slice_in_dim(
        x, i * geom.hidden_local, geom.hidden_local, axis=x.ndim - 1)


def _qkv_local(geom, p, l, x, lead):
    """Column-parallel qkv: the replicated ``x`` against LOCAL column
    shards.  The contract reshape ``(rows, heads, dim)`` is head-major in
    columns, so member i's contiguous column block is exactly heads
    ``[i*local : (i+1)*local]`` — aligned with the pool's head shard, no
    collective between projection and cache write."""
    shape = tuple(lead) + (geom.local_heads, geom.head_dim)
    q = (x @ p["l%d_wq" % l]).reshape(shape)
    k = (x @ p["l%d_wk" % l]).reshape(shape)
    v = (x @ p["l%d_wv" % l]).reshape(shape)
    return q, k, v


def _mlp_block(geom, p, l, h):
    """Megatron MLP half-block: column-parallel up (w1), row-parallel
    down (w2), one psum."""
    import jax
    g = jax.nn.gelu(_rms(h) @ p["l%d_w1" % l])
    return h + _block_psum(geom, g @ p["l%d_w2" % l])


def _decode_step(geom, p, small, k_pool, v_pool):
    """Compute-parallel twin of TinyCausalLM.decode_fn: one fixed-shape
    token step per slot, head-local paged attention, 2 psums per layer."""
    import jax.numpy as jnp
    tokens, positions, tables = small
    bs = k_pool.shape[2]
    S = tokens.shape[0]
    W = tables.shape[1]
    T = W * bs
    srow = jnp.arange(S)
    h = _assemble_replicated(
        geom, p["embed"][tokens] + p["pos"][positions])        # [S, H]
    blk = tables[srow, positions // bs]
    off = positions % bs
    mask = jnp.arange(T)[None, :] <= positions[:, None]        # [S, T]
    for l in range(geom.num_layers):
        q, k, v = _qkv_local(geom, p, l, _rms(h), (S,))
        k_pool = k_pool.at[l, blk, off].set(k)
        v_pool = v_pool.at[l, blk, off].set(v)
        kseq = k_pool[l][tables].reshape(S, T, geom.local_heads,
                                         geom.head_dim)
        vseq = v_pool[l][tables].reshape(S, T, geom.local_heads,
                                         geom.head_dim)
        scores = jnp.einsum("shd,sthd->sht", q, kseq) \
            / jnp.sqrt(float(geom.head_dim)).astype(q.dtype)
        scores = jnp.where(mask[:, None, :], scores, -jnp.inf)
        w = _softmax(scores)
        att = jnp.einsum("sht,sthd->shd", w, vseq).reshape(
            S, geom.hidden_local)
        h = h + _block_psum(geom, att @ p["l%d_wo" % l])
        h = _mlp_block(geom, p, l, h)
    logits = _logits_psum(_local_cols(geom, _rms(h)) @ p["embed"].T)
    return logits, k_pool, v_pool


def _prefill(geom, p, small, k_pool, v_pool):
    """Compute-parallel twin of TinyCausalLM.prefill_fn: the whole padded
    prompt in one causal pass, local heads only."""
    import jax.numpy as jnp
    tokens, length, table = small
    bs = k_pool.shape[2]
    L = tokens.shape[1]
    t = tokens[0]
    h = _assemble_replicated(geom, p["embed"][t] + p["pos"][:L])
    idx = jnp.arange(L)
    blk = table[0, idx // bs]
    off = idx % bs
    causal = idx[None, :] <= idx[:, None]                      # [L, L]
    for l in range(geom.num_layers):
        q, k, v = _qkv_local(geom, p, l, _rms(h), (L,))
        k_pool = k_pool.at[l, blk, off].set(k)
        v_pool = v_pool.at[l, blk, off].set(v)
        scores = jnp.einsum("ihd,jhd->hij", q, k) \
            / jnp.sqrt(float(geom.head_dim)).astype(q.dtype)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        w = _softmax(scores)
        att = jnp.einsum("hij,jhd->ihd", w, v).reshape(
            L, geom.hidden_local)
        h = h + _block_psum(geom, att @ p["l%d_wo" % l])
        h = _mlp_block(geom, p, l, h)
    last = _local_cols(geom, _rms(h[length[0] - 1]))
    logits = _logits_psum(last @ p["embed"].T)
    return logits[None], k_pool, v_pool


def _chunk_prefill(geom, p, small, k_pool, v_pool):
    """Compute-parallel twin of TinyCausalLM.chunk_prefill_fn: one prompt
    chunk at absolute positions, earlier chunks read from the local pool
    shard through the page table."""
    import jax.numpy as jnp
    tokens, start, length, table = small
    bs = k_pool.shape[2]
    C = tokens.shape[1]
    W = table.shape[1]
    T = W * bs
    t = tokens[0]
    pos = start[0] + jnp.arange(C)
    h = _assemble_replicated(
        geom, p["embed"][t]
        + p["pos"][jnp.clip(pos, 0, geom.max_len - 1)])
    blk = table[0, pos // bs]
    off = pos % bs
    valid = jnp.arange(C) < length[0]
    blk = jnp.where(valid, blk, 0)                     # pad -> trash
    epos = jnp.where(valid, pos, 0)
    mask = jnp.arange(T)[None, :] <= epos[:, None]     # [C, T]
    for l in range(geom.num_layers):
        q, k, v = _qkv_local(geom, p, l, _rms(h), (C,))
        k_pool = k_pool.at[l, blk, off].set(k)
        v_pool = v_pool.at[l, blk, off].set(v)
        kseq = k_pool[l][table[0]].reshape(T, geom.local_heads,
                                           geom.head_dim)
        vseq = v_pool[l][table[0]].reshape(T, geom.local_heads,
                                           geom.head_dim)
        scores = jnp.einsum("ihd,jhd->hij", q, kseq) \
            / jnp.sqrt(float(geom.head_dim)).astype(q.dtype)
        scores = jnp.where(mask[None], scores, -jnp.inf)
        w = _softmax(scores)
        att = jnp.einsum("hij,jhd->ihd", w, vseq).reshape(
            C, geom.hidden_local)
        h = h + _block_psum(geom, att @ p["l%d_wo" % l])
        h = _mlp_block(geom, p, l, h)
    last = _local_cols(geom, _rms(h[length[0] - 1]))
    logits = _logits_psum(last @ p["embed"].T)
    return logits[None], k_pool, v_pool


def _verify(geom, p, small, k_pool, v_pool):
    """Compute-parallel twin of TinyCausalLM.verify_fn: K+1 tokens per
    slot in one fixed-shape call, invalid rows to the trash block."""
    import jax.numpy as jnp
    tokens, positions, valids, tables = small
    bs = k_pool.shape[2]
    S, K1 = tokens.shape
    W = tables.shape[1]
    T = W * bs
    pos = positions[:, None] + jnp.arange(K1)[None, :]   # [S, K1]
    valid = jnp.arange(K1)[None, :] < valids[:, None]
    h = _assemble_replicated(
        geom, p["embed"][tokens]
        + p["pos"][jnp.clip(pos, 0, geom.max_len - 1)])  # [S, K1, H]
    blk = jnp.take_along_axis(tables, pos // bs, axis=1)
    blk = jnp.where(valid, blk, 0)                       # -> trash
    off = pos % bs
    epos = jnp.where(valid, pos, 0)
    mask = jnp.arange(T)[None, None, :] <= epos[:, :, None]
    for l in range(geom.num_layers):
        q, k, v = _qkv_local(geom, p, l, _rms(h), (S, K1))
        k_pool = k_pool.at[l, blk, off].set(k)
        v_pool = v_pool.at[l, blk, off].set(v)
        kseq = k_pool[l][tables].reshape(S, T, geom.local_heads,
                                         geom.head_dim)
        vseq = v_pool[l][tables].reshape(S, T, geom.local_heads,
                                         geom.head_dim)
        scores = jnp.einsum("sihd,sjhd->shij", q, kseq) \
            / jnp.sqrt(float(geom.head_dim)).astype(q.dtype)
        scores = jnp.where(mask[:, None, :, :], scores, -jnp.inf)
        w = _softmax(scores)
        att = jnp.einsum("shij,sjhd->sihd", w, vseq).reshape(
            S, K1, geom.hidden_local)
        h = h + _block_psum(geom, att @ p["l%d_wo" % l])
        h = _mlp_block(geom, p, l, h)
    logits = _logits_psum(_local_cols(geom, _rms(h)) @ p["embed"].T)
    return logits, k_pool, v_pool


def _propose_steps(geom, p, small, k_pool, v_pool, num_tokens):
    """Compute-parallel twin of TinyCausalLM.propose_fn: ``num_tokens``
    unrolled decode steps with the argmax on-device (logits are psum'd
    replicated, so the argmax is too)."""
    import jax.numpy as jnp
    tokens, positions, tables = small
    cur = tokens
    pos = positions
    outs = []
    for _ in range(int(num_tokens)):
        logits, k_pool, v_pool = _decode_step(
            geom, p, (cur, pos, tables), k_pool, v_pool)
        cur = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        outs.append(cur)
        pos = pos + 1
    return jnp.stack(outs, axis=1), k_pool, v_pool


def _sharded_kernel(geom, which, p, small, k_pool, v_pool):
    """Single inside-shard_map entry point for every contract fn.

    Called by literal name from the one region ``body`` so the whole
    kernel family — and each of the four static psum sites — lands in the
    mxshard/mxmem budget closure of
    ``ShardedDecodeModel._build_fn.body``."""
    p = _contract_local(geom, p)
    kind = which[0]
    if kind == "decode":
        return _decode_step(geom, p, small, k_pool, v_pool)
    if kind == "prefill":
        return _prefill(geom, p, small, k_pool, v_pool)
    if kind == "chunk_prefill":
        return _chunk_prefill(geom, p, small, k_pool, v_pool)
    if kind == "verify":
        return _verify(geom, p, small, k_pool, v_pool)
    if kind == "propose":
        return _propose_steps(geom, p, small, k_pool, v_pool, which[1])
    raise ValueError("unknown sharded kernel %r" % (which,))


# ---------------------------------------------------------------------------
# the sharded contract wrapper
# ---------------------------------------------------------------------------

class ShardedDecodeModel:
    """Run a decode-model contract compute-parallel over a ('tp','sp') mesh.

    Satisfies the full contract of the wrapped model (same attrs, same
    fn signatures, ``chunk_prefill_fn``/``verify_fn``/``propose_fn``
    present iff the inner model has them), so DecodeEngine, the prefix
    cache, speculative decode, export/import handoff and the sequential
    reference all compose unchanged — now shard-resident end to end.
    Three extra hooks the engine picks up when present:

    * ``zeros_pool(shape)`` — fresh head-sharded K/V pool storage;
    * ``place_inputs(x)`` — pins per-step host inputs replicated on the
      mesh (a jit call cannot mix single-device-committed and
      mesh-committed operands);
    * ``tp_degree`` / ``sp_degree`` — the fleet's device-footprint
      accounting (`FleetRouter.load_decode(..., tp=k)`).

    The wrapper requires the canonical decode parameter schema
    (``embed``/``pos`` plus per-layer ``wq wk wv wo w1 w2``) in either
    the contract layout (``[in, units]``, TinyCausalLM) or the Gluon
    layout (``[units, in]``, ``param_layout = "gluon"`` — the adapter);
    the kernels erase the difference by transposing local shards.

    Exported pages (`export_stream`) host-gather to the full head axis,
    so sharded→sharded and sharded→unsharded handoffs are geometry-free
    round trips; greedy/sampled token streams are identical across
    geometries (logits allclose under the documented psum relaxation).
    """

    def __init__(self, model, tp=2, sp=1, devices=None, wire=None,
                 wire_threshold=0.05):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ...ndarray import NDArray
        self._inner = model
        self.tp = int(tp)
        self.sp = int(sp)
        self.tp_degree = self.tp
        self.sp_degree = self.sp
        # contract geometry proxies (export/import geometry dicts and the
        # PagedKVCache pool grid come from these)
        self.vocab_size = model.vocab_size
        self.num_layers = model.num_layers
        self.num_heads = model.num_heads
        self.head_dim = model.head_dim
        self.max_len = model.max_len
        self.eos_id = getattr(model, "eos_id", None)
        self._local_heads = check_tp_divisible(
            type(model).__name__, model.num_heads, self.tp)
        if wire not in (None, "2bit"):
            raise ValueError(
                "ShardedDecodeModel: unknown wire %r (supported: None "
                "for exact fp32 psums, '2bit' for the quantized codec)"
                % (wire,))
        self.wire = wire
        self.wire_threshold = float(wire_threshold)
        if self.wire == "2bit" and not self.wire_threshold > 0:
            raise ValueError(
                "ShardedDecodeModel: wire='2bit' needs wire_threshold "
                "> 0, got %r" % (wire_threshold,))
        if getattr(model, "context_attention", None) is not None:
            raise ValueError(
                "ShardedDecodeModel: inner model sets "
                "context_attention=%r, but the compute-parallel kernels "
                "run head-local attention and do not route the fused "
                "long-context path; serve this model unsharded or clear "
                "context_attention" % (model.context_attention,))
        self.mesh = decode_mesh(self.tp, self.sp, devices)
        if int(self.mesh.shape["tp"]) != self.tp:
            raise ValueError(
                "ShardedDecodeModel: mesh 'tp' extent %d does not match "
                "the requested tp degree %d"
                % (int(self.mesh.shape["tp"]), self.tp))
        # no trailing None: shard_map normalizes its out_specs that way,
        # and jit's executable cache keys on sharding EQUALITY — a fresh
        # zeros_pool must carry the byte-same sharding as a pool carried
        # out of a step, or the first post-warmup step stealth-recompiles
        self._pool_sharding = NamedSharding(
            self.mesh, P(None, None, None, "tp"))
        self._replicated = NamedSharding(self.mesh, P())

        # resolve one PartitionSpec per parameter and place the weights
        raw = {}
        if hasattr(model, "partition_specs"):
            raw = dict(model.partition_specs())
        inner_params = model.param_dict()
        self._pspecs = {}
        self._params = {}
        for name in sorted(inner_params):
            spec = self._check_spec(name, raw.get(name),
                                    inner_params[name].shape)
            self._pspecs[name] = spec
            self._params[name] = NDArray(jax.device_put(
                inner_params[name]._data, NamedSharding(self.mesh, spec)))

        gluon = getattr(model, "param_layout", "contract") == "gluon"
        self._validate_canonical(inner_params, gluon)
        self._geom = _Geometry(
            num_layers=self.num_layers, num_heads=self.num_heads,
            local_heads=self._local_heads, head_dim=self.head_dim,
            hidden=self.num_heads * self.head_dim,
            hidden_local=(self.num_heads * self.head_dim) // self.tp,
            vocab_size=self.vocab_size, max_len=self.max_len,
            tp=self.tp, gluon=gluon, wire=self.wire,
            wire_threshold=self.wire_threshold)

        self._prefill_sm = self._build_fn(("prefill",), 3)
        self._decode_sm = self._build_fn(("decode",), 3)
        if hasattr(model, "chunk_prefill_fn"):
            self._chunk_sm = self._build_fn(("chunk_prefill",), 4)
            self.chunk_prefill_fn = self._make_call(self._chunk_sm, 4)
        if hasattr(model, "verify_fn"):
            self._verify_sm = self._build_fn(("verify",), 4)
            self.verify_fn = self._make_call(self._verify_sm, 4)
        if hasattr(model, "propose_fn"):
            self._propose_sms = {}
            self.propose_fn = self._propose_call

    # -- contract surface ------------------------------------------------
    def param_dict(self):
        """Live mesh-sharded parameter handles (same-name contract)."""
        return dict(self._params)

    def prefill_fn(self, p, tokens, length, table, k_pool, v_pool):
        return self._prefill_sm(p, (tokens, length, table), k_pool, v_pool)

    def decode_fn(self, p, tokens, positions, tables, k_pool, v_pool):
        return self._decode_sm(p, (tokens, positions, tables), k_pool,
                               v_pool)

    def _propose_call(self, p, tokens, positions, tables, k_pool, v_pool,
                      num_tokens):
        sm = self._propose_sms.get(int(num_tokens))
        if sm is None:
            sm = self._build_fn(("propose", int(num_tokens)), 3)
            self._propose_sms[int(num_tokens)] = sm
        return sm(p, (tokens, positions, tables), k_pool, v_pool)

    # -- engine hooks ----------------------------------------------------
    def zeros_pool(self, shape):
        """Fresh zeroed head-sharded pool storage for ``shape`` (the
        contract layout; the head axis must divide tp)."""
        import jax
        import jax.numpy as jnp
        from ...ndarray import NDArray
        check_pool_matches_mesh(type(self._inner).__name__, shape,
                                self.mesh)
        return NDArray(jax.device_put(jnp.zeros(shape, jnp.float32),
                                      self._pool_sharding))

    def place_inputs(self, x):
        """Pin a per-step operand on the serving mesh (replicated) unless
        it already lives there; mesh-resident pools/params pass through
        untouched so their shardings stay byte-stable across steps."""
        import jax
        from jax.sharding import NamedSharding
        sh = getattr(x, "sharding", None)
        if isinstance(sh, NamedSharding) and sh.mesh == self.mesh:
            return x
        return jax.device_put(x, self._replicated)

    # -- internals -------------------------------------------------------
    def _check_spec(self, name, spec, shape):
        """Validate a parameter PartitionSpec eagerly: only the 'tp' axis,
        one axis name per dim, and the dim must divide the extent."""
        from jax.sharding import PartitionSpec as P
        if spec is None:
            return P()
        entries = tuple(spec)
        if len(entries) > len(shape):
            raise ValueError(
                "%s: partition spec %r has %d entries for a rank-%d "
                "parameter" % (name, spec, len(entries), len(shape)))
        for dim, ax in enumerate(entries):
            if ax is None:
                continue
            if ax != "tp":
                raise ValueError(
                    "%s: partition spec %r names axis %r; decode weight "
                    "sharding supports only the 'tp' mesh axis"
                    % (name, spec, ax))
            check_tp_divisible(name, shape[dim], self.tp,
                               what="dim %d extent" % dim)
        return P(*entries)

    def _validate_canonical(self, inner_params, gluon):
        """The compute-parallel kernels are written against the canonical
        decode schema; verify roles, shapes and the Megatron spec pattern
        eagerly so mismatches raise here, never inside shard_map."""
        name = type(self._inner).__name__
        hid = self.num_heads * self.head_dim
        want = {"embed", "pos"}
        for l in range(self.num_layers):
            want |= {"l%d_%s" % (l, r) for r in _DENSE_ROLES}
        have = set(inner_params)
        if have != want:
            raise ValueError(
                "%s: parameter roles do not match the canonical decode "
                "schema the compute-parallel kernels require (missing %s, "
                "unexpected %s)"
                % (name, sorted(want - have) or "none",
                   sorted(have - want) or "none"))
        # shapes per layout; the sharded dim per role per layout
        col = ("wq", "wk", "wv", "w1")
        shapes = {"embed": (self.vocab_size, hid),
                  "pos": (self.max_len, hid)}
        specs = {"embed": (None, "tp"), "pos": (None, "tp")}
        for l in range(self.num_layers):
            for r in ("wq", "wk", "wv", "wo"):
                shapes["l%d_%s" % (l, r)] = (hid, hid)
            if gluon:
                shapes["l%d_w1" % l] = (2 * hid, hid)
                shapes["l%d_w2" % l] = (hid, 2 * hid)
            else:
                shapes["l%d_w1" % l] = (hid, 2 * hid)
                shapes["l%d_w2" % l] = (2 * hid, hid)
            for r in _DENSE_ROLES:
                col_role = (r in col) != bool(gluon)
                specs["l%d_%s" % (l, r)] = ((None, "tp") if col_role
                                            else ("tp",))
        for pname in sorted(want):
            got_shape = tuple(inner_params[pname].shape)
            if got_shape != shapes[pname]:
                raise ValueError(
                    "%s: parameter %r has shape %r; the %s layout of the "
                    "canonical decode schema requires %r"
                    % (name, pname, got_shape,
                       "gluon" if gluon else "contract", shapes[pname]))
            got = tuple(self._pspecs[pname])
            while got and got[-1] is None:
                got = got[:-1]
            if got != specs[pname]:
                raise ValueError(
                    "%s: parameter %r has partition spec %r; the "
                    "compute-parallel Megatron kernels require %r for the "
                    "%s layout"
                    % (name, pname, tuple(self._pspecs[pname]),
                       specs[pname], "gluon" if gluon else "contract"))

    def _build_fn(self, which, n_small):
        """shard_map one compute-parallel kernel: weights and K/V stay on
        their shards, each Megatron half-block ends in its single psum,
        and the kernels write the LOCAL head slice of the pool carries
        directly — no gather, no slice-back."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        geom = self._geom
        pool_spec = P(None, None, None, "tp")
        pspecs = dict(self._pspecs)

        # The decode step's collective bill: one exact scatter-assembly
        # psum, two Megatron block psums per layer, one tied-unembed psum
        # — 2*num_layers + 2 psum calls, ZERO gathers.  Four static psum
        # sites back those calls (assembly / block / 2bit-wire / unembed).
        # The declared worst case under the accountant's reuse-free model
        # is the psum outputs live at once — predict_decode_step_peak_bytes()
        # is the exact symbolic form, pinned == the runtime peak by
        # tests/test_mxmem.py and the sharded-decode smoke.
        # mxmem: budget(hbm=64MB)
        # mxshard: budget(psum=4)
        def body(p_local, small, k_local, v_local):
            return _sharded_kernel(geom, which, p_local, small, k_local,
                                   v_local)

        return shard_map(
            body, mesh=self.mesh,
            in_specs=(pspecs, tuple(P() for _ in range(n_small)),
                      pool_spec, pool_spec),
            out_specs=(P(), pool_spec, pool_spec),
            check_vma=False)

    @staticmethod
    def _make_call(sm, n_small):
        def call(p, *args):
            return sm(p, tuple(args[:n_small]), args[n_small],
                      args[n_small + 1])
        return call
