"""Pallas TPU kernels for the hot ops XLA fusion can't produce by itself.

Reference counterpart: the CUDA kernels under src/operator/ (and the
transformer attention helpers in src/operator/contrib/transformer.cc).  Here
the accelerator kernels are Pallas: tiled flash attention with the streaming
log-sum-exp softmax, keeping the working set in VMEM and the QK^T / PV matmuls
on the MXU, forward and backward.

One pair of kernels (the forward; one backward for the query, key and value
gradients) serves every mask.  A static mask (none, causal 'top' / 'bottom'
aligned, a causal window of the last ``W`` keys, the block diffusion mask over
``[noised; clean]`` rows) is a
function of a row's and a column's index; from it the wrapper works out on
the host, per query tile, which key tiles hold a visible pair (the others
are never visited: no DMA, no MXU pass, no grid step) and which are wholly
visible (no masking); the kernels' grid walks the one flat list of them.  A
data mask (``sparse_attention``) is an operand the step computes: the
visible pairs as int8, streamed by tile beside the keys, over the causal
mask's list.  Query heads may outnumber key/value heads
(grouped-query attention): the kernels index the shared key/value head, and
the key/value gradient sums over the group inside the kernel.

On a TPU backend the entry points run the kernels, and a kernel the compiler
refuses is an error the caller sees.  Elsewhere they run the dense XLA
reference, forward and backward: the Pallas TPU lowering exists only for
TPUs.  The reference is also the kernels' oracle in the tests.
"""
from __future__ import annotations

import contextlib
import functools

import numpy as _np

from .. import profiler
from .registry import register

_NEG = -1e30
# what a decoder layer's calls name for jax.checkpoint policies: the
# attention's forward kernel's output and log-sum-exp, the two residuals only
# it can produce, and the held experts' slot table (parallel/moe.py: three
# sorts' results, 1.3 MB a layer, where each sort recomputed is 1 to 1.5 MB
# of program)
SLOT_TABLE = "moe.table"
ATTENTION_RESIDUALS = ("attn.out", "attn.lse", SLOT_TABLE)


# ---------------------------------------------------------------------------
# static masks
# ---------------------------------------------------------------------------
# A mask is a hashable tuple: ("none",), ("causal", offset), ("window", W)
# (query i sees keys i - W < j <= i: itself and the W - 1 before it),
# ("block_diffusion", L, block_length) or DATA_MASK, whose visible pairs are
# an operand of the call.
DATA_MASK = ("data",)

def _causal_offset(causal, Tq, Tk):
    """Key-position offset of the causal diagonal: query i attends keys
    j <= i + offset.  'top' aligns query 0 with key 0 (offset 0); 'bottom'
    is the KV-cache decode convention (the last query sees every key,
    offset Tk - Tq).  The two coincide when Tq == Tk."""
    return Tk - Tq if causal == "bottom" else 0


def _causal_mask(causal, Tq, Tk):
    return ("causal", _causal_offset(causal, Tq, Tk)) if causal else ("none",)


def block_diffusion_mask(seq_len, block_length):
    """The mask of block-diffusion training over ``2 * seq_len`` rows, the
    noised copy of a sequence followed by the clean one.  With
    ``b(i) = i // block_length`` inside each copy: a noised query sees the
    noised keys of its own block (both ways) and the clean keys of earlier
    blocks; a clean query sees the clean keys of its own and earlier blocks
    and no noised key."""
    if seq_len % block_length:
        raise ValueError("block_length %d does not divide the sequence's %d "
                         "positions" % (block_length, seq_len))
    return ("block_diffusion", int(seq_len), int(block_length))


def mask_visible(mask, q_pos, k_pos):
    """Whether query row ``q_pos`` sees key row ``k_pos``: integer arrays
    (numpy or jax, broadcast against each other) in, booleans out."""
    kind = mask[0]
    if kind == "none":
        return (q_pos >= 0) & (k_pos >= 0)
    if kind == "causal":
        return q_pos + mask[1] >= k_pos
    if kind == "window":
        return (k_pos <= q_pos) & (k_pos > q_pos - mask[1])
    _, L, bl = mask
    q_clean, k_clean = q_pos >= L, k_pos >= L
    qb = (q_pos - L * q_clean) // bl
    kb = (k_pos - L * k_clean) // bl
    return ((~q_clean) & (~k_clean) & (qb == kb)) \
        | ((~q_clean) & k_clean & (kb < qb)) \
        | (q_clean & k_clean & (kb <= qb))


# An entry of the tile list carries its tile's state (0: nothing visible,
# the one entry of a query tile that sees no key tile; 1: partly visible,
# masked from its indices or from the pairs; 2: wholly visible) and two
# marks: the entry is its query tile's first, its last.
_STATE, _FIRST, _LAST = 3, 4, 8


@functools.lru_cache(maxsize=64)
def _tile_tables(mask, Tq, Tk, n_q, n_k, block_q, block_k):
    """Which tiles the mask leaves something in, found on the host.

    Returns ``(q_tile, k_tile, flag)``: one flat list of the tiles to visit,
    query tile by query tile and within one by ascending key tile, an entry
    a grid step.  ``flag`` holds the tile's state (``flag & _STATE``: 1
    partly visible, masked from its indices; 2 wholly visible) and the
    marks ``_FIRST`` and ``_LAST`` on a query tile's first and last entry.
    A query tile that sees no key tile keeps one entry of state 0, so that
    its rows are still written.  Rows and columns past ``Tq`` / ``Tk`` are
    padding: a padded column is never visible, a padded row is no reason
    to visit a tile."""
    state = _np.zeros((n_q, n_k), _np.int32)
    k_pos = _np.arange(n_k * block_k)[None, :]
    for qi in range(n_q):
        q_pos = _np.arange(qi * block_q, (qi + 1) * block_q)[:, None]
        vis = mask_visible(mask, q_pos, k_pos) & (k_pos < Tk)
        real = vis & (q_pos < Tq)
        vis = vis.reshape(block_q, n_k, block_k)
        real = real.reshape(block_q, n_k, block_k)
        state[qi] = _np.where(vis.all(axis=(0, 2)), 2,
                              real.any(axis=(0, 2)).astype(_np.int32))

    q_tile, k_tile, flag = [], [], []
    for row in range(n_q):
        found = _np.nonzero(state[row])[0]
        if not len(found):
            found = _np.zeros(1, _np.intp)
        marks = state[row, found]
        marks[0] |= _FIRST
        marks[-1] |= _LAST
        q_tile.append(_np.full(len(found), row, _np.int32))
        k_tile.append(found.astype(_np.int32))
        flag.append(marks)
    return tuple(_np.concatenate(part) for part in (q_tile, k_tile, flag))


def _attention_reference(q, k, v, causal, scale, mask=None, pairs=None):
    """Dense XLA attention: every score materialised.  ``causal`` is the
    flash_attention argument; ``mask`` (a mask tuple) overrides it, and
    under ``DATA_MASK`` the visible pairs are ``pairs`` (B, Tq, Tk), nonzero
    or true where the query sees the key; the log-sum-exp (B, Hq, Tq) is
    then returned beside the output.  Query heads may be a multiple of the
    key/value heads."""
    import jax
    import jax.numpy as jnp
    B, Hq, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    if mask is None:
        mask = _causal_mask(causal, Tq, Tk)
    qg = q.reshape(B, Hkv, Hq // Hkv, Tq, D)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k).astype(jnp.float32) * scale
    if mask == DATA_MASK:
        keep = (pairs != 0)[:, None, None]
        s = jnp.where(keep, s, _NEG)
        lse = jax.nn.logsumexp(s, axis=-1)
        p = jnp.where(keep, jnp.exp(s - lse[..., None]), 0.0)
        out = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(v.dtype), v)
        return out.reshape(B, Hq, Tq, D), lse.reshape(B, Hq, Tq)
    if mask[0] != "none":
        keep = mask_visible(mask, jnp.arange(Tq)[:, None],
                            jnp.arange(Tk)[None, :])
        s = jnp.where(keep[None, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(v.dtype), v)
    return out.reshape(B, Hq, Tq, D)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

class _Plan:
    """Everything static about one attention call: sizes, tiles, tables."""

    def __init__(self, q_shape, k_shape, mask, scale, block_q, block_k,
                 mxu_dtype, interpret):
        B, Hq, Tq, D = q_shape
        Hkv, Tk = k_shape[1], k_shape[2]
        if Hq % Hkv:
            raise ValueError("%d query heads are no multiple of %d "
                             "key/value heads" % (Hq, Hkv))
        self.B, self.Hq, self.Hkv, self.G, self.D = B, Hq, Hkv, Hq // Hkv, D
        self.Tq, self.Tk = Tq, Tk
        self.block_q, self.block_k = min(block_q, Tq), min(block_k, Tk)
        self.pad_q, self.pad_k = -Tq % self.block_q, -Tk % self.block_k
        self.n_q = (Tq + self.pad_q) // self.block_q
        self.n_k = (Tk + self.pad_k) // self.block_k
        self.mask, self.scale = mask, float(scale)
        self.mxu_dtype, self.interpret = mxu_dtype, interpret
        # a data mask's pairs come with the call and lie on or under the
        # diagonal: its tables are the causal mask's, every visited tile
        # masked from the operand
        self.data = mask == DATA_MASK
        self.q_tile, self.k_tile, self.flag = _tile_tables(
            ("causal", 0) if self.data else mask, Tq, Tk, self.n_q, self.n_k,
            self.block_q, self.block_k)
        if self.data:
            self.flag = self.flag - ((self.flag & _STATE) == 2)
        self.steps = len(self.flag)

    def tables(self):
        """(q_tile, k_tile, flag) as the kernels' scalar-prefetch operands."""
        import jax.numpy as jnp
        return (jnp.asarray(self.q_tile), jnp.asarray(self.k_tile),
                jnp.asarray(self.flag))

    def keep(self, q_tile, k_tile, transposed=False):
        """The visible pairs of one tile, from its indices, as the kernels
        hold it: (block_q, block_k), or transposed."""
        import jax
        import jax.numpy as jnp
        shape = (self.block_k, self.block_q) if transposed \
            else (self.block_q, self.block_k)
        q_pos = q_tile * self.block_q + jax.lax.broadcasted_iota(
            jnp.int32, shape, 1 if transposed else 0)
        k_pos = k_tile * self.block_k + jax.lax.broadcasted_iota(
            jnp.int32, shape, 0 if transposed else 1)
        keep = mask_visible(self.mask, q_pos, k_pos)
        if self.pad_k:
            keep &= k_pos < self.Tk     # padded keys contribute nothing
        return keep

    def count_tiles(self):
        """One kernel's grid in the recorder: tiles of the whole square,
        tiles visited and grid steps, over all batch rows and query heads."""
        heads = self.B * self.Hq
        profiler.count("attn.tiles_total", heads * self.n_q * self.n_k)
        profiler.count("attn.tiles_visited",
                       heads * int(((self.flag & _STATE) > 0).sum()))
        profiler.count("attn.grid_steps", heads * self.steps)


def _nt(a, b):
    """a @ b.T on the MXU, float32 out."""
    import jax
    import jax.numpy as jnp
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _pad_rows(x, pad):
    """``x`` (B, H, T, D) with ``pad`` zero rows after the T it has."""
    import jax.numpy as jnp
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else x


def _trim(plan, out):
    """The forward kernel's (B * H, padded T, D) as (B, H, T, D)."""
    return out.reshape(plan.B, plan.Hq, -1, plan.D)[:, :, :plan.Tq]


def _padded_pairs(plan, pairs):
    """A data mask's pairs (B, Tq, Tk) as int8 over whole tiles, the padding
    invisible."""
    import jax.numpy as jnp
    return jnp.pad(pairs.astype(jnp.int8),
                   ((0, 0), (0, plan.pad_q), (0, plan.pad_k)))


def _attention_fwd_pallas(plan, q, k, v, pairs=None):
    """(out, lse): grid over (batch * query heads, the list of visited
    tiles); a step takes its query tile and its key tile from the list.
    K/V stream through VMEM one ``(block_k, D)`` tile per step while the
    online-softmax state (running max, normaliser, accumulator) lives in
    VMEM scratch across the consecutive steps of one query tile (zeroed at
    the entry marked first, written out at the one marked last), so VMEM
    use is bounded by the tile sizes, never by the sequence length.  Ragged
    lengths are padded up to the tile size; padded key columns are masked
    and padded query rows are sliced off.  Under a data mask ``pairs`` are
    the padded int8 pairs, whose (block_q, block_k) tile masks a visited
    tile."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    p = plan
    bq, bk, D, G = p.block_q, p.block_k, p.D, p.G
    cdt = p.mxu_dtype

    def kernel(qidx_ref, kidx_ref, flag_ref, q_ref, k_ref, v_ref, *refs):
        pairs_ref = refs[0] if p.data else None
        o_ref, lse_ref, m_ref, l_ref, acc_ref = refs[-5:]
        at = pl.program_id(1)
        flag = flag_ref[at]
        state = flag & _STATE

        @pl.when((flag & _FIRST) != 0)
        def _():
            m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
            l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        def accumulate(masked):
            s = _nt(q_ref[...].astype(cdt), k_ref[...].astype(cdt)) * p.scale
            if masked:
                keep = pairs_ref[...].astype(jnp.int32) != 0 if p.data \
                    else p.keep(qidx_ref[at], kidx_ref[at])
                s = jnp.where(keep, s, _NEG)
            m_prev = m_ref[...]                                   # (bq, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            e = jnp.exp(s - m_new)
            if masked:      # a row with nothing visible in this tile
                e = jnp.where(keep, e, 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = alpha * l_ref[...] + jnp.sum(e, axis=1,
                                                      keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
                e.astype(cdt), v_ref[...].astype(cdt),
                preferred_element_type=jnp.float32)
            m_ref[...] = m_new

        pl.when(state == 1)(lambda: accumulate(True))
        pl.when(state == 2)(lambda: accumulate(False))

        @pl.when((flag & _LAST) != 0)
        def _():
            l = jnp.maximum(l_ref[...], 1e-30)
            o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
            lse_ref[...] = m_ref[...] + jnp.log(l)

    BH = p.B * p.Hq
    qf = _pad_rows(q, p.pad_q).reshape(BH, p.n_q * bq, D)
    kf = _pad_rows(k, p.pad_k).reshape(p.B * p.Hkv, p.n_k * bk, D)
    vf = _pad_rows(v, p.pad_k).reshape(p.B * p.Hkv, p.n_k * bk, D)
    q_spec = pl.BlockSpec(
        (None, bq, D), lambda b, t, qidx, kidx, flag: (b, qidx[t], 0))
    kv_spec = pl.BlockSpec(
        (None, bk, D), lambda b, t, qidx, kidx, flag: (b // G, kidx[t], 0))
    operands, in_specs = [qf, kf, vf], [q_spec, kv_spec, kv_spec]
    if p.data:
        operands.append(pairs)
        in_specs.append(pl.BlockSpec(
            (None, bq, bk), lambda b, t, qidx, kidx, flag:
            (b // p.Hq, qidx[t], kidx[t])))
    p.count_tiles()
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(BH, p.steps),
            in_specs=in_specs,
            out_specs=[q_spec, pl.BlockSpec(
                (None, bq, 1), lambda b, t, qidx, kidx, flag:
                (b, qidx[t], 0))],
            scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, D), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((BH, p.n_q * bq, D), q.dtype),
                   jax.ShapeDtypeStruct((BH, p.n_q * bq, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=p.interpret, name="attention_fwd",
    )(*p.tables(), *operands)
    return out, lse


def _attention_bwd_pallas(plan, q, k, v, out, lse, g, pairs_t=None):
    """(dq, dk, dv) from one kernel, scores recomputed tile by tile from the
    forward's log-sum-exp (``lse`` as the forward call keeps it, (B * H,
    padded T)).  The grid is the forward's: (batch * query heads, the list
    of visited tiles).  Every visited tile is computed once, key
    rows by query columns, so that the per-row statistics enter as
    lane-dense rows and the two key-side products take the tile as it
    stands: scores, exponentials, mask (partly visible tiles only), dP and
    dS, then ``dv += P^T dO``, ``dk += dS^T q`` and ``dq^T += k^T dS``: no
    product turns a tile.

    dq of a query tile adds up over its consecutive steps in scratch,
    transposed, and is turned once a query tile.  dk and dv are revisited
    out of order, so one key/value head's whole (padded Tk, D) float32
    gradients are the output blocks: they stay in VMEM over the head's
    ``G`` consecutive query heads, are zeroed at the list's first entry of
    the group's first head and scaled at the last entry of its last, and
    each tile adds its (block_k, D) rows in place.
    VMEM therefore grows with the key length (4 MiB a gradient at 8,192 x
    128 and 8 MiB at 16,384, twice for the pipeline's second buffer: 48 MiB
    of the cap below at 16,384), whatever the mask; nothing in HBM grows
    with tiles x heads.  Under a data mask ``pairs_t`` are the padded pairs,
    key rows by query columns."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    p = plan
    bq, bk, D, G = p.block_q, p.block_k, p.D, p.G
    cdt = p.mxu_dtype
    BH, BHkv = p.B * p.Hq, p.B * p.Hkv
    Tq_t, Tk_t = p.n_q * bq, p.n_k * bk
    gf = _pad_rows(g, p.pad_q).reshape(BH, Tq_t, D)
    qf = _pad_rows(q, p.pad_q).reshape(BH, Tq_t, D)
    kf = _pad_rows(k, p.pad_k).reshape(BHkv, Tk_t, D)
    vf = _pad_rows(v, p.pad_k).reshape(BHkv, Tk_t, D)
    # k^T for dq: turned here once (the key/value heads are few), where the
    # kernel would turn a (block_k, block_q) tile of dS at every step
    kt = jnp.swapaxes(kf, 1, 2)                               # (BHkv, D, Tk)
    # delta_i = sum_j P_ij dP_ij = <dO_i, O_i>: one fused pass in XLA
    delta = jnp.sum(gf.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, None]                         # (BH, 1, Tq)
    lse = lse[:, None]

    def kernel(qidx_ref, kidx_ref, flag_ref, q_ref, g_ref, lse_ref, delta_ref,
               k_ref, kt_ref, v_ref, *refs):
        pairs_ref = refs[0] if p.data else None
        dq_ref, dk_ref, dv_ref, dqt_acc = refs[-4:]
        b, at = pl.program_id(0), pl.program_id(1)
        flag = flag_ref[at]
        state = flag & _STATE

        @pl.when((b % G == 0) & (at == 0))
        def _():
            dk_ref[...] = jnp.zeros(dk_ref.shape, jnp.float32)
            dv_ref[...] = jnp.zeros(dv_ref.shape, jnp.float32)

        @pl.when((flag & _FIRST) != 0)
        def _():
            dqt_acc[...] = jnp.zeros(dqt_acc.shape, jnp.float32)

        def accumulate(masked):
            kidx = kidx_ref[at]
            rows = pl.ds(pl.multiple_of(kidx * bk, bk), bk)
            q_blk = q_ref[...].astype(cdt)
            g_blk = g_ref[...].astype(cdt)
            st = _nt(k_ref[...].astype(cdt), q_blk) * p.scale    # (bk, bq)
            et = jnp.exp(st - lse_ref[...])
            if masked:
                keep = pairs_ref[...].astype(jnp.int32) != 0 if p.data \
                    else p.keep(qidx_ref[at], kidx, transposed=True)
                et = jnp.where(keep, et, 0.0)
            dv_ref[rows, :] += jnp.dot(et.astype(cdt), g_blk,
                                       preferred_element_type=jnp.float32)
            dpt = _nt(v_ref[...].astype(cdt), g_blk)
            dst = (et * (dpt - delta_ref[...])).astype(cdt)
            dk_ref[rows, :] += jnp.dot(dst, q_blk,
                                       preferred_element_type=jnp.float32)
            dqt_acc[...] += jnp.dot(kt_ref[...].astype(cdt), dst,
                                    preferred_element_type=jnp.float32)

        pl.when(state == 1)(lambda: accumulate(True))
        pl.when(state == 2)(lambda: accumulate(False))

        @pl.when((flag & _LAST) != 0)
        def _():
            dq_ref[...] = (dqt_acc[...].T * p.scale).astype(dq_ref.dtype)

        @pl.when((b % G == G - 1) & (at == p.steps - 1))
        def _():
            dk_ref[...] *= p.scale

    def q_side(b, t, qidx, kidx, flag):
        return (b, qidx[t], 0)

    def row_side(b, t, qidx, kidx, flag):
        return (b, 0, qidx[t])

    def k_side(b, t, qidx, kidx, flag):
        return (b // G, kidx[t], 0)

    def kt_side(b, t, qidx, kidx, flag):
        return (b // G, 0, kidx[t])

    q_spec = pl.BlockSpec((None, bq, D), q_side)
    row_spec = pl.BlockSpec((None, 1, bq), row_side)
    k_spec = pl.BlockSpec((None, bk, D), k_side)
    held_spec = pl.BlockSpec((None, Tk_t, D),
                             lambda b, t, qidx, kidx, flag: (b // G, 0, 0))
    held = jax.ShapeDtypeStruct((BHkv, Tk_t, D), jnp.float32)
    # dk and dv whole, each with the pipeline's second buffer, beside the 16
    # MiB a kernel has by default for its tiles and temporaries; past 100 of
    # a v5e core's 128 MiB the compiler refuses the call, and says so.  A
    # row of 64 takes a whole row of 128 lanes
    lanes = -(-D // 128) * 128
    vmem_bytes = min(16 * 2 ** 20 + 2 * 2 * Tk_t * lanes * 4, 100 * 2 ** 20)
    operands = [qf, gf, lse, delta, kf, kt, vf]
    in_specs = [q_spec, q_spec, row_spec, row_spec, k_spec,
                pl.BlockSpec((None, D, bk), kt_side), k_spec]
    if p.data:
        operands.append(pairs_t)
        in_specs.append(pl.BlockSpec(
            (None, bk, bq), lambda b, t, qidx, kidx, flag:
            (b // p.Hq, kidx[t], qidx[t])))
    p.count_tiles()
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(BH, p.steps),
            in_specs=in_specs,
            out_specs=[q_spec, held_spec, held_spec],
            scratch_shapes=[pltpu.VMEM((D, bq), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((BH, Tq_t, D), q.dtype), held, held],
        # both axes in order: dk and dv are added to across the two
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_bytes),
        interpret=p.interpret, name="attention_bwd",
    )(*p.tables(), *operands)

    dq = dq.reshape(p.B, p.Hq, Tq_t, D)[:, :, :p.Tq]
    dk = dk.reshape(p.B, p.Hkv, Tk_t, D)[:, :, :p.Tk].astype(k.dtype)
    dv = dv.reshape(p.B, p.Hkv, Tk_t, D)[:, :, :p.Tk].astype(v.dtype)
    return dq, dk, dv


def _flash_attention_pallas(q, k, v, causal, scale, block_q=256, block_k=512,
                            interpret=False, mask=None, mxu_dtype=None):
    """The forward kernel alone (tests, chip compiles, benches): attention
    under ``causal`` (the flash_attention argument) or a mask tuple."""
    if mask is None:
        mask = _causal_mask(causal, q.shape[2], k.shape[2])
    plan = _Plan(q.shape, k.shape, mask, scale, block_q, block_k,
                 mxu_dtype or _mxu_dtype(q.dtype, "highest"), interpret)
    return _trim(plan, _attention_fwd_pallas(plan, q, k, v)[0])


def _mxu_dtype(dtype, precision):
    """What the matrix units are fed: bfloat16 operands with float32
    accumulation (XLA's default precision on the TPU for float32 inputs, one
    bfloat16 pass), or the input's own dtype at precision 'highest'."""
    import jax.numpy as jnp
    if precision == "highest" and jnp.dtype(dtype) != jnp.bfloat16:
        return jnp.float32
    return jnp.bfloat16


def _attention(q, k, v, mask, scale, precision, interpret, block_q, block_k,
               scope=None):
    """Differentiable attention under a static mask: the Pallas kernels on
    a TPU (or where ``interpret`` is given), the XLA reference elsewhere.
    ``scope`` names the backward kernel's operations as the caller named the
    forward's."""
    import jax
    from jax.ad_checkpoint import checkpoint_name

    use_pallas = interpret is not None or jax.default_backend() == "tpu"
    if not use_pallas:
        return _attention_reference(q, k, v, None, scale, mask=mask)
    plan = _Plan(q.shape, k.shape, mask, scale, block_q, block_k,
                 _mxu_dtype(q.dtype, precision), bool(interpret))

    @jax.custom_vjp
    def f(q_, k_, v_):
        return f_fwd(q_, k_, v_)[0]

    def f_fwd(q_, k_, v_):
        out, lse = _attention_fwd_pallas(plan, q_, k_, v_)
        # named, so that a recomputed block can keep the two by name
        # (hybridize(remat_policy=ATTENTION_RESIDUALS)) and the kernel does
        # not run again in the backward pass; an identity anywhere else.
        # The log-sum-exp as (B * H, T): held with a last dimension of 1
        # it takes tiles of (8, 128), 128 times its size.
        out = checkpoint_name(out, ATTENTION_RESIDUALS[0])
        lse = checkpoint_name(lse[..., 0], ATTENTION_RESIDUALS[1])
        return _trim(plan, out), (q_, k_, v_, out, lse)

    def f_bwd(res, g):
        q_, k_, v_, out, lse = res
        with jax.named_scope(scope) if scope else contextlib.nullcontext():
            return _attention_bwd_pallas(plan, q_, k_, v_, out, lse, g)

    f.defvjp(f_fwd, f_bwd)
    return f(q, k, v)


def flash_attention(q, k, v, causal=False, scale=None, interpret=None):
    """Fused attention entry: Pallas kernels on TPU, forward and backward;
    the XLA reference elsewhere.  ``interpret`` (tests only) forces the
    kernels, interpreted or compiled.

    q: (B, H, T, D); k/v: (B, Hkv, Tk, D), H a multiple of Hkv.
    Differentiable: a custom_vjp whose backward is one blockwise kernel
    (each visited tile's scores are recomputed once from the saved
    log-sum-exp and give dq, dk and dv; nothing of size T x Tk is ever
    held, one key/value head's dk and dv stay in VMEM while they are added
    to), and skips the tiles above the diagonal as the forward does.

    ``causal`` may be False, True, 'top', or 'bottom'.  With mismatched q/k
    lengths the diagonal's alignment is ambiguous, so bare ``True`` refuses
    and the caller must say which convention they mean: 'top' aligns query 0
    with key 0; 'bottom' is the KV-cache decode convention (the last query
    sees every key) — e.g. ``causal='bottom'`` for T=1, Tk=n decode.

    The matrix units take the inputs' own dtype (float32 operands at
    precision 'highest'); a decoder block that computes at XLA's default
    precision calls ``causal_attention``, the same kernels over the same
    tables with bfloat16 operands."""
    if scale is None:
        scale = 1.0 / _np.sqrt(q.shape[-1])
    # identity checks: 1/1.0 would sneak past an `in` test via 1 == True
    if not (causal is False or causal is True
            or causal in ("top", "bottom")):
        raise ValueError("causal must be False/True/'top'/'bottom', got %r"
                         % (causal,))
    if causal is True and q.shape[2] != k.shape[2]:
        raise ValueError(
            "causal=True is ambiguous for q/k lengths %d vs %d: pass "
            "causal='top' (align query 0 with key 0) or causal='bottom' "
            "(KV-cache decode: last query sees every key)"
            % (q.shape[2], k.shape[2]))
    if causal == "bottom" and q.shape[2] > k.shape[2]:
        # queries before the first key would attend nothing (0/0 rows)
        raise ValueError(
            "causal='bottom' needs q length <= k length, got %d vs %d"
            % (q.shape[2], k.shape[2]))
    return _attention(q, k, v, _causal_mask(causal, q.shape[2], k.shape[2]),
                      scale, "highest", interpret, 256, 512)


def causal_attention(q, k, v, scale=None, precision="default", interpret=None,
                     block_q=512, block_k=512):
    """Causal attention of a decoder block in training: ``q`` (B, H, T, D),
    ``k``/``v`` (B, Hkv, T, D), query ``t`` sees keys ``s <= t``.  The
    kernels of ``flash_attention`` under the mask ``("causal", 0)`` (the
    tiles above the diagonal are never visited, those wholly under it not
    masked), in tiles of 512 x 512 and, at ``precision`` 'default', with
    bfloat16 operands and float32 accumulation as XLA's products have them."""
    import jax
    if q.shape[2] != k.shape[2]:
        raise ValueError("causal attention over %d queries and %d keys: one "
                         "square" % (q.shape[2], k.shape[2]))
    if scale is None:
        scale = 1.0 / _np.sqrt(q.shape[-1])
    with jax.named_scope("attn.causal"):
        return _attention(q, k, v, ("causal", 0), scale, precision, interpret,
                          block_q, block_k, scope="attn.causal")


def window_attention(q, k, v, window, scale=None, precision="default",
                     interpret=None, block_q=512, block_k=512):
    """Sliding-window attention of a decoder block in training: ``q`` (B, H,
    T, D), ``k``/``v`` (B, Hkv, T, D), query ``t`` sees keys ``t - window <
    s <= t`` (itself and the ``window - 1`` before it).  The kernels of
    ``causal_attention`` under the mask ``("window", window)``: only the band
    of tiles that hold a visible pair is visited (at 512 x 512 tiles and a
    window of 1,024, three a query tile), those cut by either edge masked
    from their indices."""
    import jax
    if q.shape[2] != k.shape[2]:
        raise ValueError("window attention over %d queries and %d keys: one "
                         "square" % (q.shape[2], k.shape[2]))
    if int(window) < 1:
        raise ValueError("a window of %r keys" % (window,))
    if scale is None:
        scale = 1.0 / _np.sqrt(q.shape[-1])
    with jax.named_scope("attn.window"):
        return _attention(q, k, v, ("window", int(window)), scale, precision,
                          interpret, block_q, block_k, scope="attn.window")


def block_mask_attention(q, k, v, seq_len, block_length, scale=None,
                         precision="default", interpret=None,
                         block_q=512, block_k=512):
    """Attention of block-diffusion training: ``q`` (B, H, 2L, D) and
    ``k``/``v`` (B, Hkv, 2L, D) hold the noised copy of a sequence of
    ``seq_len`` = L positions followed by the clean one, under
    ``block_diffusion_mask``.  Only about a quarter of the square is
    visible; the forward kernel and the backward kernel each visit the
    tiles that hold a visible pair once and mask the partly visible ones
    from row and column indices.  At
    ``precision`` 'default' the matrix units take bfloat16 operands and
    accumulate in float32, as XLA does with float32 inputs."""
    import jax
    if q.shape[2] != 2 * seq_len or k.shape[2] != 2 * seq_len:
        raise ValueError("block-mask attention over %d and %d rows, not "
                         "twice seq_len %d" % (q.shape[2], k.shape[2],
                                               seq_len))
    if scale is None:
        scale = 1.0 / _np.sqrt(q.shape[-1])
    with jax.named_scope("attn.block_mask"):
        return _attention(q, k, v, block_diffusion_mask(seq_len, block_length),
                          scale, precision, interpret, block_q, block_k,
                          scope="attn.block_mask")


def sparse_attention(q, k, v, pairs, scale=None, precision="default",
                     interpret=None, block_q=512, block_k=512):
    """Attention over the pairs a step picks itself: ``pairs`` (B, T, T),
    nonzero where query ``t`` sees key ``s``, is data (an indexer's
    selection), not a function of the indices known when the step is traced.
    ``q``: (B, H, T, D); ``k``, ``v``: (B, Hkv, T, D).  The picked pairs lie
    on or under the diagonal.

    The kernels are the static masks' own over the causal mask's list of
    tiles: every tile on or under the diagonal is visited, a grid step
    each, and masked from the pairs' int8 (block_q, block_k) tile, streamed
    beside the keys (the backward kernel takes the transposed array).  A
    tile in which nothing is picked adds nothing; it is visited all the
    same, so that the step's time does not follow what the indexer picks.
    Returns ``(out, lse)``: the
    output and the log-sum-exp over each row's picked keys (B, H, T), whose
    cotangent is taken as 0 (it feeds an indexer's loss under
    ``stop_gradient``).  No gradient reaches ``pairs``."""
    import jax
    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name

    B, Hq, T, D = q.shape
    if k.shape[2] != T or pairs.shape != (B, T, T):
        raise ValueError("sparse attention over %d queries, %d keys and "
                         "pairs %s: one square a batch row"
                         % (T, k.shape[2], pairs.shape))
    if scale is None:
        scale = 1.0 / _np.sqrt(D)
    with jax.named_scope("dsa.attend"):
        if interpret is None and jax.default_backend() != "tpu":
            out, lse = _attention_reference(q, k, v, None, scale,
                                            mask=DATA_MASK, pairs=pairs)
            return out, jax.lax.stop_gradient(lse)
        plan = _Plan(q.shape, k.shape, DATA_MASK, scale, block_q, block_k,
                     _mxu_dtype(q.dtype, precision), bool(interpret))

        @jax.custom_vjp
        def f(q_, k_, v_, padded_):
            return f_fwd(q_, k_, v_, padded_)[0]

        def f_fwd(q_, k_, v_, padded_):
            out, lse = _attention_fwd_pallas(plan, q_, k_, v_, padded_)
            out = checkpoint_name(out, ATTENTION_RESIDUALS[0])
            lse = checkpoint_name(lse[..., 0], ATTENTION_RESIDUALS[1])
            return (_trim(plan, out), lse.reshape(B, Hq, -1)[:, :, :T]), \
                (q_, k_, v_, padded_, out, lse)

        def f_bwd(res, cotangents):
            q_, k_, v_, padded_, out, lse = res
            with jax.named_scope("dsa.attend"):
                grads = _attention_bwd_pallas(
                    plan, q_, k_, v_, out, lse, cotangents[0],
                    jnp.swapaxes(padded_, 1, 2))
            return grads + (None,)

        f.defvjp(f_fwd, f_bwd)
        out, lse = f(q, k, v, _padded_pairs(plan, pairs))
        return out, jax.lax.stop_gradient(lse)


def _head_mean_pallas(plan, q, k, lse, pairs):
    """``mean_h exp(scale * q[h] k[g(h)]^T - lse[h])`` over the picked pairs
    of every visited tile, 0 on its other pairs: (B, padded Tq, padded Tk)
    float32.  Grid (batch, the list of visited tiles, query heads), the
    heads innermost: a tile of the result stays in VMEM while the heads'
    probabilities are added into it, and is masked and scaled at the last.
    A tile above the diagonal is not visited and never written: the caller
    reads the result under the pairs alone."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    p = plan
    bq, bk, D, G, H = p.block_q, p.block_k, p.D, p.G, p.Hq
    cdt = p.mxu_dtype

    def kernel(qidx_ref, kidx_ref, flag_ref, q_ref, k_ref, lse_ref, pairs_ref,
               o_ref):
        h = pl.program_id(2)

        @pl.when((flag_ref[pl.program_id(1)] & _STATE) > 0)
        def _():
            s = _nt(q_ref[...].astype(cdt), k_ref[...].astype(cdt)) * p.scale
            prob = jnp.exp(s - lse_ref[...])

            @pl.when(h == 0)
            def _():
                o_ref[...] = prob

            @pl.when(h > 0)
            def _():
                o_ref[...] += prob

            @pl.when(h == H - 1)
            def _():
                o_ref[...] = jnp.where(
                    pairs_ref[...].astype(jnp.int32) != 0,
                    o_ref[...] * (1.0 / H), 0.0)

    qf = _pad_rows(q, p.pad_q).reshape(p.B * H, p.n_q * bq, D)
    kf = _pad_rows(k, p.pad_k).reshape(p.B * p.Hkv, p.n_k * bk, D)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(p.B, p.steps, H),
            in_specs=[
                pl.BlockSpec((None, bq, D), lambda b, t, h, qidx, kidx, flag:
                             (b * H + h, qidx[t], 0)),
                pl.BlockSpec((None, bk, D), lambda b, t, h, qidx, kidx, flag:
                             (b * p.Hkv + h // G, kidx[t], 0)),
                pl.BlockSpec((None, bq, 1), lambda b, t, h, qidx, kidx, flag:
                             (b * H + h, qidx[t], 0)),
                pl.BlockSpec((None, bq, bk), lambda b, t, h, qidx, kidx, flag:
                             (b, qidx[t], kidx[t]))],
            out_specs=pl.BlockSpec(
                (None, bq, bk), lambda b, t, h, qidx, kidx, flag:
                (b, qidx[t], kidx[t]))),
        out_shape=jax.ShapeDtypeStruct((p.B, p.n_q * bq, p.n_k * bk),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "arbitrary", "arbitrary")),
        interpret=p.interpret, name="index_target",
    )(*p.tables(), qf, kf, lse, pairs)


def head_mean_probabilities(q, k, lse, pairs, scale=None, precision="default",
                            interpret=None, block_q=512, block_k=512):
    """The attention's own distribution over each query's picked keys,
    averaged over its query heads: ``mean_h exp(scale * q[b, h, t] . k[b,
    g(h), s] - lse[b, h, t])`` where ``pairs[b, t, s]`` is nonzero; (B, T,
    T) float32, **to be read under the pairs alone**: a tile above the
    diagonal is not visited and holds whatever was there (the other pairs
    of a visited tile, and everything on the XLA path, are 0).
    ``q``: (B, H, T, D), ``k``: (B, Hkv, T, D), ``lse``: (B, H, T) as
    ``sparse_attention`` gave it.  On a TPU (or where ``interpret`` is
    given) one kernel over the attention kernels' tiles and their list, the
    heads innermost; elsewhere (and as the kernel's oracle) XLA by chunks
    of queries.  The result is a constant: no gradient goes back to ``q``,
    ``k`` or ``lse``."""
    import jax
    import jax.numpy as jnp
    B, H, T, D = q.shape
    if scale is None:
        scale = 1.0 / _np.sqrt(D)
    q, k, lse = jax.lax.stop_gradient((q, k, lse))
    if interpret is None and jax.default_backend() != "tpu":
        return _head_mean_reference(q, k, lse, pairs, scale)
    plan = _Plan(q.shape, k.shape, DATA_MASK, scale, block_q, block_k,
                 _mxu_dtype(q.dtype, precision), bool(interpret))
    lse = jnp.pad(lse, ((0, 0), (0, 0), (0, plan.pad_q))).reshape(
        B * H, -1, 1)
    return _head_mean_pallas(plan, q, k, lse,
                             _padded_pairs(plan, pairs))[:, :T, :T]


def _head_mean_reference(q, k, lse, pairs, scale, chunk=256):
    """``head_mean_probabilities`` in XLA, by chunks of ``chunk`` queries
    (a chunk's (B, H, chunk, T) scores are the largest value alive)."""
    import jax
    import jax.numpy as jnp
    B, H, T, D = q.shape
    Hkv = k.shape[1]
    chunk = chunk if T % chunk == 0 else T
    n = T // chunk

    def one(args):
        q_c, lse_c, pairs_c = args   # (B,Hkv,G,chunk,D) (B,Hkv,G,chunk) ...
        s = jnp.einsum("bhgtd,bhsd->bhgts", q_c, k,
                       preferred_element_type=jnp.float32) * scale
        prob = jnp.exp(s - lse_c[..., None])
        prob = jnp.where((pairs_c != 0)[:, None, None], prob, 0.0)
        return jnp.sum(prob, axis=(1, 2)) / H                 # (B, chunk, T)

    out = jax.lax.map(one, (
        jnp.moveaxis(q.reshape(B, Hkv, H // Hkv, n, chunk, D), 3, 0),
        jnp.moveaxis(lse.reshape(B, Hkv, H // Hkv, n, chunk), 3, 0),
        jnp.moveaxis(pairs.reshape(B, n, chunk, T), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(B, T, T)


_INT_MIN = -2 ** 31
SELECT_ROWS = 64        # rows of scores a step of the selection kernel holds


def select_thresholds(scores, k, interpret=False):
    """For each row ``t`` of ``scores`` (B, T, T) float32, ``T`` a multiple
    of 128: the order-preserving bits of its ``k``-th largest score among
    the keys ``s <= t`` (0 where there are fewer than ``k``), as the int32
    pattern of that uint32, and the cut among the keys equal to it (the
    largest ``p`` such that fewer of them than are still wanted lie before
    ``p``): two (B, T) int32 arrays, as ``ops.decoder_ops.select_top_k``
    finds them.  One kernel: ``SELECT_ROWS`` rows of scores are read into
    VMEM once and the 32 + ``log2 T`` passes that compare and count run
    there, where XLA reads the square from HBM for every pass."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, _ = scores.shape
    R = min(SELECT_ROWS, T)
    cut_bits = max(T - 1, 1).bit_length()

    def kernel(s_ref, threshold_ref, cut_ref):
        x = s_ref[...]
        x = jnp.where(x == 0, 0.0, x)               # -0 as +0
        bits = jax.lax.bitcast_convert_type(x, jnp.int32)
        # signed integers in the scores' order; the keys after the query last
        key = bits ^ ((bits >> 31) & jnp.int32(0x7fffffff))
        row = pl.program_id(1) * R + jax.lax.broadcasted_iota(
            jnp.int32, (R, T), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (R, T), 1)
        key = jnp.where(col <= row, key, jnp.int32(_INT_MIN))

        def count(found):       # exact in float32: at most T of them
            return jnp.sum(jnp.where(found, 1.0, 0.0), axis=1, keepdims=True)

        def raise_threshold(i, low):    # low: the uint32's pattern, (R, 1)
            tried = low | jax.lax.shift_left(jnp.int32(1), 31 - i)
            enough = count(key >= (tried ^ jnp.int32(_INT_MIN))) >= k
            return jnp.where(enough, tried, low)

        low = jax.lax.fori_loop(0, 32, raise_threshold,
                                jnp.zeros((R, 1), jnp.int32))
        threshold = low ^ jnp.int32(_INT_MIN)
        wanted = k - count(key > threshold)
        equal = key == threshold

        def raise_cut(i, low):
            tried = low | jax.lax.shift_left(jnp.int32(1), cut_bits - 1 - i)
            fewer = count(equal & (col < tried)) < wanted
            return jnp.where(fewer, tried, low)

        threshold_ref[...] = low
        cut_ref[...] = jax.lax.fori_loop(0, cut_bits, raise_cut,
                                         jnp.zeros((R, 1), jnp.int32))

    row_spec = pl.BlockSpec((None, R, 1), lambda b, i: (b, i, 0))
    shape = jax.ShapeDtypeStruct((B, T, 1), jnp.int32)
    threshold, cut = pl.pallas_call(
        kernel, grid=(B, T // R),
        in_specs=[pl.BlockSpec((None, R, T), lambda b, i: (b, i, 0))],
        out_specs=[row_spec, row_spec], out_shape=[shape, shape],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret, name="index_select")(scores)
    return threshold[..., 0], cut[..., 0]


# ---------------------------------------------------------------------------
# the gated short convolution
# ---------------------------------------------------------------------------
SHORT_CONV_ROWS = 256       # rows of the sequence to a grid step
_SHORT_CONV_LANES = 512     # channels to a pass inside a step
_HALO = 8                   # rows fetched of the neighbouring tile


def _gated_short_conv_reference(streams, taps):
    """The gated short convolution in XLA, ``K`` shifted copies in one
    elementwise pass: the kernels' oracle, and the path off the TPU."""
    import jax.numpy as jnp
    K = taps.shape[1]
    gate_in, gate_out, x = jnp.split(streams, 3, axis=-1)
    z = gate_in * x
    length = z.shape[1]
    padded = jnp.pad(z, ((0, 0), (K - 1, 0), (0, 0)))
    taps = taps.astype(z.dtype)
    c = padded[:, :length] * taps[:, 0]
    for j in range(1, K):
        c = c + padded[:, j:j + length] * taps[:, j]
    return gate_out * c


def _rows_from_before(z, s, before, row):
    """``z`` (T, C) moved ``s`` rows on, its first ``s`` rows the last of
    ``before`` (8, C), the tile's rows before it; ``row``: (8, C) iota."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu
    moved = pltpu.roll(z, s, 0)
    first = jnp.where(row < s, pltpu.roll(before, s, 0), moved[:_HALO])
    return jnp.concatenate([first, moved[_HALO:]], axis=0)


def _rows_from_after(z, s, after, row):
    """``z`` (T, C) moved ``s`` rows back, its last ``s`` rows the first of
    ``after`` (8, C), the tile's rows after it."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu
    T = z.shape[0]
    moved = pltpu.roll(z, T - s, 0)
    last = jnp.where(row >= _HALO - s, pltpu.roll(after, _HALO - s, 0),
                     moved[T - _HALO:])
    return jnp.concatenate([moved[:T - _HALO], last], axis=0)


def gated_short_conv(streams, taps, interpret=None, rows=SHORT_CONV_ROWS):
    """The gated short convolution between a block's two projections (LFM2's
    ``Lfm2ShortConv``).  ``streams``: (B, L, 3 d), the input projection's
    three streams ``[Bg, Cg, X]``; ``taps``: (d, K), one filter a channel.
    ``z = Bg * X``; ``c[t] = sum_j taps[:, j] * z[t - (K - 1) + j]``, ``z``
    zero before the sequence's first row (a causal depthwise
    cross-correlation along L: row ``t`` reads rows ``t - K + 1 .. t`` of its
    own sequence); output ``Cg * c``, (B, L, d).  Nothing for a matrix unit:
    the bound is memory's, 4 passes of ``B L d`` values forward (three
    streams read, one written) and 7 backward.

    On a TPU (or where ``interpret`` is given), with ``d`` a multiple of 128
    and ``L`` of ``rows``: two kernels, ``short_conv_fwd`` and
    ``short_conv_bwd``, each over tiles of ``rows`` rows and all channels,
    that read every stream once, take the ``K - 1`` rows a tile needs of its
    neighbour from an 8-row block of the same array, and move rows inside the
    tile on the rotate unit; the backward kernel recomputes ``c``, writes the
    three streams' gradients as one array and a tile's share of the taps'
    gradient, which XLA then sums.  XLA's own form of the forward pass read
    2.4 times its bound on a v5e and 3.3 times with the backward pass
    (PERF.md, PR 35).  Elsewhere the XLA form."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    B, L, d3 = streams.shape
    d, K = taps.shape
    if d3 != 3 * d:
        raise ValueError("three streams of %d channels, not %d in all"
                         % (d, d3))
    rows = min(rows, L)
    use = interpret is not None or jax.default_backend() == "tpu"
    if not use or d % 128 or L % rows or rows % _HALO or K - 1 > _HALO \
            or rows < 2 * _HALO:
        with jax.named_scope("conv.gated"):
            return _gated_short_conv_reference(streams, taps)
    from jax.experimental.pallas import tpu as pltpu
    n, lanes = L // rows, min(_SHORT_CONV_LANES, d)
    per_tile = rows // _HALO
    # a tap a row, so that it lies along the lanes of its channels
    taps_t = jnp.pad(taps.astype(streams.dtype).T, ((0, _HALO - K), (0, 0)))

    def tile(width):
        return pl.BlockSpec((None, rows, width), lambda b, i: (b, i, 0))

    def before(width):      # the 8 rows before tile i (tile 0: unused)
        return pl.BlockSpec((None, _HALO, width), lambda b, i: (
            b, jnp.maximum(i * per_tile - 1, 0), 0))

    def after(width):       # the 8 rows after tile i (the last: unused)
        return pl.BlockSpec((None, _HALO, width), lambda b, i: (
            b, jnp.minimum((i + 1) * per_tile, n * per_tile - 1), 0))

    taps_spec = pl.BlockSpec((_HALO, d), lambda b, i: (0, 0))

    chunks = [slice(c, min(c + lanes, d)) for c in range(0, d, lanes)]

    def of(k, cols):        # where stream k's channels ``cols`` lie in a row
        return slice(k * d + cols.start, k * d + cols.stop)

    def conv(z, z_before, w_ref, cols, row):
        """(c, [z moved s rows on, s = 0 .. K - 1]) of one chunk."""
        moved = [z] + [_rows_from_before(z, s, z_before, row)
                       for s in range(1, K)]
        c = w_ref[K - 1:K, cols] * z
        for s in range(1, K):
            c = c + w_ref[K - 1 - s:K - s, cols] * moved[s]
        return c, moved

    def fwd_kernel(s_ref, b_ref, w_ref, o_ref):
        first = pl.program_id(1) == 0
        for cols in chunks:
            row = jax.lax.broadcasted_iota(
                jnp.int32, (_HALO, cols.stop - cols.start), 0)
            z = s_ref[:, of(0, cols)] * s_ref[:, of(2, cols)]
            z_before = jnp.where(
                first, 0.0, b_ref[:, of(0, cols)] * b_ref[:, of(2, cols)])
            c, _ = conv(z, z_before, w_ref, cols, row)
            o_ref[:, cols] = s_ref[:, of(1, cols)] * c

    def bwd_kernel(g_ref, s_ref, b_ref, ga_ref, a_ref, w_ref, ds_ref,
                   dw_ref):
        i = pl.program_id(1)
        dw_ref[...] = jnp.zeros(dw_ref.shape, dw_ref.dtype)
        for cols in chunks:
            row = jax.lax.broadcasted_iota(
                jnp.int32, (_HALO, cols.stop - cols.start), 0)
            gate_in, gate_out, x = (s_ref[:, of(k, cols)] for k in range(3))
            g = g_ref[:, cols]
            z_before = jnp.where(
                i == 0, 0.0, b_ref[:, of(0, cols)] * b_ref[:, of(2, cols)])
            c, moved = conv(gate_in * x, z_before, w_ref, cols, row)
            dc = g * gate_out
            dc_after = jnp.where(i == n - 1, 0.0,
                                 ga_ref[:, cols] * a_ref[:, of(1, cols)])
            # c[t] reads z[t - s]: dz[t] collects dc[t + s]
            dz = w_ref[K - 1:K, cols] * dc
            for s in range(1, K):
                dz = dz + w_ref[K - 1 - s:K - s, cols] * _rows_from_after(
                    dc, s, dc_after, row)
            ds_ref[:, of(0, cols)] = dz * x
            ds_ref[:, of(1, cols)] = g * c
            ds_ref[:, of(2, cols)] = dz * gate_in
            for s in range(K):
                dw_ref[K - 1 - s:K - s, cols] = jnp.sum(
                    dc * moved[s], axis=0, keepdims=True)

    def call(kernel, name, in_specs, out_specs, out_shape):
        # a tile of 256 rows of three streams is 6 MiB, twice for the
        # pipeline, beside the output's: more than a kernel's default 16
        return pl.pallas_call(
            kernel, grid=(B, n), in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
                vmem_limit_bytes=64 * 2 ** 20),
            interpret=bool(interpret), name=name)

    @jax.custom_vjp
    def f(streams_, taps_t_):
        return f_fwd(streams_, taps_t_)[0]

    def f_fwd(streams_, taps_t_):
        out = call(fwd_kernel, "short_conv_fwd",
                   [tile(d3), before(d3), taps_spec], tile(d),
                   jax.ShapeDtypeStruct((B, L, d), streams_.dtype))(
                       streams_, streams_, taps_t_)
        return out, (streams_, taps_t_)

    def f_bwd(res, g):
        streams_, taps_t_ = res
        with jax.named_scope("conv.gated"):
            d_streams, d_taps = call(
                bwd_kernel, "short_conv_bwd",
                [tile(d), tile(d3), before(d3), after(d), after(d3),
                 taps_spec],
                [tile(d3), pl.BlockSpec((None, None, _HALO, d),
                                        lambda b, i: (b, i, 0, 0))],
                [jax.ShapeDtypeStruct((B, L, d3), streams_.dtype),
                 jax.ShapeDtypeStruct((B, n, _HALO, d), jnp.float32)])(
                     g, streams_, streams_, g, streams_, taps_t_)
        return d_streams, jnp.sum(d_taps, axis=(0, 1)).astype(taps_t_.dtype)

    f.defvjp(f_fwd, f_bwd)
    with jax.named_scope("conv.gated"):
        return f(streams, taps_t)


# ---------------------------------------------------------------------------
# the state-space scan (Mamba-2's structured state-space duality)
# ---------------------------------------------------------------------------
SSD_CHUNK = 128             # rows of the sequence a chunk of the scan holds


def _ssd_heads(a, groups):
    """(Bt, L, G, N) of a group's values as (Bt, L, H, N), head ``h``
    reading group ``h // (H / G)``."""
    import jax.numpy as jnp
    return jnp.repeat(a, groups, axis=2)


def _ssd_chunks_reference(x, dt, l, B, C, chunk):
    """The scan without ``D``, XLA's chunked form (Mamba-2's "SSD minimal"):
    each chunk's rows from the chunk's own pairs (a ``chunk x chunk`` square
    of decays a head) and the state at its start, the states from chunk to
    chunk by a carry.  ``x`` (Bt, L, H, P), ``dt`` and ``l`` (Bt, L, H): the
    step and the cumulative log decay inside each chunk; ``B``, ``C`` (Bt,
    L, G, N).  Returns (y (Bt, L, H, P) float32, each chunk's starting state
    (Bt, n, H, P, N) float32)."""
    import jax
    import jax.numpy as jnp
    Bt, L, H, P = x.shape
    G = B.shape[2]
    n, Q = L // chunk, chunk
    x, dt, l = (a.astype(jnp.float32).reshape((Bt, n, Q) + a.shape[2:])
                for a in (x, dt, l))
    Bh, Ch = (_ssd_heads(a.astype(jnp.float32), H // G).reshape(
        Bt, n, Q, H, -1) for a in (B, C))
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    lt = jnp.moveaxis(l, 2, 3)                              # (Bt, n, H, Q)
    decay = jnp.where(causal, jnp.exp(jnp.minimum(
        lt[..., :, None] - lt[..., None, :], 0.0)), 0.0)    # (Bt, n, H, Q, Q)
    scores = jnp.einsum("bcqhn,bcshn->bchqs", Ch, Bh) * decay \
        * jnp.moveaxis(dt, 2, 3)[..., None, :]
    y = jnp.einsum("bchqs,bcshp->bcqhp", scores, x)
    last = l[:, :, -1]                                      # (Bt, n, H)
    w = jnp.exp(last[:, :, None] - l) * dt                  # (Bt, n, Q, H)
    inside = jnp.einsum("bcqh,bcqhp,bcqhn->bchpn", w, x, Bh)

    def carry(state, chunk_in):
        grown, into = chunk_in
        return jnp.exp(grown)[..., None, None] * state + into, state

    _, starts = jax.lax.scan(carry, jnp.zeros((Bt, H, P, Bh.shape[-1]),
                                              jnp.float32),
                             (jnp.moveaxis(last, 1, 0),
                              jnp.moveaxis(inside, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)                     # (Bt, n, H, P, N)
    y = y + jnp.exp(l)[..., None] * jnp.einsum("bcqhn,bchpn->bcqhp", Ch,
                                               starts)
    return y.reshape(Bt, L, H, P), starts


def _ssd_kernels(shape, groups, state_size, chunk, interpret):
    """The forward and backward kernels of ``ssd_scan`` for ``x`` of
    ``shape`` (Bt, L, H, P), ``B`` and ``C`` of ``groups`` groups of
    ``state_size``: ``(forward, backward)``, each a function of the kernels'
    operands."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    Bt, L, H, P = shape
    G, Q, N = groups, chunk, state_size
    hg, n = H // G, L // Q
    cdt = jnp.float32 if interpret else jnp.bfloat16

    def cast(a):
        return a.astype(cdt)

    def head(ref, h):                     # (Q, hg * P) block: head h's rows
        return ref[:, h * P:(h + 1) * P]

    def column(ref, h):                   # (Q, hg) block: head h's (Q, 1)
        block = ref[...]
        lane = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
        return jnp.sum(jnp.where(lane == h, block, 0.0), axis=1,
                       keepdims=True)

    def squares(b_ref, c_ref):
        Bq, Cq = cast(b_ref[...]), cast(c_ref[...])
        row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
        return Bq, Cq, _nt(Cq, Bq), col <= row, row

    def decays(lc, lr, causal):
        return jnp.where(causal, jnp.exp(jnp.minimum(lc - lr, 0.0)), 0.0)

    def fwd_kernel(x_ref, b_ref, c_ref, lc_ref, lr_ref, dc_ref, dr_ref,
                   y_ref, s_ref, state):
        @pl.when(pl.program_id(2) == 0)
        def _():
            state[...] = jnp.zeros(state.shape, state.dtype)

        Bq, Cq, CB, causal, _ = squares(b_ref, c_ref)
        for h in range(hg):
            lc, dtc = column(lc_ref, h), column(dc_ref, h)
            lr, dtr = lr_ref[h:h + 1, :], dr_ref[h:h + 1, :]
            xh, start = head(x_ref, h), state[h]
            s_ref[h] = start
            scores = CB * decays(lc, lr, causal) * dtr
            y_ref[:, h * P:(h + 1) * P] = jnp.dot(
                cast(scores), cast(xh), preferred_element_type=jnp.float32) \
                + jnp.exp(lc) * _nt(Cq, cast(start))
            last = lc[Q - 1:Q, :]
            w = jnp.exp(last - lc) * dtc
            state[h] = jnp.exp(last) * start + _tn(cast(xh * w), Bq)

    def bwd_kernel(x_ref, b_ref, c_ref, lc_ref, lr_ref, dc_ref, dr_ref,
                   dy_ref, s_ref, dx_ref, db_ref, dcm_ref, ddtc_ref, ddtr_ref,
                   dlc_ref, dlr_ref, dstate):
        @pl.when(pl.program_id(2) == 0)
        def _():
            dstate[...] = jnp.zeros(dstate.shape, dstate.dtype)

        Bq, Cq, CB, causal, row = squares(b_ref, c_ref)
        Bm = b_ref[...]
        d_cb = jnp.zeros((Q, Q), jnp.float32)
        dB = jnp.zeros(Bm.shape, jnp.float32)
        dC = jnp.zeros(Bm.shape, jnp.float32)
        lane = jax.lax.broadcasted_iota(jnp.int32, (Q, hg), 1)
        sub = jax.lax.broadcasted_iota(jnp.int32, (hg, Q), 0)
        ddt_c = jnp.zeros((Q, hg), jnp.float32)
        dl_c = jnp.zeros((Q, hg), jnp.float32)
        ddt_r = jnp.zeros((hg, Q), jnp.float32)
        dl_r = jnp.zeros((hg, Q), jnp.float32)
        for h in range(hg):
            lc, dtc = column(lc_ref, h), column(dc_ref, h)
            lr, dtr = lr_ref[h:h + 1, :], dr_ref[h:h + 1, :]
            xh, dyh = head(x_ref, h), head(dy_ref, h)
            start, d_end = s_ref[h], dstate[h]
            decay = decays(lc, lr, causal)
            e, last = jnp.exp(lc), lc[Q - 1:Q, :]
            grown = jnp.exp(last)
            w = jnp.exp(last - lc) * dtc
            # y = (CB * decay * dt_s) x + e * C start^T
            scores = CB * decay * dtr
            d_scores = _nt(cast(dyh), cast(xh))
            d_cbd = d_scores * CB * decay
            d_cb = d_cb + d_scores * decay * dtr
            pulled = d_cbd * dtr
            edy = e * dyh
            from_start = _nt(Cq, cast(start))
            # the end state: grown * start + (x * w)^T B
            x_d_end = jnp.dot(cast(xh), cast(d_end),
                              preferred_element_type=jnp.float32)
            dw = jnp.sum(x_d_end * Bm, axis=1, keepdims=True)
            dx_ref[:, h * P:(h + 1) * P] = _tn(cast(scores), cast(dyh)) \
                + w * _nt(Bq, cast(d_end))
            dB = dB + w * x_d_end
            dC = dC + jnp.dot(cast(edy), cast(start),
                              preferred_element_type=jnp.float32)
            d_last = jnp.sum(d_end * grown * start) + jnp.sum(dw * w)
            dl = jnp.sum(pulled, axis=1, keepdims=True) \
                + jnp.sum(edy * from_start, axis=1, keepdims=True) - dw * w \
                + jnp.where(row[:, :1] == Q - 1, d_last, 0.0)
            ddt_c = jnp.where(lane == h, dw * jnp.exp(last - lc), ddt_c)
            dl_c = jnp.where(lane == h, dl, dl_c)
            ddt_r = jnp.where(sub == h, jnp.sum(d_cbd, axis=0,
                                                keepdims=True), ddt_r)
            dl_r = jnp.where(sub == h, -jnp.sum(pulled, axis=0,
                                                keepdims=True), dl_r)
            dstate[h] = grown * d_end + _tn(cast(edy), Cq)
        db_ref[...] = dB + _tn(cast(d_cb), Cq)
        dcm_ref[...] = dC + jnp.dot(cast(d_cb), Bq,
                                    preferred_element_type=jnp.float32)
        ddtc_ref[...], dlc_ref[...] = ddt_c, dl_c
        ddtr_ref[...], dlr_ref[...] = ddt_r, dl_r

    def specs(reverse):
        at = (lambda c: n - 1 - c) if reverse else (lambda c: c)
        spec = pl.BlockSpec
        wide = spec((None, Q, hg * P), lambda b, g, c: (b, at(c), g))
        group = spec((None, Q, N), lambda b, g, c: (b, at(c), g))
        cols = spec((None, None, Q, hg), lambda b, g, c: (b, g, at(c), 0))
        rows = spec((None, None, hg, Q), lambda b, g, c: (b, g, 0, at(c)))
        states = spec((None, None, hg, P, N),
                      lambda b, g, c: (b, at(c), g, 0, 0))
        return wide, group, cols, rows, states

    def call(kernel, name, in_specs, out_specs, out_shape, scratch):
        return pl.pallas_call(
            kernel, grid=(Bt, G, n), in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=bool(interpret), name=name)

    def forward(x, B, C, lc, lr, dc, dr):
        wide, group, cols, rows, states = specs(False)
        f32 = jnp.float32
        return call(fwd_kernel, "ssd_scan_fwd",
                    [wide, group, group, cols, rows, cols, rows],
                    [wide, states],
                    [jax.ShapeDtypeStruct((Bt, L, H * P), f32),
                     jax.ShapeDtypeStruct((Bt, n, H, P, N), f32)],
                    [pltpu.VMEM((hg, P, N), f32)])(
                        x, B, C, lc, lr, dc, dr)

    def backward(x, B, C, lc, lr, dc, dr, dy, starts):
        wide, group, cols, rows, states = specs(True)
        f32 = jnp.float32
        return call(bwd_kernel, "ssd_scan_bwd",
                    [wide, group, group, cols, rows, cols, rows, wide, states],
                    [wide, group, group, cols, rows, cols, rows],
                    [jax.ShapeDtypeStruct((Bt, L, H * P), f32),
                     jax.ShapeDtypeStruct((Bt, L, G * N), f32),
                     jax.ShapeDtypeStruct((Bt, L, G * N), f32),
                     jax.ShapeDtypeStruct((Bt, G, L, hg), f32),
                     jax.ShapeDtypeStruct((Bt, G, hg, L), f32),
                     jax.ShapeDtypeStruct((Bt, G, L, hg), f32),
                     jax.ShapeDtypeStruct((Bt, G, hg, L), f32)],
                    [pltpu.VMEM((hg, P, N), f32)])(
                        x, B, C, lc, lr, dc, dr, dy, starts)

    return forward, backward


def ssd_scan(x, dt, A, B, C, D, chunk=SSD_CHUNK, interpret=None):
    """Mamba-2's state-space recurrence over a sequence, a head at a time:
    ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` (``P x N`` a head, ``S_{-1}
    = 0``), ``y_t = S_t C_t + D x_t``.  ``x`` (Bt, L, H, P); ``dt`` (Bt, L,
    H), the steps, positive; ``A`` and ``D`` (H,), ``A`` negative; ``B``,
    ``C`` (Bt, L, G, N), head ``h`` reading group ``h // (H / G)``.  Returns
    ``y`` (Bt, L, H, P) in ``x``'s dtype.

    The sequence is cut into chunks of ``chunk`` rows (the last padded with
    zero steps, which neither decay nor add).  Inside a chunk the decays are
    float32 cumulative sums of ``dt A`` (``l``); a row takes the chunk's
    own earlier rows through a ``chunk x chunk`` square of ``exp(l_t - l_s)
    dt_s C_t . B_s`` and the state at the chunk's start through ``exp(l_t)
    C_t``; the state goes on to the next chunk.

    On a TPU (or where ``interpret`` is given), with a group's heads' ``P``
    and ``N`` multiples of 128 and ``chunk`` of 128 (any shape where
    interpreted): two kernels under the scope ``ssm.scan``, ``ssd_scan_fwd``
    and ``ssd_scan_bwd``, each a grid step a (row of the batch, group,
    chunk), the chunk axis walked in order forward and in reverse backward.
    A step makes ``C B^T`` once for the group's heads, and each head's state
    (``P x N`` float32) stays in VMEM from chunk to chunk; the forward
    kernel writes ``y`` and each chunk's starting state, the backward kernel
    carries the state's gradient back and gives the gradients of ``x``,
    ``B``, ``C``, the steps and the decays, which XLA takes on to ``dt`` and
    ``A``.  The products take bfloat16 operands and accumulate in float32,
    what XLA's default precision gives float32 operands on the TPU; the
    decays and the state are float32.  Elsewhere XLA's chunked form
    (``_ssd_chunks_reference``): 2.8 times the kernels' time on a v5e at
    8,192 rows of 64 heads (PERF.md, PR 41)."""
    import jax
    import jax.numpy as jnp

    Bt, L, H, P = x.shape
    G, N = B.shape[2:]
    if H % G or C.shape != B.shape or dt.shape != (Bt, L, H):
        raise ValueError("x %s, dt %s, B %s, C %s: heads in whole groups"
                         % (x.shape, dt.shape, B.shape, C.shape))
    f32 = jnp.float32
    pad = -L % chunk
    n, hg = (L + pad) // chunk, H // G
    profiler.count("ssm.chunks", Bt * n)

    def padded(a):
        return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) \
            if pad else a

    xp, dtp, Bp, Cp = (padded(a) for a in (x, dt.astype(f32), B, C))
    Lp = L + pad
    l = jnp.cumsum((dtp * A.astype(f32)).reshape(Bt, n, chunk, H),
                   axis=2).reshape(Bt, Lp, H)
    if interpret is None and not (
            jax.default_backend() == "tpu" and (hg * P) % 128 == 0
            and N % 128 == 0 and chunk % 128 == 0):
        with jax.named_scope("ssm.scan"):
            y, _ = _ssd_chunks_reference(xp, dtp, l, Bp, Cp, chunk)
            y = y[:, :L] + D.astype(f32)[:, None] * x.astype(f32)
            return y.astype(x.dtype)
    forward, backward = _ssd_kernels((Bt, Lp, H, P), G, N, chunk,
                                     interpret)

    def forms(a):
        """(Bt, Lp, H) as a group's columns (Bt, G, Lp, hg) and rows (Bt,
        G, hg, Lp)."""
        a = a.reshape(Bt, Lp, G, hg)
        return a.transpose(0, 2, 1, 3), a.transpose(0, 2, 3, 1)

    def summed(columns, rows):
        return (columns.transpose(0, 2, 1, 3)
                + rows.transpose(0, 3, 1, 2)).reshape(Bt, Lp, H)

    @jax.custom_vjp
    def core(x_, dt_, l_, B_, C_):
        return core_fwd(x_, dt_, l_, B_, C_)[0]

    def core_fwd(x_, dt_, l_, B_, C_):
        operands = (x_, B_, C_) + forms(l_) + forms(dt_)
        y, starts = forward(*operands)
        return y, operands + (starts,)

    def core_bwd(kept, dy):
        with jax.named_scope("ssm.scan"):
            x_, B_, C_ = kept[:3]
            dx, dB, dC, ddt_c, ddt_r, dl_c, dl_r = backward(
                *kept[:-1], dy.astype(f32), kept[-1])
            return (dx.astype(x_.dtype), summed(ddt_c, ddt_r),
                    summed(dl_c, dl_r), dB.astype(B_.dtype),
                    dC.astype(C_.dtype))

    core.defvjp(core_fwd, core_bwd)
    with jax.named_scope("ssm.scan"):
        y = core(xp.reshape(Bt, Lp, H * P), dtp, l, Bp.reshape(Bt, Lp, G * N),
                 Cp.reshape(Bt, Lp, G * N))
        y = y.reshape(Bt, Lp, H, P)[:, :L] \
            + D.astype(f32)[:, None] * x.astype(f32)
        return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# the held experts' grouped products
# ---------------------------------------------------------------------------
SLOT_TILE_ROWS = 256        # slots to a tile: one expert's, one grid step
_WGRAD_WIDTH = 768          # an expert's hidden units to a weight-gradient step
COMBINE_SLAB = 128          # slots a step of ``slot_combine`` reads at once


def slot_tile_rows(pairs, held):
    """Rows of a tile of the slot table that holds ``pairs`` (row, expert)
    pairs over ``held`` experts: ``SLOT_TILE_ROWS``, or for a table that
    small the mean group rounded up to a multiple of 16 (a bfloat16 tile's
    rows)."""
    return min(SLOT_TILE_ROWS, max(16, -(-pairs // (16 * held)) * 16))


def combine_tile_rows(rows):
    """Rows of a tile of ``slot_combine``'s output: an expert's slots of a
    tile's rows are one run of at most as many, which a slab of
    ``COMBINE_SLAB`` read from a multiple of 8 holds whole, so 120 (all the
    rows, rounded up to 8, where fewer)."""
    return min(COMBINE_SLAB - 8, -(-rows // 8) * 8)


def _tn(a, b):
    """a.T @ b on the MXU, float32 out."""
    import jax
    import jax.numpy as jnp
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _silu_parts(g):
    """(sigmoid(g), silu(g))."""
    import jax
    s = jax.nn.sigmoid(g)
    return s, g * s


def _bf16_pieces(a):
    """Three bfloat16 arrays whose sum is float32 ``a`` exactly: its top 8
    significant bits, the next 8 and the rest, each cut off, not rounded.
    The three then have ``a``'s sign and no bit in common, so every partial
    sum of them is exact, in any order; a product with 0s and 1s selects
    each exactly (``a`` finite)."""
    import jax
    import jax.numpy as jnp

    def top(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.int32) & -65536
        return jax.lax.bitcast_convert_type(bits, jnp.float32)
    hi = top(a)
    rest = a - hi
    mid = top(rest)
    return tuple(p.astype(jnp.bfloat16) for p in (hi, mid, rest - mid))


def _wgrad_block(f):
    """Hidden units to a step of ``moe_experts_wgrad``: the largest multiple
    of 128 up to ``_WGRAD_WIDTH`` that divides ``f``; where none does, the
    fewest blocks of up to ``_WGRAD_WIDTH`` that cover it, in whole lanes
    (640 of 1,856: the last 576), or ``f`` whole where one block holds it."""
    if f % 128 == 0:
        return max(b for b in range(128, min(f, _WGRAD_WIDTH) + 1, 128)
                   if f % b == 0)
    per_block = -(-f // -(-f // _WGRAD_WIDTH))
    return min(-(-per_block // 128) * 128, f)


class _Experts:
    """The grouped products of a held experts' layer over a table of
    ``S`` slots in tiles of ``tm``, every tile one expert's
    (``tile_expert``, (S / tm,) int32, each expert's tiles consecutive and
    every expert with at least one): forward, the gradients of the slots'
    values, the weights' gradients, and the slots' values added back to
    rows.  ``gate_w``, ``up_w``: (held, f, d); ``down_w``: (held, d, f).
    ``act``: what an expert computes, ``"swiglu"`` (``down(silu(gate x) *
    up x)``) or ``"relu2"`` (``down(relu(up x)^2)``, no gate: one product
    to the hidden units, whose gradient is ``dh * 2 relu(up x)``, and two
    weight gradients); the methods take ``gate_w`` and ``dg`` as None for
    the second.

    On a TPU (or where ``interpret`` is given), with ``d`` a multiple of
    128, ``f`` at least 128 and ``tm`` a multiple of 16: five kernels,
    ``moe_experts_hidden``, ``moe_experts_down``, ``moe_experts_bwd`` (a
    grid step a tile, the tile's expert by scalar prefetch, that expert's
    matrices whole in VMEM, ``f`` as it is, and
    fetched again only when the expert changes; the down projection is a
    call of its own so that a recomputed forward pass, which needs the
    hidden units and not the output, drops it), ``moe_experts_wgrad`` (a
    grid step a tile and block of at most ``_WGRAD_WIDTH`` hidden units, an
    expert's gradient blocks resident over its consecutive tiles; where
    ``f`` is no multiple of 128 the last block is ragged, its units past
    ``f`` read as anything and written nowhere) and ``slot_combine``
    (``combine``: each row's slots added up, a grid step a
    tile of rows and a held expert; not ``moe_``-named, since its time is
    no product's).  The number of grid steps
    follows from the shapes alone.  The products take bfloat16 operands
    (cast once, outside) and accumulate in float32, which is what XLA's
    default precision gives float32 operands on the TPU; everything between
    them is float32.  Elsewhere the same products a tile at a time in XLA,
    at the operands' own dtype: the kernels' oracle, and the path off the
    TPU."""

    def __init__(self, tile_expert, tm, d, f, held, interpret=None,
                 act="swiglu"):
        import jax
        import jax.numpy as jnp
        if act not in ("swiglu", "relu2"):
            raise ValueError("an expert is swiglu or relu2, not %r" % (act,))
        self.te, self.tm, self.d, self.f, self.held = tile_expert, tm, d, f, \
            held
        self.act = act
        self.n = tile_expert.shape[0]
        use = interpret is not None or jax.default_backend() == "tpu"
        self.kernels = bool(use and d % 128 == 0 and f >= 128
                            and tm % 16 == 0)
        self.interpret = bool(interpret)
        # what the matrix units are fed
        self.cdt = jnp.bfloat16 if self.kernels else None

    def cast(self, a):
        return a.astype(self.cdt) if self.cdt is not None else a

    def _tiles(self, a):
        return a.reshape(self.n, self.tm, -1)

    # -- specs ---------------------------------------------------------------
    def _call(self, kernel, name, grid, in_specs, out_specs, out_shape):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
                out_specs=out_specs),
            out_shape=out_shape,
            # an expert's matrices (or its gradients' blocks) whole, twice
            # for the pipeline, beside the tiles: 36 MiB at 1536 x 2048,
            # 24 MiB at 896 x 2304
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",) * len(grid),
                vmem_limit_bytes=100 * 2 ** 20),
            interpret=self.interpret, name=name)

    def _specs(self):
        from jax.experimental import pallas as pl
        tm, d, f = self.tm, self.d, self.f
        rows = lambda w: pl.BlockSpec((tm, w), lambda i, te: (i, 0))
        of_expert = lambda a, b: pl.BlockSpec(
            (None, a, b), lambda i, te: (te[i], 0, 0))
        return rows, of_expert(f, d), of_expert(d, f)

    # -- forward -------------------------------------------------------------
    def hidden(self, x_s, w_s, gate_w, up_w, keep):
        """Every slot's weighted hidden units ``silu(gate x) * up x * w`` by
        its tile's expert, (S, f), as the down product takes them; with
        ``keep`` also ``gate x`` and ``up x``, float32, which the backward
        pass reads."""
        import jax
        import jax.numpy as jnp
        S = x_s.shape[0]
        if self.act == "relu2":
            return self._relu2_hidden(x_s, w_s, up_w, keep)
        if not self.kernels:
            xt = self._tiles(x_s)
            g = jnp.einsum("ntd,nfd->ntf", xt, gate_w[self.te])
            u = jnp.einsum("ntd,nfd->ntf", xt, up_w[self.te])
            hw = jax.nn.silu(g) * u * self._tiles(w_s)
            return tuple(a.reshape(S, -1) for a in ((hw, g, u) if keep
                                                    else (hw,)))

        def kernel(te_ref, x_ref, w_ref, wg_ref, wu_ref, hw_ref, *kept):
            x = x_ref[...]
            g = _nt(x, wg_ref[...])
            u = _nt(x, wu_ref[...])
            hw_ref[...] = (jax.nn.silu(g) * u * w_ref[...]).astype(
                hw_ref.dtype)
            if kept:
                kept[0][...], kept[1][...] = g, u

        rows, in_w, _ = self._specs()
        out_shape = [jax.ShapeDtypeStruct((S, self.f), t) for t in (
            (x_s.dtype, jnp.float32, jnp.float32) if keep else (x_s.dtype,))]
        return self._call(
            kernel, "moe_experts_hidden", (self.n,),
            [rows(self.d), rows(1), in_w, in_w],
            [rows(self.f)] * len(out_shape), out_shape)(
                self.te, x_s, w_s[:, None], gate_w, up_w)

    def _relu2_hidden(self, x_s, w_s, up_w, keep):
        """``hidden`` of relu² experts: ``relu(up x)^2 * w``, and with
        ``keep`` also ``up x``, float32."""
        import jax
        import jax.numpy as jnp
        S = x_s.shape[0]
        if not self.kernels:
            u = jnp.einsum("ntd,nfd->ntf", self._tiles(x_s), up_w[self.te])
            hw = jnp.square(jax.nn.relu(u)) * self._tiles(w_s)
            return tuple(a.reshape(S, -1) for a in ((hw, u) if keep
                                                    else (hw,)))

        def kernel(te_ref, x_ref, w_ref, wu_ref, hw_ref, *kept):
            u = _nt(x_ref[...], wu_ref[...])
            hw_ref[...] = (jnp.square(jnp.maximum(u, 0.0)) * w_ref[...]
                           ).astype(hw_ref.dtype)
            if kept:
                kept[0][...] = u

        rows, in_w, _ = self._specs()
        out_shape = [jax.ShapeDtypeStruct((S, self.f), t) for t in (
            (x_s.dtype, jnp.float32) if keep else (x_s.dtype,))]
        return self._call(
            kernel, "moe_experts_hidden", (self.n,),
            [rows(self.d), rows(1), in_w],
            [rows(self.f)] * len(out_shape), out_shape)(
                self.te, x_s, w_s[:, None], up_w)

    def down(self, hw, down_w):
        """``y_s`` (S, d) float32: every slot's hidden units through its
        tile's expert's down projection."""
        import jax
        import jax.numpy as jnp
        S = hw.shape[0]
        if not self.kernels:
            return jnp.einsum("ntf,ndf->ntd", self._tiles(hw),
                              down_w[self.te]).reshape(S, -1)

        def kernel(te_ref, hw_ref, wd_ref, y_ref):
            y_ref[...] = _nt(hw_ref[...], wd_ref[...])

        rows, _, out_w = self._specs()
        return self._call(
            kernel, "moe_experts_down", (self.n,), [rows(self.f), out_w],
            rows(self.d), jax.ShapeDtypeStruct((S, self.d), jnp.float32))(
                self.te, hw, down_w)

    # -- backward: the slots' values -----------------------------------------
    def backward(self, dy_s, g, u, w_s, gate_w, up_w, down_w):
        """(dx_s (S, d) float32, dw_s (S,) float32, dg, du (S, f) as the
        weight gradients' products take them)."""
        import jax
        import jax.numpy as jnp
        S = dy_s.shape[0]
        if self.act == "relu2":
            return self._relu2_backward(dy_s, u, w_s, up_w, down_w)
        if not self.kernels:
            tiles = self._tiles
            dhw = jnp.einsum("ntd,ndf->ntf", tiles(dy_s), down_w[self.te])
            s, sg = _silu_parts(tiles(g))
            dw = jnp.sum(dhw * sg * tiles(u), axis=-1)
            dh = dhw * tiles(w_s)
            du = dh * sg
            dg = dh * tiles(u) * (s + sg * (1 - s))
            dx = jnp.einsum("ntf,nfd->ntd", dg, gate_w[self.te]) \
                + jnp.einsum("ntf,nfd->ntd", du, up_w[self.te])
            return (dx.reshape(S, -1), dw.reshape(S), dg.reshape(S, -1),
                    du.reshape(S, -1))

        def kernel(te_ref, dy_ref, g_ref, u_ref, w_ref, wg_ref, wu_ref,
                   wd_ref, dx_ref, dw_ref, dg_ref, du_ref):
            g, u = g_ref[...], u_ref[...]
            dhw = jnp.dot(dy_ref[...], wd_ref[...],
                          preferred_element_type=jnp.float32)
            s, sg = _silu_parts(g)
            dw_ref[...] = jnp.sum(dhw * sg * u, axis=1, keepdims=True)
            dh = dhw * w_ref[...]
            du = (dh * sg).astype(du_ref.dtype)
            dg = (dh * u * (s + sg * (1 - s))).astype(dg_ref.dtype)
            dg_ref[...], du_ref[...] = dg, du
            dx_ref[...] = jnp.dot(
                dg, wg_ref[...], preferred_element_type=jnp.float32) \
                + jnp.dot(du, wu_ref[...], preferred_element_type=jnp.float32)

        rows, in_w, out_w = self._specs()
        dx, dw, dg, du = self._call(
            kernel, "moe_experts_bwd", (self.n,),
            [rows(self.d), rows(self.f), rows(self.f), rows(1), in_w, in_w,
             out_w],
            [rows(self.d), rows(1), rows(self.f), rows(self.f)],
            [jax.ShapeDtypeStruct((S, self.d), jnp.float32),
             jax.ShapeDtypeStruct((S, 1), jnp.float32),
             jax.ShapeDtypeStruct((S, self.f), dy_s.dtype),
             jax.ShapeDtypeStruct((S, self.f), dy_s.dtype)])(
                 self.te, dy_s, g, u, w_s[:, None], gate_w, up_w, down_w)
        return dx, dw[:, 0], dg, du

    def _relu2_backward(self, dy_s, u, w_s, up_w, down_w):
        """``backward`` of relu² experts: (dx_s, dw_s, None, du)."""
        import jax
        import jax.numpy as jnp
        S = dy_s.shape[0]
        if not self.kernels:
            tiles = self._tiles
            dhw = jnp.einsum("ntd,ndf->ntf", tiles(dy_s), down_w[self.te])
            r = jax.nn.relu(tiles(u))
            dw = jnp.sum(dhw * r * r, axis=-1)
            du = dhw * tiles(w_s) * 2 * r
            dx = jnp.einsum("ntf,nfd->ntd", du, up_w[self.te])
            return dx.reshape(S, -1), dw.reshape(S), None, du.reshape(S, -1)

        def kernel(te_ref, dy_ref, u_ref, w_ref, wu_ref, wd_ref, dx_ref,
                   dw_ref, du_ref):
            r = jnp.maximum(u_ref[...], 0.0)
            dhw = jnp.dot(dy_ref[...], wd_ref[...],
                          preferred_element_type=jnp.float32)
            dw_ref[...] = jnp.sum(dhw * r * r, axis=1, keepdims=True)
            du = (dhw * w_ref[...] * 2 * r).astype(du_ref.dtype)
            du_ref[...] = du
            dx_ref[...] = jnp.dot(du, wu_ref[...],
                                  preferred_element_type=jnp.float32)

        rows, in_w, out_w = self._specs()
        dx, dw, du = self._call(
            kernel, "moe_experts_bwd", (self.n,),
            [rows(self.d), rows(self.f), rows(1), in_w, out_w],
            [rows(self.d), rows(1), rows(self.f)],
            [jax.ShapeDtypeStruct((S, self.d), jnp.float32),
             jax.ShapeDtypeStruct((S, 1), jnp.float32),
             jax.ShapeDtypeStruct((S, self.f), dy_s.dtype)])(
                 self.te, dy_s, u, w_s[:, None], up_w, down_w)
        return dx, dw[:, 0], None, du

    # -- backward: the weights -----------------------------------------------
    def weight_gradients(self, x_s, dy_s, dg, du, hw):
        """(d gate_w, d up_w (held, f, d), d down_w (held, d, f)), float32:
        each expert's sums over its consecutive tiles; an expert whose tiles
        hold no pair gets zeros."""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        held, d, f, tm = self.held, self.d, self.f, self.tm
        if self.act == "relu2":
            return self._relu2_weight_gradients(x_s, dy_s, du, hw)
        if not self.kernels:
            tiles = self._tiles
            xt, dyt = tiles(x_s), tiles(dy_s)
            by_expert = lambda a: jax.ops.segment_sum(a, self.te, held)
            return (by_expert(jnp.einsum("ntf,ntd->nfd", tiles(dg), xt)),
                    by_expert(jnp.einsum("ntf,ntd->nfd", tiles(du), xt)),
                    by_expert(jnp.einsum("ntd,ntf->ndf", dyt, tiles(hw))))
        fb = _wgrad_block(f)

        def kernel(te_ref, x_ref, dy_ref, dg_ref, du_ref, hw_ref, dwg_ref,
                   dwu_ref, dwd_ref):
            i = pl.program_id(1)

            @pl.when((i == 0) | (te_ref[i] != te_ref[jnp.maximum(i - 1, 0)]))
            def _():
                for ref in (dwg_ref, dwu_ref, dwd_ref):
                    ref[...] = jnp.zeros(ref.shape, ref.dtype)

            x = x_ref[...]
            dwg_ref[...] += _tn(dg_ref[...], x)
            dwu_ref[...] += _tn(du_ref[...], x)
            dwd_ref[...] += _tn(dy_ref[...], hw_ref[...])

        wide = pl.BlockSpec((tm, d), lambda j, i, te: (i, 0))
        block = pl.BlockSpec((tm, fb), lambda j, i, te: (i, j))
        in_w = pl.BlockSpec((None, fb, d), lambda j, i, te: (te[i], j, 0))
        out_w = pl.BlockSpec((None, d, fb), lambda j, i, te: (te[i], 0, j))
        return self._call(
            kernel, "moe_experts_wgrad", (-(-f // fb), self.n),
            [wide, wide, block, block, block], [in_w, in_w, out_w],
            [jax.ShapeDtypeStruct((held, f, d), jnp.float32),
             jax.ShapeDtypeStruct((held, f, d), jnp.float32),
             jax.ShapeDtypeStruct((held, d, f), jnp.float32)])(
                 self.te, x_s, dy_s, dg, du, hw)

    def _relu2_weight_gradients(self, x_s, dy_s, du, hw):
        """``weight_gradients`` of relu² experts: (d up_w, d down_w)."""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        held, d, f, tm = self.held, self.d, self.f, self.tm
        if not self.kernels:
            tiles = self._tiles
            by_expert = lambda a: jax.ops.segment_sum(a, self.te, held)
            return (by_expert(jnp.einsum("ntf,ntd->nfd", tiles(du),
                                         tiles(x_s))),
                    by_expert(jnp.einsum("ntd,ntf->ndf", tiles(dy_s),
                                         tiles(hw))))
        fb = _wgrad_block(f)

        def kernel(te_ref, x_ref, dy_ref, du_ref, hw_ref, dwu_ref, dwd_ref):
            i = pl.program_id(1)

            @pl.when((i == 0) | (te_ref[i] != te_ref[jnp.maximum(i - 1, 0)]))
            def _():
                for ref in (dwu_ref, dwd_ref):
                    ref[...] = jnp.zeros(ref.shape, ref.dtype)

            dwu_ref[...] += _tn(du_ref[...], x_ref[...])
            dwd_ref[...] += _tn(dy_ref[...], hw_ref[...])

        wide = pl.BlockSpec((tm, d), lambda j, i, te: (i, 0))
        block = pl.BlockSpec((tm, fb), lambda j, i, te: (i, j))
        in_w = pl.BlockSpec((None, fb, d), lambda j, i, te: (te[i], j, 0))
        out_w = pl.BlockSpec((None, d, fb), lambda j, i, te: (te[i], 0, j))
        return self._call(
            kernel, "moe_experts_wgrad", (-(-f // fb), self.n),
            [wide, wide, block, block], [in_w, out_w],
            [jax.ShapeDtypeStruct((held, f, d), jnp.float32),
             jax.ShapeDtypeStruct((held, d, f), jnp.float32)])(
                 self.te, x_s, dy_s, du, hw)

    # -- the slots back to rows ----------------------------------------------
    def combine_runs(self, rows):
        """Grid steps of ``combine`` over ``rows`` rows, a tile of rows and
        a held expert each; 0 where the kernels do not run."""
        return (-(-rows // combine_tile_rows(rows)) * self.held
                if self.kernels else 0)

    def combine(self, a_s, slot_of, here):
        """(T, width) float32: the values of each row's slots added up, for
        float32 ``a_s`` (S, width) whose empty slots hold 0; ``slot_of`` and
        ``here`` (T, k) as ``parallel.moe.slot_table`` gives them.  One
        kernel, ``slot_combine``: a grid step a tile of rows and a held
        expert.  An expert's group holds its pairs in the order of their
        rows, a row at most one, so a tile's pairs with the expert are one
        run of consecutive slots, no longer than the tile: the step reads a
        slab of ``COMBINE_SLAB`` slots from a multiple of 8 at or below the
        run's first (scalar prefetch; the slab clamped into the table), and
        a product with a one-hot matrix places each slot's row at its row
        of the tile, in three exact bfloat16 pieces (``_bf16_pieces``) that
        are added before the sum over the experts, which stays resident in
        float32.  A pair of an expert held elsewhere is read by no step.
        The grid and the slab follow from the shapes alone."""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        T, k = here.shape
        held, tm, d = self.held, self.tm, a_s.shape[1]
        slots = self.n * tm
        tr = combine_tile_rows(T)
        nt, slab = -(-T // tr), min(COMBINE_SLAB, slots)
        # each row's slot with each held expert, -1 for none: an expert's
        # group runs from its first tile to the next expert's
        first = tm * jnp.sum(self.te[:, None] < jnp.arange(held), axis=0)
        end = jnp.append(first[1:], slots)
        slot = slot_of[:T * k].reshape(T, k, 1)
        hit = here[..., None] & (slot >= first) & (slot < end)
        row_slots = jnp.pad(jnp.max(jnp.where(hit, slot, -1), axis=1),
                            ((0, nt * tr - T), (0, 0)), constant_values=-1)
        lowest = jnp.min(jnp.where(row_slots >= 0, row_slots, slots)
                         .reshape(nt, tr, held), axis=1)
        # the slab's first slot over 8, which the compiler can see is aligned
        start = jnp.minimum(lowest // 8, (slots - slab) // 8).reshape(
            nt * held)

        def kernel(start_ref, rows_ref, a_ref, out_ref):
            i, e = pl.program_id(0), pl.program_id(1)

            @pl.when(e == 0)
            def _():
                out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

            rows = rows_ref[...]
            mine = jnp.max(jnp.where(jax.lax.broadcasted_iota(
                jnp.int32, rows.shape, 1) == e, rows, -1), axis=1,
                keepdims=True) - 8 * start_ref[i * held + e]
            pick = (mine == jax.lax.broadcasted_iota(
                jnp.int32, (tr, slab), 1)).astype(jnp.bfloat16)
            hi, mid, lo = (jnp.dot(pick, p, preferred_element_type=jnp.float32)
                           for p in _bf16_pieces(a_ref[...]))
            out_ref[...] += hi + mid + lo

        return self._call(
            kernel, "slot_combine", (nt, held),
            [pl.BlockSpec((tr, held), lambda i, e, s: (i, 0)),
             pl.BlockSpec((pl.Element(slab), pl.Element(d)),
                          lambda i, e, s: (8 * s[i * held + e], 0))],
            pl.BlockSpec((tr, d), lambda i, e, s: (i, 0)),
            jax.ShapeDtypeStruct((T, d), jnp.float32))(start, row_slots, a_s)


@register("_contrib_flash_attention")
def _flash_attention_op(attrs, q, k, v):
    return flash_attention(q, k, v, causal=bool(attrs.get("causal", False)),
                           scale=attrs.get("scale"))


@register("_contrib_causal_attention", no_jit=True, shape_rule="input",
          dtype_rule="input")
def _causal_attention_op(attrs, q, k, v):
    """Causal attention at the default matmul precision (``causal_attention``);
    optional attr ``scale``."""
    return causal_attention(q, k, v, scale=attrs.get("scale"))


@register("_contrib_window_attention", no_jit=True, shape_rule="input",
          dtype_rule="input")
def _window_attention_op(attrs, q, k, v):
    """Sliding-window attention at the default matmul precision
    (``window_attention``): attr ``window`` (keys a query sees, itself
    included), optional ``scale``."""
    return window_attention(q, k, v, int(attrs["window"]),
                            scale=attrs.get("scale"))


@register("_contrib_block_mask_attention", no_jit=True, shape_rule="input",
          dtype_rule="input")
def _block_mask_attention_op(attrs, q, k, v):
    """Block-diffusion attention over ``[noised; clean]`` rows: attrs
    ``seq_len`` (clean positions), ``block_length``, optional ``scale``."""
    return block_mask_attention(q, k, v, int(attrs["seq_len"]),
                                int(attrs["block_length"]),
                                scale=attrs.get("scale"))


@register("_contrib_sparse_attention", num_outputs=2, no_jit=True,
          shape_rule="input", dtype_rule="input")
def _sparse_attention_op(attrs, q, k, v, pairs):
    """Attention over the pairs ``pairs`` (B, T, T) holds nonzero: outputs
    the attention's output and the rows' log-sum-exp; optional attr
    ``scale``."""
    return sparse_attention(q, k, v, pairs, scale=attrs.get("scale"))
