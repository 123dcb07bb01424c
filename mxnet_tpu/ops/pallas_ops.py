"""Pallas TPU kernels for the hot ops XLA fusion can't produce by itself.

Reference counterpart: the CUDA kernels under src/operator/ (and the
transformer attention helpers in src/operator/contrib/transformer.cc).  Here
the accelerator kernels are Pallas: tiled flash attention with the streaming
log-sum-exp softmax, keeping the working set in VMEM and the QK^T / PV matmuls
on the MXU, forward and backward.

One pair of kernels (the forward; one backward for the query, key and value
gradients) serves every static mask: none, causal ('top' / 'bottom' aligned)
and the block diffusion mask over ``[noised; clean]`` rows.  A mask is a
function of a row's and a column's index; from it the wrapper works out on
the host, per query tile, which key tiles hold a visible pair (the others
are never visited: no DMA, no MXU pass) and which are wholly visible (no
masking).  Query heads may outnumber key/value heads (grouped-query
attention): the kernels index the shared key/value head, and the key/value
gradient sums over the group inside the kernel.

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
# what the attention call names for jax.checkpoint policies: the forward
# kernel's output and log-sum-exp, the two residuals only it can produce
ATTENTION_RESIDUALS = ("attn.out", "attn.lse")


# ---------------------------------------------------------------------------
# static masks
# ---------------------------------------------------------------------------
# A mask is a hashable tuple: ("none",), ("causal", offset) or
# ("block_diffusion", L, block_length).

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
    _, L, bl = mask
    q_clean, k_clean = q_pos >= L, k_pos >= L
    qb = (q_pos - L * q_clean) // bl
    kb = (k_pos - L * k_clean) // bl
    return ((~q_clean) & (~k_clean) & (qb == kb)) \
        | ((~q_clean) & k_clean & (kb < qb)) \
        | (q_clean & k_clean & (kb <= qb))


@functools.lru_cache(maxsize=64)
def _tile_tables(mask, Tq, Tk, n_q, n_k, block_q, block_k):
    """Which tiles the mask leaves something in, found on the host.

    Returns ``(k_of_q, q_of_k)``, each ``(index, flag, slots)``: for every
    query tile the key tiles to visit (and the other way round for the
    key/value gradient), padded to ``slots`` a row by repeating the last
    one with flag 0, so that a padded step fetches nothing new.  Flag 1: the
    tile is partly visible and is masked from its indices; 2: wholly
    visible.  Rows and columns past ``Tq`` / ``Tk`` are padding: a padded
    column is never visible, a padded row is no reason to visit a tile."""
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

    def table(state):
        slots = max(1, int((state > 0).sum(axis=1).max()))
        index = _np.zeros((state.shape[0], slots), _np.int32)
        flag = _np.zeros((state.shape[0], slots), _np.int32)
        for row in range(state.shape[0]):
            found = _np.nonzero(state[row])[0]
            index[row, :len(found)] = found
            flag[row, :len(found)] = state[row, found]
            if len(found):
                index[row, len(found):] = found[-1]
        return index.reshape(-1), flag.reshape(-1), slots

    return table(state), table(state.T)


def _attention_reference(q, k, v, causal, scale, mask=None):
    """Dense XLA attention: every score materialised.  ``causal`` is the
    flash_attention argument; ``mask`` (a mask tuple) overrides it.  Query
    heads may be a multiple of the key/value heads."""
    import jax
    import jax.numpy as jnp
    B, Hq, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    if mask is None:
        mask = _causal_mask(causal, Tq, Tk)
    qg = q.reshape(B, Hkv, Hq // Hkv, Tq, D)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k).astype(jnp.float32) * scale
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
        self.k_of_q, _ = _tile_tables(
            mask, Tq, Tk, self.n_q, self.n_k, self.block_q, self.block_k)

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
        """One kernel's grid in the recorder: tiles of the whole square
        and tiles visited, over all batch rows and query heads."""
        heads = self.B * self.Hq
        profiler.count("attn.tiles_total", heads * self.n_q * self.n_k)
        profiler.count("attn.tiles_visited",
                       heads * int((self.k_of_q[1] > 0).sum()))


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


def _attention_fwd_pallas(plan, q, k, v):
    """(out, lse): grid over (batch * query heads, query tiles, visited key
    tiles).  K/V stream through VMEM one ``(block_k, D)`` tile per step
    while the online-softmax state (running max, normaliser, accumulator)
    lives in VMEM scratch across the steps of one query tile, so VMEM use is
    bounded by the tile sizes, never by the sequence length.  Ragged lengths
    are padded up to the tile size; padded key columns are masked and padded
    query rows are sliced off."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    p = plan
    bq, bk, D, G = p.block_q, p.block_k, p.D, p.G
    _, _, S = p.k_of_q
    cdt = p.mxu_dtype

    def kernel(kidx_ref, flag_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
               m_ref, l_ref, acc_ref):
        qi, si = pl.program_id(1), pl.program_id(2)
        at = qi * S + si
        flag = flag_ref[at]

        @pl.when(si == 0)
        def _():
            m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
            l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        def accumulate(masked):
            s = _nt(q_ref[...].astype(cdt), k_ref[...].astype(cdt)) * p.scale
            if masked:
                keep = p.keep(qi, kidx_ref[at])
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

        pl.when(flag == 1)(lambda: accumulate(True))
        pl.when(flag == 2)(lambda: accumulate(False))

        @pl.when(si == S - 1)
        def _():
            l = jnp.maximum(l_ref[...], 1e-30)
            o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
            lse_ref[...] = m_ref[...] + jnp.log(l)

    BH = p.B * p.Hq
    qf = _pad_rows(q, p.pad_q).reshape(BH, p.n_q * bq, D)
    kf = _pad_rows(k, p.pad_k).reshape(p.B * p.Hkv, p.n_k * bk, D)
    vf = _pad_rows(v, p.pad_k).reshape(p.B * p.Hkv, p.n_k * bk, D)
    q_spec = pl.BlockSpec((None, bq, D), lambda b, i, j, kidx, flag: (b, i, 0))
    kv_spec = pl.BlockSpec(
        (None, bk, D), lambda b, i, j, kidx, flag: (b // G, kidx[i * S + j], 0))
    p.count_tiles()
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(BH, p.n_q, S),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=[q_spec, pl.BlockSpec(
                (None, bq, 1), lambda b, i, j, kidx, flag: (b, i, 0))],
            scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, D), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((BH, p.n_q * bq, D), q.dtype),
                   jax.ShapeDtypeStruct((BH, p.n_q * bq, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=p.interpret, name="attention_fwd",
    )(jnp.asarray(p.k_of_q[0]), jnp.asarray(p.k_of_q[1]), qf, kf, vf)
    return out, lse


def _attention_bwd_pallas(plan, q, k, v, out, lse, g):
    """(dq, dk, dv) from one kernel, scores recomputed tile by tile from the
    forward's log-sum-exp (``lse`` as the forward call keeps it, (B * H,
    padded T)).  The grid is the forward's: (batch * query heads, query
    tiles, visited key tiles).  Every visited tile is computed once, key
    rows by query columns, so that the per-row statistics enter as
    lane-dense rows and the two key-side products take the tile as it
    stands: scores, exponentials, mask (partly visible tiles only), dP and
    dS, then ``dv += P^T dO``, ``dk += dS^T q`` and ``dq^T += k^T dS``: no
    product turns a tile.

    dq of a query tile adds up over its consecutive steps in scratch,
    transposed, and is turned once a query tile.  dk and dv are revisited
    out of order, so one key/value head's whole (padded Tk, D) float32
    gradients are the output blocks: they stay in VMEM over the head's
    ``G`` consecutive query heads, are zeroed at the group's first step and
    scaled at its last, and each tile adds its (block_k, D) rows in place.
    VMEM therefore grows with the key length (4 MiB a gradient at 8,192 x
    128, twice for the pipeline's second buffer); nothing in HBM grows
    with tiles x heads."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    p = plan
    bq, bk, D, G = p.block_q, p.block_k, p.D, p.G
    _, _, S = p.k_of_q
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

    def kernel(kidx_ref, flag_ref, q_ref, g_ref, lse_ref, delta_ref, k_ref,
               kt_ref, v_ref, dq_ref, dk_ref, dv_ref, dqt_acc):
        b, qi, si = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        at = qi * S + si
        flag = flag_ref[at]

        @pl.when((b % G == 0) & (qi == 0) & (si == 0))
        def _():
            dk_ref[...] = jnp.zeros(dk_ref.shape, jnp.float32)
            dv_ref[...] = jnp.zeros(dv_ref.shape, jnp.float32)

        @pl.when(si == 0)
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
                et = jnp.where(p.keep(qi, kidx, transposed=True), et, 0.0)
            dv_ref[rows, :] += jnp.dot(et.astype(cdt), g_blk,
                                       preferred_element_type=jnp.float32)
            dpt = _nt(v_ref[...].astype(cdt), g_blk)
            dst = (et * (dpt - delta_ref[...])).astype(cdt)
            dk_ref[rows, :] += jnp.dot(dst, q_blk,
                                       preferred_element_type=jnp.float32)
            dqt_acc[...] += jnp.dot(kt_ref[...].astype(cdt), dst,
                                    preferred_element_type=jnp.float32)

        pl.when(flag == 1)(lambda: accumulate(True))
        pl.when(flag == 2)(lambda: accumulate(False))

        @pl.when(si == S - 1)
        def _():
            dq_ref[...] = (dqt_acc[...].T * p.scale).astype(dq_ref.dtype)

        @pl.when((b % G == G - 1) & (qi == p.n_q - 1) & (si == S - 1))
        def _():
            dk_ref[...] *= p.scale

    def q_side(b, i, j, kidx, flag):
        return (b, i, 0)

    def row_side(b, i, j, kidx, flag):
        return (b, 0, i)

    def k_side(b, i, j, kidx, flag):
        return (b // G, kidx[i * S + j], 0)

    def kt_side(b, i, j, kidx, flag):
        return (b // G, 0, kidx[i * S + j])

    q_spec = pl.BlockSpec((None, bq, D), q_side)
    row_spec = pl.BlockSpec((None, 1, bq), row_side)
    k_spec = pl.BlockSpec((None, bk, D), k_side)
    held_spec = pl.BlockSpec((None, Tk_t, D),
                             lambda b, i, j, kidx, flag: (b // G, 0, 0))
    held = jax.ShapeDtypeStruct((BHkv, Tk_t, D), jnp.float32)
    # dk and dv whole, each with the pipeline's second buffer, beside the 16
    # MiB a kernel has by default for its tiles and temporaries; past 100 of
    # a v5e core's 128 MiB the compiler refuses the call, and says so
    vmem_bytes = min(16 * 2 ** 20 + 2 * 2 * Tk_t * D * 4, 100 * 2 ** 20)
    p.count_tiles()
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(BH, p.n_q, S),
            in_specs=[q_spec, q_spec, row_spec, row_spec, k_spec,
                      pl.BlockSpec((None, D, bk), kt_side), k_spec],
            out_specs=[q_spec, held_spec, held_spec],
            scratch_shapes=[pltpu.VMEM((D, bq), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((BH, Tq_t, D), q.dtype), held, held],
        # every axis in order: dk and dv are added to across all three
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_bytes),
        interpret=p.interpret, name="attention_bwd",
    )(jnp.asarray(p.k_of_q[0]), jnp.asarray(p.k_of_q[1]), qf, gf, lse, delta,
      kf, kt, vf)

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
    sees every key) — e.g. ``causal='bottom'`` for T=1, Tk=n decode."""
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


@register("_contrib_flash_attention")
def _flash_attention_op(attrs, q, k, v):
    return flash_attention(q, k, v, causal=bool(attrs.get("causal", False)),
                           scale=attrs.get("scale"))


@register("_contrib_block_mask_attention", no_jit=True, shape_rule="input",
          dtype_rule="input")
def _block_mask_attention_op(attrs, q, k, v):
    """Block-diffusion attention over ``[noised; clean]`` rows: attrs
    ``seq_len`` (clean positions), ``block_length``, optional ``scale``."""
    return block_mask_attention(q, k, v, int(attrs["seq_len"]),
                                int(attrs["block_length"]),
                                scale=attrs.get("scale"))
