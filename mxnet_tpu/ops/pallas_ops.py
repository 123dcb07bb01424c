"""Pallas TPU kernels for the hot ops XLA fusion can't produce by itself.

Reference counterpart: the CUDA kernels under src/operator/ (and the
transformer attention helpers in src/operator/contrib/transformer.cc).  Here
the accelerator kernels are Pallas: tiled flash attention with the streaming
log-sum-exp softmax, keeping the working set in VMEM and the QK^T / PV matmuls
on the MXU.

On a TPU backend the entry points run the kernel, and a kernel the compiler
refuses is an error the caller sees.  Elsewhere they run the dense XLA
reference (also the vjp path): the Pallas TPU lowering exists only for TPUs.
"""
from __future__ import annotations

import numpy as _np

from .registry import register


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def _causal_offset(causal, Tq, Tk):
    """Key-position offset of the causal diagonal: query i attends keys
    j <= i + offset.  'top' aligns query 0 with key 0 (offset 0); 'bottom'
    is the KV-cache decode convention (the last query sees every key,
    offset Tk - Tq).  The two coincide when Tq == Tk."""
    return Tk - Tq if causal == "bottom" else 0


def _attention_reference(q, k, v, causal, scale):
    import jax
    import jax.numpy as jnp
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        Tq, Tk = q.shape[2], k.shape[2]
        off = _causal_offset(causal, Tq, Tk)
        mask = (jnp.arange(Tk)[None, :] <= jnp.arange(Tq)[:, None] + off)
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def _flash_attention_pallas(q, k, v, causal, scale, block_q=256, block_k=512,
                            interpret=False):
    """Tiled attention: grid over (batch*heads, q blocks, k blocks).  K/V
    stream through VMEM one ``(block_k, D)`` block per grid step while the
    online-softmax state (running max, normalizer, accumulator) lives in
    VMEM scratch across the k steps of one q block.  VMEM use is therefore
    bounded by the block sizes, never by the sequence length.

    Ragged sequence lengths are handled by padding q/k/v up to the tile
    size and masking the padded key columns to -inf inside the kernel (the
    padded query rows compute garbage that is sliced off afterwards) — so
    T % block != 0 workloads stay on the fused path."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, T, D = q.shape
    Tk = k.shape[2]
    block_q = min(block_q, T)
    block_k = min(block_k, Tk)
    pad_q = -T % block_q
    pad_k = -Tk % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    Tq_t, Tk_t = T + pad_q, Tk + pad_k
    n_k_blocks = Tk_t // block_k
    k_tail = bool(pad_k)  # static: tail masking compiled in only if needed
    c_off = _causal_offset(causal, T, Tk)  # offsets use UNPADDED lengths

    def last_k_block(qi):
        """Last k block the causal q block ``qi`` can see."""
        return jnp.minimum(((qi + 1) * block_q - 1 + c_off) // block_k,
                           n_k_blocks - 1)

    def kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
        qi = pl.program_id(1)
        ki = pl.program_id(2)

        @pl.when(ki == 0)
        def _():
            m_ref[...] = jnp.full(m_ref.shape, -1e30, jnp.float32)
            l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        def accumulate():
            q_blk = q_ref[...].astype(jnp.float32) * scale        # (bq, D)
            k_blk = k_ref[...].astype(jnp.float32)                # (bk, D)
            v_blk = v_ref[...].astype(jnp.float32)
            s = jax.lax.dot_general(                              # MXU
                q_blk, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)               # (bq, bk)
            if causal or k_tail:
                k_pos = ki * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                keep = jnp.ones_like(k_pos, dtype=bool)
                if causal:
                    q_pos = qi * block_q + jax.lax.broadcasted_iota(
                        jnp.int32, (block_q, block_k), 0)
                    keep &= q_pos + c_off >= k_pos
                if k_tail:
                    keep &= k_pos < Tk  # padded keys contribute nothing
                s = jnp.where(keep, s, -1e30)
            m_prev = m_ref[...]                                   # (bq, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1,
                                                      keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jnp.dot(        # MXU
                p, v_blk, preferred_element_type=jnp.float32)
            m_ref[...] = m_new

        if causal:
            # k blocks wholly above the diagonal contribute nothing
            pl.when(ki <= last_k_block(qi))(accumulate)
        else:
            accumulate()

        @pl.when(ki == n_k_blocks - 1)
        def _():
            o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)

    qf = q.reshape(B * H, Tq_t, D)
    kf = k.reshape(B * H, Tk_t, D)
    vf = v.reshape(B * H, Tk_t, D)

    # a causal q block re-names its last visible k block for the steps past
    # the diagonal: an unchanged block index is not fetched again
    kv_spec = pl.BlockSpec(
        (None, block_k, D),
        (lambda b, i, j: (b, jnp.minimum(j, last_k_block(i)), 0)) if causal
        else (lambda b, i, j: (b, j, 0)))
    out = pl.pallas_call(
        kernel,
        grid=(B * H, Tq_t // block_q, n_k_blocks),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0)),
            kv_spec, kv_spec,
        ],
        out_specs=pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Tq_t, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    out = out.reshape(B, H, Tq_t, D)
    return out[:, :, :T] if pad_q else out


def flash_attention(q, k, v, causal=False, scale=None, interpret=None):
    """Fused attention entry: Pallas kernel on TPU, XLA reference elsewhere.
    ``interpret`` (tests only) forces the kernel, interpreted or compiled.

    q/k/v: (B, H, T, D).  Differentiable: custom_vjp with the reference
    backward (recompute-based, XLA-fused).

    ``causal`` may be False, True, 'top', or 'bottom'.  With mismatched q/k
    lengths the diagonal's alignment is ambiguous, so bare ``True`` refuses
    and the caller must say which convention they mean: 'top' aligns query 0
    with key 0; 'bottom' is the KV-cache decode convention (the last query
    sees every key) — e.g. ``causal='bottom'`` for T=1, Tk=n decode."""
    import jax
    import jax.numpy as jnp

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
    use_pallas = interpret is not None or jax.default_backend() == "tpu"

    @jax.custom_vjp
    def f(q_, k_, v_):
        # ragged lengths stay on the fused path: the kernel pads to tile
        # multiples and masks the tail keys itself
        if use_pallas:
            return _flash_attention_pallas(q_, k_, v_, causal, scale,
                                           interpret=bool(interpret))
        return _attention_reference(q_, k_, v_, causal, scale)

    def f_fwd(q_, k_, v_):
        return f(q_, k_, v_), (q_, k_, v_)

    def f_bwd(res, g):
        q_, k_, v_ = res
        _, vjp = jax.vjp(lambda a, b, c: _attention_reference(a, b, c, causal,
                                                              scale), q_, k_, v_)
        return vjp(g)

    f.defvjp(f_fwd, f_bwd)
    return f(q, k, v)


@register("_contrib_flash_attention")
def _flash_attention_op(attrs, q, k, v):
    return flash_attention(q, k, v, causal=bool(attrs.get("causal", False)),
                           scale=attrs.get("scale"))
