"""Operators of a decoder language model's layers that ``nn_ops`` lacks:
weighted RMSNorm, rotary position embedding with the positions as an input,
and the operator of the mixture-of-experts layer for the experts held on this
chip (the layer itself is parallel/moe.py's, imported when the operator runs:
``mx.nd`` installs its operators before ``parallel`` is imported, so an
operator registered there would not be found).  Block-mask attention is in
pallas_ops.py.
"""
from __future__ import annotations

from .registry import register


@register("_contrib_rms_norm")
def _rms_norm(attrs, x, gamma):
    """``x / sqrt(mean(x^2, last axis) + eps) * gamma``, the mean taken in
    float32."""
    import jax
    import jax.numpy as jnp
    eps = float(attrs.get("eps", 1e-6))
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * inv).astype(x.dtype) * gamma.astype(x.dtype)


@register("_contrib_rotary_embedding")
def _rotary_embedding(attrs, x, positions):
    """Rotary position embedding over all of the last axis (rotate-half
    pairing: dimension i with i + D/2).  ``x``: (..., T, D); ``positions``:
    (T,) integers, given by the caller, so that two rows may share one."""
    import jax.numpy as jnp
    base = float(attrs.get("base", 10000.0))
    half = x.shape[-1] // 2
    inv_freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)                 # (T, D/2)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


@register("_contrib_moe_held_experts", num_outputs=2, no_jit=True,
          shape_rule="input", dtype_rule="input")
def _moe_held_experts(attrs, x, router_w, gate_w, up_w, down_w):
    """``parallel.moe.moe_held_apply`` as an operator.  ``x``: (..., d);
    ``router_w``: (E, d) over all experts; ``gate_w``, ``up_w``:
    (E_held * f, d) and ``down_w``: (E_held * d, f), the held experts'
    matrices stacked along the first axis.  attrs: ``experts_per_token``,
    ``expert_width`` (f), ``first_expert``.  Outputs: the layer's output,
    shaped as ``x``, and float32 ``[pairs routed here, largest load]``."""
    from ..parallel.moe import moe_held_apply
    d, f = x.shape[-1], int(attrs["expert_width"])
    out, load = moe_held_apply(
        x.reshape(-1, d), router_w, gate_w.reshape(-1, f, d),
        up_w.reshape(-1, f, d), down_w.reshape(-1, d, f),
        int(attrs["experts_per_token"]),
        first_expert=int(attrs.get("first_expert", 0)))
    return out.reshape(x.shape), load
