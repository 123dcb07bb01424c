"""Layers of a decoder language model trained by block diffusion over
mixture-of-experts feed-forwards: weighted RMSNorm, grouped-query attention
with query/key norm and rotary positions under the block-diffusion mask, the
mixture-of-experts layer for the experts this chip holds, and the decoder
layer that joins them.  gluon/model_zoo/block_diffusion.py builds a model of
them from a configuration.
"""
from __future__ import annotations

from ... import autograd
from ... import profiler
from ...ndarray import NDArray
from ..block import HybridBlock
from .basic_layers import Dense

__all__ = ["RMSNorm", "BlockDiffusionAttention", "HeldExpertsMoE",
           "BlockDiffusionDecoderLayer"]


class RMSNorm(HybridBlock):
    """``x / sqrt(mean(x^2) + eps) * gamma`` over the last axis."""

    def __init__(self, units, epsilon=1e-6, **kwargs):
        super().__init__(**kwargs)
        self._eps = epsilon
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(units,), init="ones")

    def hybrid_forward(self, F, x, gamma):
        return F._contrib_rms_norm(x, gamma, eps=self._eps)


class BlockDiffusionAttention(HybridBlock):
    """Grouped-query attention over ``[noised; clean]`` rows: projections
    without bias, RMSNorm over each head's dimensions of q and of k, rotary
    embedding on all of them at the positions given (a noised row has its
    clean twin's), scale ``1 / sqrt(head_dim)``, softmax over the keys that
    ``ops.pallas_ops.block_diffusion_mask`` allows.  Inputs: ``x``
    (B, 2L, hidden) and ``positions`` (2L,)."""

    def __init__(self, hidden, heads, kv_heads, head_dim, block_length,
                 rope_base=1e6, epsilon=1e-6, **kwargs):
        super().__init__(**kwargs)
        self._heads, self._kv_heads, self._head_dim = heads, kv_heads, head_dim
        self._block_length, self._rope_base = block_length, rope_base
        with self.name_scope():
            def proj(units, in_units, prefix):
                return Dense(units, in_units=in_units, use_bias=False,
                             flatten=False, prefix=prefix)
            self.q = proj(heads * head_dim, hidden, "q_")
            self.k = proj(kv_heads * head_dim, hidden, "k_")
            self.v = proj(kv_heads * head_dim, hidden, "v_")
            self.o = proj(hidden, heads * head_dim, "o_")
            self.q_norm = RMSNorm(head_dim, epsilon, prefix="q_norm_")
            self.k_norm = RMSNorm(head_dim, epsilon, prefix="k_norm_")

    def hybrid_forward(self, F, x, positions):
        def heads(t, count, norm=None):
            t = F.reshape(t, shape=(0, 0, count, self._head_dim))
            if norm is not None:
                t = norm(t)
            t = F.transpose(t, axes=(0, 2, 1, 3))            # (B, H, 2L, D)
            if norm is not None:
                t = F._contrib_rotary_embedding(t, positions,
                                                base=self._rope_base)
            return t

        q = heads(self.q(x), self._heads, self.q_norm)
        k = heads(self.k(x), self._kv_heads, self.k_norm)
        v = heads(self.v(x), self._kv_heads)
        out = F._contrib_block_mask_attention(
            q, k, v, seq_len=x.shape[1] // 2, block_length=self._block_length)
        out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)), shape=(0, 0, -1))
        return self.o(out)


class HeldExpertsMoE(HybridBlock):
    """The part of a mixture-of-experts layer that the experts held on this
    chip give (parallel/moe.py ``moe_held_apply``): the router scores all
    ``experts_total`` experts, takes ``experts_per_token`` with renormalised
    weights, and experts ``first_expert .. first_expert + experts_held - 1``
    compute ``down(silu(gate y) * up y)`` for the tokens routed to them.  No
    token is dropped.  The held experts' matrices are stacked in 2-D leaves,
    ``(experts_held * width, hidden)`` and ``(experts_held * hidden,
    width)``.

    ``load`` (non-trainable state, written by every training step) holds the
    newest step's ``[pairs routed here, largest held expert's load]``;
    ``profiler.totals()`` reads it, when asked, under
    ``moe.load.<this block's prefix>`` (``count`` and ``max``)."""

    def __init__(self, hidden, width, experts_total, experts_per_token,
                 experts_held, first_expert=0, **kwargs):
        super().__init__(**kwargs)
        self._attrs = {"experts_per_token": experts_per_token,
                       "expert_width": width, "first_expert": first_expert}
        with self.name_scope():
            get = self.params.get
            self.router_weight = get("router_weight",
                                     shape=(experts_total, hidden))
            self.gate_weight = get("gate_weight",
                                   shape=(experts_held * width, hidden))
            self.up_weight = get("up_weight",
                                 shape=(experts_held * width, hidden))
            self.down_weight = get("down_weight",
                                   shape=(experts_held * hidden, width))
            self.load = get("load", shape=(2,), init="zeros",
                            grad_req="null", differentiable=False)
        # the gauge keeps this leaf (two numbers) alive, not the block: a
        # reader asks for the load after the step and its network are freed
        state = self.load
        profiler.gauge("moe.load." + self.prefix,
                       lambda: tuple(float(v) for v in state.data().asnumpy()))

    def hybrid_forward(self, F, x, router_weight, gate_weight, up_weight,
                       down_weight, load):
        out, now = F._contrib_moe_held_experts(
            x, router_weight, gate_weight, up_weight, down_weight,
            **self._attrs)
        if autograd.is_training() and isinstance(out, NDArray):
            load._set_data(now._data)
        return out


class BlockDiffusionDecoderLayer(HybridBlock):
    """``h = x + Attn(RMSNorm(x)); x' = h + MoE(RMSNorm(h))``."""

    def __init__(self, hidden, heads, kv_heads, head_dim, block_length,
                 width, experts_total, experts_per_token, experts_held,
                 first_expert=0, rope_base=1e6, epsilon=1e-6, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.attn_norm = RMSNorm(hidden, epsilon, prefix="attn_norm_")
            self.attn = BlockDiffusionAttention(
                hidden, heads, kv_heads, head_dim, block_length, rope_base,
                epsilon, prefix="attn_")
            self.moe_norm = RMSNorm(hidden, epsilon, prefix="moe_norm_")
            self.moe = HeldExpertsMoE(hidden, width, experts_total,
                                      experts_per_token, experts_held,
                                      first_expert, prefix="moe_")

    def hybrid_forward(self, F, x, positions):
        h = x + self.attn(self.attn_norm(x), positions)
        return h + self.moe(self.moe_norm(h))
