"""Layers of decoder language models: weighted RMSNorm, grouped-query
attention with or without query/key norm and rotary positions (causal,
causal over a sliding window of keys, under the block-diffusion mask, or
causal over the keys a learned indexer picks for each query; the rotary's
default frequencies or YaRN's), a gated short convolution along the
sequence, a Mamba-2 mixer (a state-space scan between its projections), a
dense gated feed-forward and a dense relu² one, the mixture-of-experts layer
for the experts this chip holds (softmax or sigmoid router, SiLU-gated or
relu² experts, an optional shared expert), and the decoder layers that join
them: attention over experts (two forms), a layer whose operator and
feed-forward are chosen per layer, and a layer of one block.
gluon/model_zoo/block_diffusion.py, sparse_causal_lm.py, short_conv_lm.py,
window_moe_lm.py and hybrid_ssm_lm.py build models of them from a
configuration.
"""
from __future__ import annotations

from ... import autograd
from ... import profiler
from ...ndarray import NDArray
from ..block import HybridBlock
from .basic_layers import Dense

__all__ = ["RMSNorm", "BlockDiffusionAttention", "HeldExpertsMoE",
           "BlockDiffusionDecoderLayer", "IndexedSparseAttention",
           "SparseDecoderLayer", "CausalAttention", "GatedShortConv",
           "Mamba2Mixer", "GatedMLP", "ReluSquaredMLP", "DecoderLayer",
           "ResidualLayer"]


class RMSNorm(HybridBlock):
    """``x / sqrt(mean(x^2) + eps) * gamma`` over the last axis."""

    def __init__(self, units, epsilon=1e-6, **kwargs):
        super().__init__(**kwargs)
        self._eps = epsilon
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(units,), init="ones")

    def hybrid_forward(self, F, x, gamma):
        return F._contrib_rms_norm(x, gamma, eps=self._eps)


class _GroupedQueryAttention(HybridBlock):
    """What the attention blocks share: q, k, v and o projections without
    bias, ``heads`` query heads to ``kv_heads`` key/value heads of
    ``head_dim``, with ``qk_norm`` RMSNorm over each head's dimensions of q
    and of k, and a rotary embedding (each block's own form, or none) of q
    and k, all under the scope ``attn.proj``; the blocks differ in the mask
    of their kernels."""

    def __init__(self, hidden, heads, kv_heads, head_dim, epsilon,
                 qk_norm=True, **kwargs):
        super().__init__(**kwargs)
        self._heads, self._kv_heads, self._head_dim = heads, kv_heads, head_dim
        with self.name_scope():
            self.q = self._proj(heads * head_dim, hidden, "q_")
            self.k = self._proj(kv_heads * head_dim, hidden, "k_")
            self.v = self._proj(kv_heads * head_dim, hidden, "v_")
            self.o = self._proj(hidden, heads * head_dim, "o_")
            self.q_norm = self.k_norm = None
            if qk_norm:
                self.q_norm = RMSNorm(head_dim, epsilon, prefix="q_norm_")
                self.k_norm = RMSNorm(head_dim, epsilon, prefix="k_norm_")

    @staticmethod
    def _proj(units, in_units, prefix):
        return Dense(units, in_units=in_units, use_bias=False, flatten=False,
                     prefix=prefix)

    @staticmethod
    def _split(F, t, count, dim, norm=None):
        """(B, T, count * dim) as (B, count, T, dim), each head normed."""
        t = F.reshape(t, shape=(0, 0, count, dim))
        if norm is not None:
            t = norm(t)
        return F.transpose(t, axes=(0, 2, 1, 3))

    def _qkv(self, F, x, rotary):
        """The three operands of the attention call, (B, H, T, D).  With
        ``_output`` under the scope ``attn.proj``: what an attention block
        costs around its kernels."""
        import jax
        with jax.named_scope("attn.proj"):
            q = rotary(self._split(F, self.q(x), self._heads, self._head_dim,
                                   self.q_norm))
            k = rotary(self._split(F, self.k(x), self._kv_heads,
                                   self._head_dim, self.k_norm))
            return q, k, self._split(F, self.v(x), self._kv_heads,
                                     self._head_dim)

    def _output(self, F, out):
        """The attention's (B, H, T, D) through the output projection."""
        import jax
        with jax.named_scope("attn.proj"):
            return self.o(F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                                    shape=(0, 0, -1)))


class BlockDiffusionAttention(_GroupedQueryAttention):
    """Grouped-query attention over ``[noised; clean]`` rows: projections
    without bias, RMSNorm over each head's dimensions of q and of k, rotary
    embedding on all of them at the positions given (a noised row has its
    clean twin's), scale ``1 / sqrt(head_dim)``, softmax over the keys that
    ``ops.pallas_ops.block_diffusion_mask`` allows.  Inputs: ``x``
    (B, 2L, hidden) and ``positions`` (2L,)."""

    def __init__(self, hidden, heads, kv_heads, head_dim, block_length,
                 rope_base=1e6, epsilon=1e-6, **kwargs):
        super().__init__(hidden, heads, kv_heads, head_dim, epsilon, **kwargs)
        self._block_length, self._rope_base = block_length, rope_base

    def hybrid_forward(self, F, x, positions):
        q, k, v = self._qkv(F, x, lambda t: F._contrib_rotary_embedding(
            t, positions, base=self._rope_base))
        return self._output(F, F._contrib_block_mask_attention(
            q, k, v, seq_len=x.shape[1] // 2, block_length=self._block_length))


class HeldExpertsMoE(HybridBlock):
    """The part of a mixture-of-experts layer that the experts held on this
    chip give (parallel/moe.py ``moe_held_apply``): the router scores all
    ``experts_total`` experts, takes ``experts_per_token`` with their weights
    normalised over the picked ones, and experts ``first_expert ..
    first_expert + experts_held - 1`` compute, for the tokens routed to
    them, ``down(silu(gate y) * up y)`` (``act`` "swiglu") or
    ``down(relu(up y)^2)`` (``act`` "relu2", no gate leaf).  No token is
    dropped: a row's pairs are sorted by expert into a table of ``rows x
    min(experts_per_token, experts_held)`` slots and a tile of alignment an
    expert, which holds
    every pair of every routing, and grouped products run over its tiles
    (every slot every step, an empty one with weight 0: a step's time does
    not follow the routing).  The held experts' matrices are stacked in 2-D
    leaves, ``(experts_held * width, hidden)`` and ``(experts_held * hidden,
    width)``.

    The router's form is a property of the model: ``scoring`` "softmax" (the
    largest probabilities, renormalised) or "sigmoid" (each expert's own
    score), the weights times ``scale``, and with ``selection_bias`` a leaf
    ``expert_bias`` (experts_total,) added to the sigmoid scores for the
    selection alone: the weights are the unbiased scores', so its gradient
    is exactly 0 and a training step leaves it where the checkpoint had it.
    ``router_eps``: what the sigmoid router adds under its normalisation
    (default ``parallel.moe.SIGMOID_NORM_EPS``).

    ``shared_width``: a shared expert beside the held ones, a
    ``ReluSquaredMLP`` of that width (child ``shared``) over every row,
    computed whole on every chip of the layer alike and added to the held
    experts' part, under the scope ``mlp.shared``.

    ``load`` (non-trainable state, written by every training step) holds the
    newest step's ``[pairs routed here, largest held expert's load]``;
    ``profiler.totals()`` reads it, when asked, under
    ``moe.load.<this block's prefix>`` (``count`` and ``max``)."""

    def __init__(self, hidden, width, experts_total, experts_per_token,
                 experts_held, first_expert=0, scoring="softmax", scale=1.0,
                 selection_bias=False, act="swiglu", router_eps=None,
                 shared_width=None, **kwargs):
        super().__init__(**kwargs)
        self._attrs = {"experts_per_token": experts_per_token,
                       "expert_width": width, "first_expert": first_expert,
                       "scoring": scoring, "scale": scale}
        if act != "swiglu":
            self._attrs["act"] = act
        if router_eps is not None:
            self._attrs["router_eps"] = router_eps
        self.shared = None
        with self.name_scope():
            get = self.params.get
            if selection_bias:
                self.expert_bias = get("expert_bias", shape=(experts_total,),
                                       init="zeros")
            self.router_weight = get("router_weight",
                                     shape=(experts_total, hidden))
            if act == "swiglu":
                self.gate_weight = get("gate_weight",
                                       shape=(experts_held * width, hidden))
            self.up_weight = get("up_weight",
                                 shape=(experts_held * width, hidden))
            self.down_weight = get("down_weight",
                                   shape=(experts_held * hidden, width))
            self.load = get("load", shape=(2,), init="zeros",
                            grad_req="null", differentiable=False)
            if shared_width:
                self.shared = ReluSquaredMLP(hidden, shared_width,
                                             prefix="shared_")
        # the gauge keeps this leaf (two numbers) alive, not the block: a
        # reader asks for the load after the step and its network are freed
        state = self.load
        profiler.gauge("moe.load." + self.prefix,
                       lambda: tuple(float(v) for v in state.data().asnumpy()))

    def hybrid_forward(self, F, x, router_weight, up_weight, down_weight,
                       load, gate_weight=None, expert_bias=None):
        out, now = F._contrib_moe_held_experts(
            x, router_weight, gate_weight, up_weight, down_weight,
            expert_bias, **self._attrs)
        if autograd.is_training() and isinstance(out, NDArray):
            load._set_data(now._data)
        if self.shared is not None:
            out = out + self.shared(x)
        return out

    def route(self, F, x, router_weight, expert_bias=None):
        """What this layer's router picks for the rows ``x``, as the layer
        itself routes them: (weights, expert ids), each (rows,
        ``experts_per_token``); for a caller that checks the selection."""
        return F._contrib_moe_route(x, router_weight, expert_bias,
                                    **self._attrs)


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


class IndexedSparseAttention(_GroupedQueryAttention):
    """Causal grouped-query attention over the keys a learned indexer picks
    for each query (DeepSeek Sparse Attention).  The main path is
    ``BlockDiffusionAttention``'s (projections without bias, RMSNorm over
    each head's dimensions of q and k, rotary, scale ``1 / sqrt(head_dim)``)
    with the rotary frequencies in ``sections`` over three position rows.

    The indexer reads the block's input detached: ``index_heads`` queries of
    ``index_dim`` and one key of ``index_dim`` shared by them (LayerNorm, eps
    1e-6), rotary on all of both at position row 0, the heads' weights ``W_w
    u`` scaled by ``index_heads ** -0.5 * index_dim ** -0.5``; score
    ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``; the ``topk`` largest
    ``I[t, s <= t]`` are the keys query ``t`` attends, exactly
    (``ops.decoder_ops.select_top_k``).  Inputs: ``x`` (B, L, hidden) and
    ``positions`` (3, L).  Outputs: the block's rows; the indexer's loss
    (1,): the KL divergence from the attention's distribution over the
    picked keys (mean of the heads, detached) to the softmax of ``I`` over
    them, over ``B * L``; and the picked pairs (B, L, L) int8, for a caller
    that checks the selection (the decoder layer leaves them unused, and
    they are then never written out).  The indexer's parameters get a
    gradient from that loss alone, and nothing else gets one from it.

    ``index_loss`` (non-trainable state, written by every training step)
    holds the newest step's ``[1, the loss]``; ``profiler.totals()`` reads
    it, when asked, under ``dsa.index_loss.<this block's prefix>`` (``count``
    and ``max``): the step hands back one scalar, the language model's loss
    and every layer's indexer loss summed, so this is where whoever trains
    the model sees whether a layer's indexer follows its attention."""

    def __init__(self, hidden, heads, kv_heads, head_dim, index_heads,
                 index_dim, topk, rope_base=1e7, sections=None, epsilon=1e-6,
                 **kwargs):
        super().__init__(hidden, heads, kv_heads, head_dim, epsilon, **kwargs)
        self._index_heads, self._index_dim = index_heads, index_dim
        self._topk, self._rope_base = topk, rope_base
        self._sections = tuple(sections or (head_dim // 2, 0, 0))
        with self.name_scope():
            self.index_q = self._proj(index_heads * index_dim, hidden,
                                      "index_q_")
            self.index_k = self._proj(index_dim, hidden, "index_k_")
            self.index_w = self._proj(index_heads, hidden, "index_w_")
            get = self.params.get
            self.index_k_norm_gamma = get("index_k_norm_gamma",
                                          shape=(index_dim,), init="ones")
            self.index_k_norm_beta = get("index_k_norm_beta",
                                         shape=(index_dim,), init="zeros")
            self.index_loss = get("index_loss", shape=(2,), init="zeros",
                                  grad_req="null", differentiable=False)
        # as HeldExpertsMoE's load: the gauge keeps this leaf alive, not the
        # block
        state = self.index_loss
        profiler.gauge("dsa.index_loss." + self.prefix,
                       lambda: tuple(float(v) for v in state.data().asnumpy()))

    def hybrid_forward(self, F, x, positions, index_k_norm_gamma,
                       index_k_norm_beta, index_loss):
        q, k, v = self._qkv(F, x, lambda t: F._contrib_rotary_embedding(
            t, positions, base=self._rope_base, sections=self._sections))

        import jax
        with jax.named_scope("dsa.proj"):       # the indexer's operands
            u = F.stop_gradient(x)
            first_row = F.reshape(
                F.slice_axis(positions, axis=0, begin=0, end=1), shape=(-1,))
            index_q = F._contrib_rotary_embedding(
                self._split(F, self.index_q(u), self._index_heads,
                            self._index_dim),
                first_row, base=self._rope_base)
            index_k = F._contrib_rotary_embedding(
                F.LayerNorm(self.index_k(u), index_k_norm_gamma,
                            index_k_norm_beta, eps=1e-6),
                first_row, base=self._rope_base)
            weights = self.index_w(u) * (self._index_heads ** -0.5
                                         * self._index_dim ** -0.5)
        scores, pairs = F._contrib_index_select(index_q, index_k, weights,
                                                topk=self._topk)
        out, lse = F._contrib_sparse_attention(q, k, v, pairs)
        loss, out = F._contrib_index_loss(scores, pairs, q, k, lse, out)
        if autograd.is_training() and isinstance(out, NDArray):
            index_loss._set_data(F.concat(F.ones_like(loss), loss,
                                          dim=0)._data)
        return self._output(F, out), loss, pairs


class SparseDecoderLayer(HybridBlock):
    """``h = x + Attn(RMSNorm(x)); x' = h + MoE(RMSNorm(h))`` with
    ``IndexedSparseAttention``; gives ``(x', the indexer's loss)``."""

    def __init__(self, hidden, heads, kv_heads, head_dim, index_heads,
                 index_dim, topk, width, experts_total, experts_per_token,
                 experts_held, first_expert=0, rope_base=1e7, sections=None,
                 epsilon=1e-6, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.attn_norm = RMSNorm(hidden, epsilon, prefix="attn_norm_")
            self.attn = IndexedSparseAttention(
                hidden, heads, kv_heads, head_dim, index_heads, index_dim,
                topk, rope_base, sections, epsilon, prefix="attn_")
            self.moe_norm = RMSNorm(hidden, epsilon, prefix="moe_norm_")
            self.moe = HeldExpertsMoE(hidden, width, experts_total,
                                      experts_per_token, experts_held,
                                      first_expert, prefix="moe_")

    def hybrid_forward(self, F, x, positions):
        rows, loss, _ = self.attn(self.attn_norm(x), positions)
        h = x + rows
        return h + self.moe(self.moe_norm(h)), loss


class CausalAttention(_GroupedQueryAttention):
    """Causal grouped-query attention: ``BlockDiffusionAttention``'s block
    (projections without bias, RMSNorm over each head's dimensions of q and
    k, rotary on all of them, scale ``1 / sqrt(head_dim)``) under the causal
    mask, or with ``window`` under the sliding window of the ``window`` keys
    up to each query, itself included (``ops.pallas_ops.window_attention``),
    by the attention kernels at the default matmul precision.  ``rope``: the
    rotary embedding's attrs beside its base (``rope_type`` "yarn" and
    YaRN's parameters, as ``_contrib_rotary_embedding`` takes them; none for
    the default form).  A ``rope_base`` of None: no rotary; ``qk_norm``
    false: no norm of q and k.  Inputs: ``x`` (B, L, hidden) and
    ``positions`` (L,)."""

    def __init__(self, hidden, heads, kv_heads, head_dim, rope_base=1e6,
                 epsilon=1e-6, window=None, rope=None, qk_norm=True,
                 **kwargs):
        super().__init__(hidden, heads, kv_heads, head_dim, epsilon, qk_norm,
                         **kwargs)
        self._rope_base, self._window = rope_base, window
        self._rope = dict(rope or {})

    def hybrid_forward(self, F, x, positions):
        if self._rope_base is None:
            q, k, v = self._qkv(F, x, lambda t: t)
        else:
            q, k, v = self._qkv(F, x, lambda t: F._contrib_rotary_embedding(
                t, positions, base=self._rope_base, **self._rope))
        if self._window is None:
            return self._output(F, F._contrib_causal_attention(q, k, v))
        return self._output(F, F._contrib_window_attention(
            q, k, v, window=self._window))


class GatedShortConv(HybridBlock):
    """A gated short convolution along the sequence (LFM2's operator): the
    input projection gives three streams ``[Bg, Cg, X]`` of ``hidden``, a
    causal depthwise convolution of ``taps`` taps runs over ``Bg * X`` (one
    filter a channel, zeros before the sequence's first row), ``Cg`` gates
    its output, and the output projection follows; no bias.  Row ``t`` reads
    rows ``t - taps + 1 .. t`` of its own sequence.  Inputs: ``x`` (B, L,
    hidden) and, as every operator of a ``DecoderLayer``, the positions,
    which it does not read."""

    def __init__(self, hidden, taps=3, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.in_proj = Dense(3 * hidden, in_units=hidden, use_bias=False,
                                 flatten=False, prefix="in_")
            self.out_proj = Dense(hidden, in_units=hidden, use_bias=False,
                                  flatten=False, prefix="out_")
            self.taps_weight = self.params.get("taps_weight",
                                               shape=(hidden, taps))

    def hybrid_forward(self, F, x, positions=None, taps_weight=None):
        import jax
        with jax.named_scope("conv.proj"):      # the kernels: conv.gated
            streams = self.in_proj(x)
        mixed = F._contrib_gated_short_conv(streams, taps_weight)
        with jax.named_scope("conv.proj"):
            return self.out_proj(mixed)


class Mamba2Mixer(HybridBlock):
    """A Mamba-2 mixer (the released ``nemotron_h`` form): the input
    projection gives ``[z, xBC, dt]`` of widths ``heads * head_dim``,
    ``heads * head_dim + 2 * groups * state`` and ``heads``; ``xBC =
    silu(conv(xBC) + conv_bias)``, a causal depthwise convolution of
    ``taps`` taps (``_contrib_ssm_conv``, handed the projection's whole
    output and where ``xBC`` begins in it: on a TPU its kernel pair reads
    ``xBC``'s channels where they lie, and XLA's form, which runs elsewhere
    and at widths that are no multiple of 128, slices them first); the
    state-space scan of ``x``
    (``heads`` heads of ``head_dim``) under ``B`` and ``C`` (``groups``
    groups of ``state``) with steps ``softplus(dt + dt_bias)``, decay ``A =
    -exp(A_log)`` and skip ``D`` a head, in chunks of ``chunk`` rows
    (``_contrib_ssd_scan``); ``RMSNorm(y * silu(z))`` in ``groups`` groups
    of channels, weight ``norm_gamma`` (``_contrib_gated_rms_norm``); the
    output projection.  No bias but the convolution's.  Scopes ``ssm.proj``
    (both projections), ``ssm.conv``, ``ssm.scan`` and ``ssm.norm``.
    Inputs: ``x`` (B, L, hidden) and, as every operator of a layer, the
    positions, which it does not read."""

    def __init__(self, hidden, heads, head_dim, state, groups, taps=4,
                 chunk=128, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        inner = heads * head_dim
        self._inner, self._conv = inner, inner + 2 * groups * state
        self._scan = {"heads": heads, "groups": groups, "state": state,
                      "chunk": chunk}
        self._norm = {"groups": groups, "eps": epsilon}
        with self.name_scope():
            self.in_proj = Dense(inner + self._conv + heads, in_units=hidden,
                                 use_bias=False, flatten=False, prefix="in_")
            self.out_proj = Dense(hidden, in_units=inner, use_bias=False,
                                  flatten=False, prefix="out_")
            get = self.params.get
            self.conv_weight = get("conv_weight", shape=(self._conv, taps))
            self.conv_bias = get("conv_bias", shape=(self._conv,),
                                 init="zeros")
            self.dt_bias = get("dt_bias", shape=(heads,), init="zeros")
            self.A_log = get("A_log", shape=(heads,), init="zeros")
            self.D = get("D", shape=(heads,), init="ones")
            self.norm_gamma = get("norm_gamma", shape=(inner,), init="ones")

    def hybrid_forward(self, F, x, positions=None, conv_weight=None,
                       conv_bias=None, dt_bias=None, A_log=None, D=None,
                       norm_gamma=None):
        import jax
        inner, conv = self._inner, self._conv
        with jax.named_scope("ssm.proj"):
            streams = self.in_proj(x)
            z = F.slice_axis(streams, axis=-1, begin=0, end=inner)
            dt = F.slice_axis(streams, axis=-1, begin=inner + conv, end=None)
        xbc = F._contrib_ssm_conv(streams, conv_weight, conv_bias,
                                  begin=inner)
        y = F._contrib_ssd_scan(xbc, dt, dt_bias, A_log, D, **self._scan)
        y = F._contrib_gated_rms_norm(y, z, norm_gamma, **self._norm)
        with jax.named_scope("ssm.proj"):
            return self.out_proj(y)


class GatedMLP(HybridBlock):
    """A dense gated feed-forward, ``down(silu(gate u) * up u)`` of width
    ``width``, no bias: the operator ``_contrib_gated_mlp``, whose backward
    pass feeds its five products from operands written once.  Weights
    ``gate_weight``, ``up_weight`` (width, hidden) and ``down_weight``
    (hidden, width): the reference's names and a ``Dense``'s layout."""

    def __init__(self, hidden, width, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.gate_weight, self.up_weight, self.down_weight = (
                self.params.get(name, shape=shape)
                for name, shape in (("gate_weight", (width, hidden)),
                                    ("up_weight", (width, hidden)),
                                    ("down_weight", (hidden, width))))

    def hybrid_forward(self, F, x, gate_weight, up_weight, down_weight):
        import jax
        with jax.named_scope("mlp.dense"):
            return F._contrib_gated_mlp(x, gate_weight, up_weight,
                                        down_weight)


class ReluSquaredMLP(HybridBlock):
    """A dense feed-forward without gate or bias, ``down(relu(up u)^2)`` of
    width ``width`` (``_contrib_relu2_mlp``), under the scope ``mlp.shared``:
    its one use is ``HeldExpertsMoE``'s shared expert.  Weights
    ``up_weight`` (width, hidden) and ``down_weight`` (hidden, width)."""

    def __init__(self, hidden, width, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.up_weight = self.params.get("up_weight",
                                             shape=(width, hidden))
            self.down_weight = self.params.get("down_weight",
                                               shape=(hidden, width))

    def hybrid_forward(self, F, x, up_weight, down_weight):
        import jax
        with jax.named_scope("mlp.shared"):
            return F._contrib_relu2_mlp(x, up_weight, down_weight)


class DecoderLayer(HybridBlock):
    """``h = x + Op(RMSNorm(x)); x' = h + FF(RMSNorm(h))`` with the operator
    and the feed-forward chosen for this layer: ``operator`` and
    ``feed_forward`` build them (each a callable without arguments, called
    inside this layer's name scope, so that the child's prefix says its
    kind), the operator a block over ``(rows, positions)``, the feed-forward
    one over the rows."""

    def __init__(self, hidden, operator, feed_forward, epsilon=1e-6,
                 **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.operator_norm = RMSNorm(hidden, epsilon,
                                         prefix="operator_norm_")
            self.operator = operator()
            self.ffn_norm = RMSNorm(hidden, epsilon, prefix="ffn_norm_")
            self.feed_forward = feed_forward()

    def hybrid_forward(self, F, x, positions):
        h = x + self.operator(self.operator_norm(x), positions)
        return h + self.feed_forward(self.ffn_norm(h))


class ResidualLayer(HybridBlock):
    """``x' = x + Block(RMSNorm(x))``: a layer of one block behind one norm
    (``norm_gamma``).  ``block`` builds it (a callable without arguments,
    called inside this layer's name scope); ``mixes``: the block mixes rows
    and takes ``(rows, positions)``, else it takes the rows alone."""

    def __init__(self, hidden, block, epsilon=1e-6, mixes=True, **kwargs):
        super().__init__(**kwargs)
        self._mixes = mixes
        with self.name_scope():
            self.norm = RMSNorm(hidden, epsilon, prefix="norm_")
            self.block = block()

    def hybrid_forward(self, F, x, positions):
        u = self.norm(x)
        return x + (self.block(u, positions) if self._mixes
                    else self.block(u))
