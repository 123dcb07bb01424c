"""Operators of a decoder language model's layers that ``nn_ops`` lacks:
weighted RMSNorm, rotary position embedding with the positions as an input
(one row, or three with the frequencies in sections; the default frequencies
or YaRN's), a dense gated
feed-forward whose backward pass is written out, a dense relu² one, the
operator of the mixture-of-experts layer for the experts held on this chip
(the layer itself is parallel/moe.py's, imported when the operator runs:
``mx.nd`` installs its operators before ``parallel`` is imported, so an
operator registered there would not be found), a gated short convolution
along the sequence, a Mamba-2 mixer's three (its causal convolution, its
state-space scan and its gated norm in groups), and a learned indexer's two:
the selection of each query's keys and the loss that trains it.  The
attention kernels (causal, sliding window, block mask, picked pairs) and the
scan's are in pallas_ops.py.
"""
from __future__ import annotations

import numpy as _np

from .. import profiler
from .registry import register

# what the selection names for jax.checkpoint policies: each row's threshold
# (the bits of its smallest picked score) and where its ties are cut; with
# them a recomputed layer makes the pairs again by two comparisons, with no
# search
SELECTION_RESIDUALS = ("dsa.threshold", "dsa.tie_cut")
INDEX_CHUNK = 512       # queries to a chunk of index scores


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


def yarn_frequency_scale(dim, base, factor, original_max_positions,
                         beta_fast, beta_slow):
    """YaRN's factor on each of the ``dim / 2`` rotary frequencies (Peng et
    al., arXiv:2309.00071, as the released ``rope_type: yarn`` computes it):
    1 for the pairs below ``low``, ``1 / factor`` above ``high``, a linear
    ramp between, where ``low`` = floor(c(beta_fast)) and ``high`` =
    ceil(c(beta_slow)) with ``c(r) = dim ln(original_max_positions / (2 pi
    r)) / (2 ln base)``, the pair that turns ``r`` times over the original
    context.  A host array (float64)."""
    import math

    def pair(rotations):
        return dim * math.log(original_max_positions
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(pair(beta_fast)), 0)
    high = min(math.ceil(pair(beta_slow)), dim - 1)
    ramp = _np.clip((_np.arange(dim // 2) - low)
                    / (high - low if high > low else 0.001), 0.0, 1.0)
    return (1.0 - ramp) + ramp / factor


@register("_contrib_rotary_embedding")
def _rotary_embedding(attrs, x, positions):
    """Rotary position embedding over all of the last axis (rotate-half
    pairing: dimension i with i + D/2), frequency ``i`` ``base^(-i / (D/2))``.
    ``x``: (..., T, D); ``positions``:
    (T,) integers, given by the caller, so that two rows may share one; or
    (3, T) with attr ``sections`` (three counts that sum to D/2): frequency
    ``i`` takes its angle from position row 0 if ``i < sections[0]``, row 1
    for the next ``sections[1]``, row 2 for the rest (temporal, height and
    width of a multimodal sequence; for text the three rows are equal).

    attr ``rope_type`` "default" (the above) or "yarn", which stretches a
    model to positions past its original context: the frequencies times
    ``yarn_frequency_scale`` of attrs ``factor``,
    ``original_max_position_embeddings``, ``beta_fast`` and ``beta_slow``,
    and ``cos`` and ``sin`` times ``attention_factor`` (so that every score
    between two rotated heads is scaled by its square)."""
    import jax.numpy as jnp
    base = float(attrs.get("base", 10000.0))
    half = x.shape[-1] // 2
    inv_freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    kind = attrs.get("rope_type", "default")
    if kind == "yarn":
        inv_freq = inv_freq * jnp.asarray(yarn_frequency_scale(
            x.shape[-1], base, float(attrs["factor"]),
            float(attrs["original_max_position_embeddings"]),
            float(attrs["beta_fast"]), float(attrs["beta_slow"])),
            jnp.float32)
    elif kind != "default":
        raise ValueError("rope_type is default or yarn, not %r" % (kind,))
    positions = positions.astype(jnp.float32)
    if positions.ndim == 2:
        sections = tuple(int(n) for n in attrs["sections"])
        if len(sections) != positions.shape[0] or sum(sections) != half:
            raise ValueError("sections %r for %d position rows and %d "
                             "frequencies" % (sections, positions.shape[0],
                                              half))
        row = jnp.repeat(jnp.arange(len(sections)), jnp.asarray(sections),
                         total_repeat_length=half)
        positions = positions[row, :].T                       # (T, D/2)
    else:
        positions = positions[:, None]
    angle = positions * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)                 # (T, D/2)
    if kind == "yarn":
        factor = float(attrs["attention_factor"])
        cos, sin = cos * factor, sin * factor
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


@register("_contrib_gated_mlp", no_jit=True, shape_rule="input",
          dtype_rule="input")
def _gated_mlp(attrs, x, gate_w, up_w, down_w):
    """A dense gated feed-forward without bias, ``down(silu(gate x) * up
    x)``: ``x`` (..., d); ``gate_w``, ``up_w``: (f, d); ``down_w``: (d, f)
    (``gated_mlp``)."""
    return gated_mlp(x, gate_w, up_w, down_w)


def gated_mlp(x, gate_w, up_w, down_w):
    """The forward pass is the three ``FullyConnected`` products.  The
    backward pass is written out: it recomputes ``g = gate x`` and ``u = up
    x`` from ``x`` (the residuals are ``x`` and the weights, so a layer's
    recomputed forward pass has no consumer here), computes ``dh = dy
    down_w``, then ``dg``, ``du`` and ``h = silu(g) u`` in one float32 pass,
    and writes them once, behind a barrier, in the dtype the matrix units
    take; the input's gradient and the three weights' are products of those
    over the flattened rows, accumulated in float32.  Without the barrier
    XLA fuses the SiLU's backward into every contraction that reads it and
    rebuilds the operand from float32 ``g``, ``u`` and ``dh`` for each
    output tile.  On a TPU that dtype is bfloat16, which is what XLA's
    default precision gives float32 operands of a product there, so the
    products see the values they saw; elsewhere the operands keep their own
    dtype, and this is the oracle of the TPU's form."""
    import jax
    import jax.numpy as jnp
    cdt = jnp.bfloat16 if jax.default_backend() == "tpu" else None

    def cast(a):
        return a.astype(cdt) if cdt is not None else a

    def forward(x, gate_w, up_w, down_w):
        g = jnp.matmul(x, gate_w.T)
        return jnp.matmul(g * jax.nn.sigmoid(g) * jnp.matmul(x, up_w.T),
                          down_w.T)

    def product(a, b, contract):
        return jax.lax.dot_general(a, b, (contract, ((), ())),
                                   preferred_element_type=jnp.float32)

    def backward(kept, dy):
        x, gate_w, up_w, down_w = kept
        with jax.named_scope("mlp.dense"):
            d = x.shape[-1]
            rows, dy_r = cast(x.reshape(-1, d)), cast(dy.reshape(-1, d))
            wg, wu, wd = cast(gate_w), cast(up_w), cast(down_w)
            g = product(rows, wg, ((1,), (1,)))
            u = product(rows, wu, ((1,), (1,)))
            dh = product(dy_r, wd, ((1,), (0,)))
            s = jax.nn.sigmoid(g)
            silu = g * s
            dg, du, h = jax.lax.optimization_barrier(tuple(cast(a) for a in (
                dh * u * s * (1 + g * (1 - s)), dh * silu, silu * u)))
            dx = product(dg, wg, ((1,), (0,))) + product(du, wu, ((1,), (0,)))
            return (dx.reshape(x.shape).astype(x.dtype),
                    product(dg, rows, ((0,), (0,))).astype(gate_w.dtype),
                    product(du, rows, ((0,), (0,))).astype(up_w.dtype),
                    product(dy_r, h, ((0,), (0,))).astype(down_w.dtype))

    layer = jax.custom_vjp(forward)
    layer.defvjp(lambda *args: (forward(*args), args), backward)
    return layer(x, gate_w, up_w, down_w)


@register("_contrib_relu2_mlp", no_jit=True, shape_rule="input",
          dtype_rule="input")
def _relu2_mlp(attrs, x, up_w, down_w):
    """A dense feed-forward without gate or bias, ``down(relu(up x)^2)``:
    ``x`` (..., d); ``up_w``: (f, d); ``down_w``: (d, f).  Two products at
    the default matmul precision; XLA's derivative."""
    import jax
    import jax.numpy as jnp
    return jnp.matmul(jnp.square(jax.nn.relu(jnp.matmul(x, up_w.T))),
                      down_w.T)


@register("_contrib_moe_held_experts", num_outputs=2, no_jit=True,
          shape_rule="input", dtype_rule="input")
def _moe_held_experts(attrs, x, router_w, *weights):
    """``parallel.moe.moe_held_apply`` as an operator.  ``x``: (..., d);
    ``router_w``: (E, d) over all experts; then the held experts' matrices
    stacked along the first axis, ``gate_w``, ``up_w``: (E_held * f, d) and
    ``down_w``: (E_held * d, f) (with attr ``act`` "relu2" no ``gate_w``);
    optionally ``bias``: (E,), a sigmoid router's selection bias.  attrs:
    ``experts_per_token``, ``expert_width`` (f), ``first_expert``, ``act``
    ("swiglu", the default, or "relu2"), and the router's form: ``scoring``
    ("softmax", the default, or "sigmoid"), ``scale`` (the weights' factor,
    default 1) and ``router_eps`` (under the sigmoid router's normalisation,
    default ``parallel.moe.SIGMOID_NORM_EPS``).  Outputs: the layer's
    output, shaped as ``x``, and float32 ``[pairs routed here, largest
    load]``.  The rows' picked experts are computed through a table of
    slots sorted by expert, sized from the shapes alone (on a TPU by the
    kernels ``moe_experts_*`` of ``pallas_ops``, elsewhere by XLA's products
    over the same table)."""
    from ..parallel.moe import SIGMOID_NORM_EPS, moe_held_apply
    d, f = x.shape[-1], int(attrs["expert_width"])
    act = str(attrs.get("act", "swiglu"))
    count = 2 if act == "relu2" else 3
    bias = weights[count] if len(weights) > count else None
    rows = x.reshape(-1, d)
    gate_w = None if count == 2 else weights[0].reshape(-1, f, d)
    up_w, down_w = weights[count - 2:count]
    out, load = moe_held_apply(
        rows, router_w, gate_w,
        up_w.reshape(-1, f, d), down_w.reshape(-1, d, f),
        int(attrs["experts_per_token"]),
        first_expert=int(attrs.get("first_expert", 0)),
        scoring=str(attrs.get("scoring", "softmax")),
        scale=float(attrs.get("scale", 1.0)), bias=bias, act=act,
        eps=float(attrs.get("router_eps", SIGMOID_NORM_EPS)))
    return out.reshape(x.shape), load


@register("_contrib_moe_route", num_outputs=2, no_jit=True,
          shape_rule="input", dtype_rule="input")
def _moe_route(attrs, x, router_w, bias=None):
    """The router of ``_contrib_moe_held_experts`` alone
    (``parallel.moe.route_tokens``), for a caller that checks the selection:
    the same inputs and attrs (those of the experts are not read); outputs
    the picked experts' weights, float32, and their ids, int32, each
    (rows, ``experts_per_token``)."""
    from ..parallel.moe import SIGMOID_NORM_EPS, route_tokens
    return route_tokens(
        x.reshape(-1, x.shape[-1]), router_w, int(attrs["experts_per_token"]),
        str(attrs.get("scoring", "softmax")), float(attrs.get("scale", 1.0)),
        bias, float(attrs.get("router_eps", SIGMOID_NORM_EPS)))


@register("_contrib_gated_short_conv", no_jit=True, shape_rule="input",
          dtype_rule="input")
def _gated_short_conv(attrs, streams, taps):
    """The gated short convolution between a block's two projections
    (``pallas_ops.gated_short_conv``): ``streams`` (B, L, 3 d), the input
    projection's three streams ``[Bg, Cg, X]``; ``taps`` (d, K), one filter a
    channel; output ``Cg * conv(Bg * X)``, (B, L, d), causal along L."""
    from .pallas_ops import gated_short_conv
    return gated_short_conv(streams, taps)


@register("_contrib_ssm_conv", no_jit=True, shape_rule="input",
          dtype_rule="input")
def _ssm_conv(attrs, x, weight, bias):
    """A Mamba-2 mixer's convolution: ``silu(conv(x) + bias)``, ``conv`` a
    causal depthwise convolution along L of ``K`` taps, one filter a
    channel: ``x`` (B, L, c); ``weight`` (c, K); ``bias`` (c,).  Row ``t``
    reads rows ``t - K + 1 .. t`` of its own sequence, zeros before the
    first (``weight[:, K - 1]`` multiplies row ``t``).  XLA's form, ``K``
    shifted copies in one elementwise pass, under the scope ``ssm.conv``."""
    import jax
    import jax.numpy as jnp
    K, length = weight.shape[1], x.shape[1]
    with jax.named_scope("ssm.conv"):
        padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
        w = weight.astype(x.dtype)
        out = padded[:, :length] * w[:, 0]
        for j in range(1, K):
            out = out + padded[:, j:j + length] * w[:, j]
        return jax.nn.silu(out + bias.astype(x.dtype))


@register("_contrib_ssd_scan", no_jit=True, shape_rule="input",
          dtype_rule="input")
def _ssd_scan(attrs, xbc, dt, dt_bias, a_log, d_skip):
    """A Mamba-2 mixer's state-space scan (``pallas_ops.ssd_scan``) from
    what the mixer's convolution and projection give: ``xbc`` (B, L, H P + 2
    G N), the streams ``x``, ``B`` and ``C`` side by side; ``dt`` (B, L, H),
    the steps before ``softplus(dt + dt_bias)``; ``a_log``, ``d_skip`` (H,):
    ``A = -exp(a_log)`` and the skip ``D``.  attrs ``heads`` (H), ``groups``
    (G), ``state`` (N), optional ``chunk``.  Output ``y`` (B, L, H P)."""
    import jax
    import jax.numpy as jnp
    from .pallas_ops import SSD_CHUNK, ssd_scan
    H, G, N = int(attrs["heads"]), int(attrs["groups"]), int(attrs["state"])
    Bt, L, width = xbc.shape
    inner = width - 2 * G * N
    with jax.named_scope("ssm.scan"):
        x = xbc[..., :inner].reshape(Bt, L, H, inner // H)
        B = xbc[..., inner:inner + G * N].reshape(Bt, L, G, N)
        C = xbc[..., inner + G * N:].reshape(Bt, L, G, N)
        step = jax.nn.softplus((dt + dt_bias).astype(jnp.float32))
        A = -jnp.exp(a_log.astype(jnp.float32))
        y = ssd_scan(x, step, A, B, C, d_skip,
                     chunk=int(attrs.get("chunk", SSD_CHUNK)))
        return y.reshape(Bt, L, inner)


@register("_contrib_gated_rms_norm", no_jit=True, shape_rule="input",
          dtype_rule="input")
def _gated_rms_norm(attrs, y, z, gamma):
    """A Mamba-2 mixer's gated norm in groups (``norm_before_gate`` false):
    ``h = y * silu(z)``, each of ``groups`` groups of consecutive channels
    divided by its RMS (``sqrt(mean(h^2) + eps)``, in float32), times
    ``gamma``.  ``y``, ``z`` (..., c); ``gamma`` (c,); attrs ``groups``,
    ``eps``.  Under the scope ``ssm.norm``."""
    import jax
    import jax.numpy as jnp
    groups, eps = int(attrs["groups"]), float(attrs.get("eps", 1e-5))
    with jax.named_scope("ssm.norm"):
        h = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        parts = h.reshape(h.shape[:-1] + (groups, -1))
        parts = parts * jax.lax.rsqrt(
            jnp.mean(parts * parts, axis=-1, keepdims=True) + eps)
        return (parts.reshape(h.shape) * gamma.astype(jnp.float32)).astype(
            y.dtype)


def _order_bits(scores):
    """float32 scores as uint32 whose order is the scores' (-0 as +0; no
    real score maps to 0)."""
    import jax
    import jax.numpy as jnp
    scores = jnp.where(scores == 0, 0.0, scores)
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    return jnp.where(bits >> 31 == 0, bits | jnp.uint32(1 << 31), ~bits)


def index_scores(index_q, index_k, weights, chunk=INDEX_CHUNK):
    """``I[b, t, s] = sum_j weights[b, t, j] * relu(index_q[b, j, t] .
    index_k[b, s])``: (B, T, T) float32, every pair (the caller masks the
    ones above the diagonal).  ``index_q``: (B, J, T, D); ``index_k``:
    (B, T, D); ``weights``: (B, T, J).  By chunks of queries, each
    recomputed in the backward pass: a chunk's (B, J, chunk, T) products
    are the largest value alive."""
    import jax
    import jax.numpy as jnp
    B, J, T, D = index_q.shape
    chunk = chunk if T % chunk == 0 else T
    n = T // chunk

    @jax.checkpoint
    def one(args):
        q_c, w_c = args                   # (B, J, chunk, D), (B, chunk, J)
        s = jnp.einsum("bjtd,bsd->bjts", q_c, index_k,
                       preferred_element_type=jnp.float32)
        w_c = jnp.swapaxes(w_c, 1, 2).astype(jnp.float32)[..., None]
        return jnp.sum(jax.nn.relu(s) * w_c, axis=1)          # (B, chunk, T)

    out = jax.lax.map(one, (
        jnp.moveaxis(index_q.reshape(B, J, n, chunk, D), 2, 0),
        jnp.moveaxis(weights.reshape(B, n, chunk, J), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(B, T, T)


def select_top_k(scores, k, interpret=None):
    """The pairs an indexer picks: for each query ``t`` the ``min(t + 1, k)``
    keys ``s <= t`` of largest ``scores[b, t, s]``, ties to the lower ``s``
    (what ``jax.lax.top_k`` gives; a zero of either sign is one value), as
    int8 (B, T, T).  Exact, with no sort: the row's ``k``-th largest score is
    found bit by bit (32 passes that compare and count over the scores'
    order-preserving bits), then among the keys equal to it the cut
    (``log2 T`` more); a key is picked if its bits lie above the row's
    threshold, or equal it at or before the cut.  On a TPU (or where
    ``interpret`` is given) the passes are one kernel's over rows it holds in
    VMEM (``pallas_ops.select_thresholds``), elsewhere XLA's over the whole
    square.  The two numbers a row are named ``SELECTION_RESIDUALS``, so a
    recomputed layer searches nothing."""
    import jax
    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name
    from . import pallas_ops
    B, T, _ = scores.shape
    s_at = jnp.arange(T, dtype=jnp.int32)
    causal = s_at[None, :] <= s_at[:, None]
    scores = jax.lax.stop_gradient(scores)
    bits = jnp.where(causal, _order_bits(scores), jnp.uint32(0))
    if T % 128 == 0 and (interpret is not None
                         or jax.default_backend() == "tpu"):
        threshold, cut = pallas_ops.select_thresholds(scores, k,
                                                      bool(interpret))
        threshold = jax.lax.bitcast_convert_type(threshold, jnp.uint32)
    else:
        threshold, cut = _search_thresholds(bits, k)
    threshold = checkpoint_name(threshold, SELECTION_RESIDUALS[0])
    cut = checkpoint_name(cut, SELECTION_RESIDUALS[1])
    picked = (bits > threshold[..., None]) | (
        (bits == threshold[..., None]) & (s_at <= cut[..., None]))
    return (picked & causal).astype(jnp.int8)


def _search_thresholds(bits, k):
    """(threshold (B, T) uint32, cut (B, T) int32) of the rows of ``bits``
    (B, T, T) uint32, the keys after the query 0: what
    ``pallas_ops.select_thresholds`` finds, in XLA."""
    import jax
    import jax.numpy as jnp
    B, T, _ = bits.shape
    s_at = jnp.arange(T, dtype=jnp.int32)

    def count(found):
        return jnp.sum(found, axis=-1, dtype=jnp.int32)

    def raise_threshold(i, low):
        tried = low | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        return jnp.where(count(bits >= tried[..., None]) >= k, tried, low)

    # the largest value that k scores of the row reach: the k-th largest
    # (0 where the row has fewer than k keys: every real score is above it)
    threshold = jax.lax.fori_loop(0, 32, raise_threshold,
                                  jnp.zeros((B, T), jnp.uint32))
    equal = bits == threshold[..., None]
    wanted = k - count(bits > threshold[..., None])  # of the equal, the first
    cut_bits = max(T - 1, 1).bit_length()

    def raise_cut(i, low):
        tried = low | (jnp.int32(1 << cut_bits - 1) >> i)
        fewer = count(equal & (s_at < tried[..., None])) < wanted
        return jnp.where(fewer, tried, low)

    return threshold, jax.lax.fori_loop(0, cut_bits, raise_cut,
                                        jnp.zeros((B, T), jnp.int32))


@register("_contrib_index_select", num_outputs=2, no_jit=True,
          shape_rule="input", dtype_rule="input")
def _index_select(attrs, index_q, index_k, weights):
    """A learned indexer's selection (DeepSeek Sparse Attention).
    ``index_q``: (B, J, T, D) and ``index_k``: (B, T, D), the indexer's
    queries and its one shared key head; ``weights``: (B, T, J), the heads'
    weights, scaled.  attr ``topk``.  Outputs: the index scores (B, T, T)
    float32 (differentiable; meaningful on and under the diagonal) and the
    picked pairs (B, T, T) int8, ``min(t + 1, topk)`` a row, exactly the
    largest (``select_top_k``)."""
    import jax
    k = int(attrs["topk"])
    T = index_q.shape[2]
    profiler.count("dsa.pairs_causal", index_q.shape[0] * T * (T + 1) // 2)
    kept = min(k, T)
    profiler.count("dsa.pairs_selected", index_q.shape[0] * (
        kept * (kept + 1) // 2 + (T - kept) * kept))
    with jax.named_scope("dsa.index"):
        scores = index_scores(index_q, index_k, weights)
    with jax.named_scope("dsa.select"):
        return scores, select_top_k(scores, k)


@register("_contrib_index_loss", num_outputs=2, no_jit=True,
          shape_rule="input", dtype_rule="input",
          no_grad="to the index scores and the handed-through output alone "
                  "(q, k and lse pass stop_gradient)")
def _index_loss(attrs, scores, pairs, q, k, lse, out):
    """The loss that trains an indexer: the KL divergence from the
    attention's own distribution over each query's picked keys, averaged
    over the query heads and taken as a constant (``q``: (B, H, T, D),
    ``k``: (B, Hkv, T, D) and ``lse``: (B, H, T) as the attention call had
    and gave them; no gradient reaches them), to the softmax of the index
    ``scores`` (B, T, T) over the same keys ``pairs``, summed over the rows
    and divided by ``B * T``.  Outputs: the loss, (1,) float32, and ``out``
    (the attention's output) as it came: handed through a barrier with the
    loss, so that the loss is computed before the attention's rows go on;
    nothing else needs it before the step's loss is summed, and the
    scheduler would leave every layer's scores alive till then.  Optional
    attr ``scale``."""
    import jax
    import jax.numpy as jnp
    B, T, _ = scores.shape
    scale = attrs.get("scale")
    scale = 1.0 / q.shape[-1] ** 0.5 if scale is None else float(scale)
    from .pallas_ops import head_mean_probabilities
    with jax.named_scope("dsa.index_loss"):
        picked = pairs != 0
        # read under the pairs: a tile with nothing picked was never
        # written, and whatever it holds (a NaN) would reach the scores'
        # gradient through the product below, times 0
        target = jax.lax.stop_gradient(jnp.where(
            picked, head_mean_probabilities(q, k, lse, pairs, scale), 0.0))
        log_q = jax.nn.log_softmax(
            jnp.where(picked, scores.astype(jnp.float32), -1e30), axis=-1)
        log_p = jnp.log(jnp.where(target > 0, target, 1.0))
        terms = jnp.where(picked, target * (log_p - log_q), 0.0)
        return jax.lax.optimization_barrier(
            ((jnp.sum(terms) / (B * T)).reshape(1), out))
