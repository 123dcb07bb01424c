"""Family ``lfm2_moe``: a causal decoder language model whose layers differ:
a gated short convolution or grouped-query attention as the token mixer, a
dense gated feed-forward or a mixture of experts behind it (LFM2-24B-A2B,
https://huggingface.co/LiquidAI/LFM2-24B-A2B, ``model_type: lfm2_moe``; the
convolution and the router are the released ``Lfm2Moe`` modeling code's
``Lfm2ShortConv`` and ``Lfm2MoeSparseMoeBlock``), as one chip of an
expert-parallel deployment holds it.

The equations
-------------
``u`` is a block's normed input, ``eps`` = ``norm_eps``, no bias anywhere.
RMSNorm: ``x / sqrt(mean(x^2) + eps) * gamma``.

Input: ``tokens`` ``[B, L]``, ids of the held slice of the vocabulary;
``x = E[tokens]``.  The layers held here are ``deployment.layers`` (published
indices; default the first ``num_hidden_layers``).  Layer ``i`` of kind
``t_i`` from ``layer_types``:
``h = x + Op_i(RMSNorm(x))``, ``x' = h + FF_i(RMSNorm(h))``; after the last
layer RMSNorm, then the head ``logits = x E^T`` over all ``L`` rows (tied to
the embedding: ``assumed``).

``Op`` = ``conv``: ``[Bg, Cg, X] = W_in u`` (three streams of ``hidden``);
``z = Bg * X``; ``c[t] = sum_{j=0..K-1} w[:, j] * z[t - (K - 1) + j]`` with
``z`` zero before the sequence's first row, ``K`` = ``conv_L_cache``, one
filter a channel (``w``: hidden x K; PyTorch's ``Conv1d`` with ``groups`` =
channels and left padding ``K - 1``, a cross-correlation);
``y = W_out (Cg * c)``.  Causal: row ``t`` reads rows ``t - K + 1 .. t`` of
its own sequence and no other sequence of the batch.  The taps are
elementwise (``K * hidden`` multiply-accumulates a row, 6,144 at the
published sizes): they go through no ``ops`` product, and ``flops.py`` counts
none of them.

``Op`` = ``full_attention``: ``q = W_q u`` as ``num_attention_heads`` heads
of ``head_dim`` (hidden over heads where the configuration gives none),
``k, v`` as ``num_key_value_heads`` heads; RMSNorm over each head's
dimensions of ``q`` and of ``k`` (``assumed``); rotary embedding on all of
them, rotate-half pairing, base ``rope_theta``, positions ``0..L-1``; causal
softmax at scale ``1 / sqrt(head_dim)``; ``W_o``.

``FF_i`` dense for ``i < num_dense_layers``: ``W_down (silu(W_gate u) * W_up
u)``, width ``intermediate_size``.

``FF_i`` routed otherwise: ``logits = W_r u`` over all ``num_experts_total``
experts in float32; ``s = sigmoid(logits)``; ``S_t`` = the
``num_experts_per_tok`` experts of largest ``s + b``, ties to the lower id
(``jax.lax.top_k``), ``b`` the experts' selection biases
(``use_expert_bias``); ``w_e = s_e / (sum_{e' in S_t} s_e' + 1e-6) *
routed_scaling_factor`` for ``e`` in ``S_t`` (``norm_topk_prob``; the bias is
not in the weights); ``out = sum_{e in S_t, e held} w_e * W_down_e
(silu(W_gate_e u) * W_up_e u)``, width ``moe_intermediate_size``, over the
chosen experts **that are held here** (``num_experts`` of them, from
``first_expert``).  What the others would add is left out, as in the program.
No token is dropped.  ``b`` is a leaf that the selection alone reads: its
gradient is exactly 0.

Loss (``batch = (tokens, targets, weight)``, from
``generators/next_token.py``): ``sum_i weight_i CE(logits_i, targets_i) / (B
* L)``.

What is counted and what is trained
-----------------------------------
``forward`` is what ``flops.py`` walks under ``jax.eval_shape``, counting
every ``ops.einsum`` from its shapes, so its loops are Python's and its shapes
those of the required work: attention by chunks of ``CHUNK`` (128) queries,
each against the keys up to its own end (1.5% more pairs than the causal
``L (L + 1) / 2`` at ``L`` 8192); the experts over the pairs routed here in a
buffer of the even load where the configuration says ``"moe_reference_load":
"even"`` (every pair where it says nothing), a load beyond it making the
logits NaN; the head over all rows.

``loss`` is what is trained and compared with the program: the same
``_attend_chunk`` and ``_expert``, looped by ``lax.map`` / ``lax.scan`` (one
chunk's program over the chunks against all keys under the causal mask, one
expert's over the held experts and all rows), each layer, chunk and expert
recomputed in the backward pass (``jax.checkpoint``) so that three steps at
the timed sizes fit the chip.  The layers differ, so they are Python's loop.
tests/test_short_conv_lm.py holds the two equal.

``router_scores``, ``selection_scores``, ``weight_scores``, ``normalised``,
``conv_window``, ``gated`` and ``lands_here`` are the rules a check replaces to
plant a fault (benchmark/checks/faults_lfm2.py); nothing here reads a switch.

Nothing here imports the program under test.
"""
from __future__ import annotations

import math
from collections import OrderedDict

import jax
import jax.numpy as jnp

CHUNK = 128          # queries to a chunk of attention
ROUTER_NORM_EPS = 1e-6


# -- the rules a check may replace -------------------------------------------

def router_scores(logits):
    """Each expert's own score of a token, from the router's float32
    logits."""
    return jax.nn.sigmoid(logits)


def selection_scores(scores, bias):
    """What the experts are ranked by: the scores and the selection bias."""
    return scores + bias


def weight_scores(scores, bias):
    """What the picked experts are weighted by: the scores without the
    bias."""
    return scores


def normalised(picked):
    """The picked experts' weights, (T, k), over their sum."""
    return picked / (jnp.sum(picked, axis=-1, keepdims=True)
                     + ROUTER_NORM_EPS)


def conv_window(z, taps):
    """The rows a convolution of ``taps`` taps reads for each row of ``z``
    (B, L, d): ``taps`` arrays, the ``j``-th holding row ``t - (taps - 1) +
    j`` at ``t``, zeros before the sequence's first row."""
    length = z.shape[1]
    padded = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    return [padded[:, j:j + length] for j in range(taps)]


def gated(gate, c):
    """The convolution's output under its output gate."""
    return gate * c


def lands_here(local, held):
    """Which (token, slot) pairs this chip computes: ``local`` is the chosen
    expert's id less the first held one's."""
    return (local >= 0) & (local < held)


# -- sizes, shapes, input ----------------------------------------------------

def _sizes(config):
    deployment = config.get("deployment", {})
    heads = config["num_attention_heads"]
    return {
        "hidden": config["hidden_size"], "heads": heads,
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config.get("head_dim") or config["hidden_size"] // heads,
        "taps": config["conv_L_cache"],
        # (operator's kind, whether the feed-forward is dense) of each layer
        # held here, by its published index
        "held_layers": tuple(
            (config["layer_types"][i], i < config["num_dense_layers"])
            for i in deployment.get("layers")
            or range(config["num_hidden_layers"])),
        "dense_width": config["intermediate_size"],
        "width": config["moe_intermediate_size"],
        "held": config["num_experts"],
        "experts": deployment.get("num_experts_total", config["num_experts"]),
        "first": deployment.get("first_expert", 0),
        "top_k": config["num_experts_per_tok"],
        "scale": float(config.get("routed_scaling_factor", 1.0)),
        "bias": bool(config.get("use_expert_bias")),
        "layers": config["num_hidden_layers"], "vocab": config["vocab_size"],
        "eps": config["norm_eps"],
        "rope": float(config["rope_parameters"]["rope_theta"]),
    }


def param_shapes(config):
    """Names are those of the program's Gluon blocks
    (``mxnet_tpu/gluon/model_zoo/short_conv_lm.py``) behind the network's
    prefix; the head has no leaf of its own.  The held experts' matrices are
    stacked in 2-D leaves, so that ``xavier_init`` takes the stacked fan; it
    reads the taps (hidden x K) as a matrix too, and makes the selection
    biases 0."""
    s = _sizes(config)
    d, hd = s["hidden"], s["head_dim"]
    if len(s["held_layers"]) != s["layers"]:
        raise ValueError("%d layers held of num_hidden_layers %d"
                         % (len(s["held_layers"]), s["layers"]))
    shapes = OrderedDict([("embed_weight", (s["vocab"], d))])
    for i, (kind, dense) in enumerate(s["held_layers"]):
        p = "layer%d_" % i
        shapes[p + "operator_norm_gamma"] = (d,)
        if kind == "conv":
            shapes.update([(p + "conv_in_weight", (3 * d, d)),
                           (p + "conv_taps_weight", (d, s["taps"])),
                           (p + "conv_out_weight", (d, d))])
        elif kind == "full_attention":
            shapes.update([
                (p + "attn_q_weight", (s["heads"] * hd, d)),
                (p + "attn_k_weight", (s["kv_heads"] * hd, d)),
                (p + "attn_v_weight", (s["kv_heads"] * hd, d)),
                (p + "attn_o_weight", (d, s["heads"] * hd)),
                (p + "attn_q_norm_gamma", (hd,)),
                (p + "attn_k_norm_gamma", (hd,))])
        else:
            raise ValueError("a layer's operator is conv or full_attention, "
                             "not %r" % (kind,))
        shapes[p + "ffn_norm_gamma"] = (d,)
        if dense:
            shapes.update([(p + "mlp_gate_weight", (s["dense_width"], d)),
                           (p + "mlp_up_weight", (s["dense_width"], d)),
                           (p + "mlp_down_weight", (d, s["dense_width"]))])
        else:
            shapes[p + "moe_router_weight"] = (s["experts"], d)
            if s["bias"]:
                shapes[p + "moe_expert_bias"] = (s["experts"],)
            shapes.update([
                (p + "moe_gate_weight", (s["held"] * s["width"], d)),
                (p + "moe_up_weight", (s["held"] * s["width"], d)),
                (p + "moe_down_weight", (s["held"] * d, s["width"]))])
    shapes["final_norm_gamma"] = (d,)
    return shapes


def example_input(config, traffic):
    return (jax.ShapeDtypeStruct((1, traffic["seq_len"]), jnp.int32),)


# -- the layers -------------------------------------------------------------

def rms_norm(x, gamma, eps):
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * inv).astype(x.dtype) * gamma.astype(x.dtype)


def rotary(x, positions, base):
    """``x``: (..., T, D); rotate-half pairing over all D."""
    half = x.shape[-1] // 2
    inv_freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def short_conv(s, ops, params, prefix, x):
    """``x``: (B, L, hidden), the layer's normed input."""
    streams = ops.einsum("bld,ed->ble", x, params[prefix + "conv_in_weight"])
    gate_in, gate_out, inner = jnp.split(streams, 3, axis=-1)
    taps = params[prefix + "conv_taps_weight"].astype(streams.dtype)
    c = sum(rows * taps[:, j] for j, rows in enumerate(
        conv_window(gate_in * inner, s["taps"])))
    return ops.einsum("bld,ed->ble", gated(gate_out, c),
                      params[prefix + "conv_out_weight"])


def _attend_chunk(s, ops, rows, q, k, v):
    """One chunk of queries at positions ``rows`` (T,) against the keys
    handed over (their positions start at 0): ``q`` (B, kv, g, T, hd),
    ``k``/``v`` (B, kv, n, hd); a query sees the keys at or before it."""
    scale = 1.0 / math.sqrt(s["head_dim"])
    keep = (jnp.arange(k.shape[2])[None, :] <= rows[:, None])[None, None, None]
    att = ops.einsum("bhgtd,bhkd->bhgtk", q, k)
    att = jnp.where(keep, att.astype(jnp.float32) * scale, -1e30)
    prob = jax.nn.softmax(att, axis=-1)
    return ops.einsum("bhgtk,bhkd->bhgtd", prob.astype(v.dtype), v)


def attention(s, ops, params, prefix, x, looped):
    """``x``: (B, L, hidden), the layer's normed input."""
    B, L, _ = x.shape
    hd, kv, heads = s["head_dim"], s["kv_heads"], s["heads"]
    chunk = CHUNK if L % CHUNK == 0 else L
    positions = jnp.arange(L)

    def project(name, count, norm):
        t = ops.einsum("bld,ed->ble", x, params[prefix + name + "_weight"])
        t = t.reshape(B, L, count, hd)
        if norm:
            t = rms_norm(t, params[prefix + name + "_norm_gamma"], s["eps"])
        t = t.transpose(0, 2, 1, 3)                         # (B, heads, L, hd)
        return rotary(t, positions, s["rope"]) if norm else t

    q = project("attn_q", heads, True).reshape(B, kv, heads // kv, L, hd)
    k, v = project("attn_k", kv, True), project("attn_v", kv, False)
    if looped:
        # one chunk's program over the chunks, each against all keys
        n = L // chunk
        one = jax.checkpoint(lambda args: _attend_chunk(
            s, ops, args[0] + jnp.arange(chunk), args[1], k, v))
        out = jax.lax.map(one, (
            jnp.arange(n) * chunk,
            jnp.moveaxis(q.reshape(B, kv, heads // kv, n, chunk, hd), 3, 0)))
        out = jnp.moveaxis(out, 0, 3).reshape(B, kv, heads // kv, L, hd)
    else:
        # what flops.py counts: every chunk against the keys up to its end
        out = jnp.concatenate([_attend_chunk(
            s, ops, jnp.arange(lo, lo + chunk), q[:, :, :, lo:lo + chunk],
            k[:, :, :lo + chunk], v[:, :, :lo + chunk])
            for lo in range(0, L, chunk)], axis=3)
    out = out.reshape(B, heads, L, hd).transpose(0, 2, 1, 3)
    return ops.einsum("ble,de->bld", out.reshape(B, L, heads * hd),
                      params[prefix + "attn_o_weight"])


def dense_mlp(ops, params, prefix, y):
    """``y``: (T, hidden)."""
    return _expert(ops, "", y, params[prefix + "mlp_gate_weight"],
                   params[prefix + "mlp_up_weight"],
                   params[prefix + "mlp_down_weight"])


def route(s, ops, params, prefix, y, given=None):
    """(weights (T, top_k), expert ids (T, top_k)) of the tokens ``y``
    (T, hidden), over all experts.  ``given`` (T, top_k), where handed over,
    are the experts to use in the place of the router's picks; the weights
    are then the router's own of those."""
    logits = ops.einsum("td,ed->te", y, params[prefix + "moe_router_weight"])
    scores = router_scores(logits.astype(jnp.float32))
    bias = params[prefix + "moe_expert_bias"].astype(jnp.float32) \
        if s["bias"] else jnp.zeros((s["experts"],), jnp.float32)
    expert = given
    if expert is None:
        _, expert = jax.lax.top_k(selection_scores(scores, bias), s["top_k"])
    picked = jnp.take_along_axis(weight_scores(scores, bias), expert, axis=-1)
    return normalised(picked) * s["scale"], expert


def moe(s, ops, params, prefix, y, pairs, looped, given=None):
    """``y``: (T, hidden).  Returns (the held experts' part, overflow);
    ``given`` as ``route`` takes it."""
    held, width, d = s["held"], s["width"], y.shape[1]
    weight, expert = route(s, ops, params, prefix, y, given)
    local = expert - s["first"]                                # (T, top_k)
    local = jnp.where(lands_here(local, held), local, held)    # held: not here
    matrices = (params[prefix + "moe_gate_weight"].reshape(held, width, d),
                params[prefix + "moe_up_weight"].reshape(held, width, d),
                params[prefix + "moe_down_weight"].reshape(held, d, width))
    if looped:
        return _experts_looped(s, ops, y, local, weight, matrices), False
    return _experts_by_shapes(s, ops, y, local, weight, matrices, pairs)


def _expert(ops, own, xs, gate_w, up_w, down_w):
    """``down(silu(gate x) * up x)`` over rows ``r``; ``own`` is ``"r"`` where
    every row brings its own matrices, else empty."""
    gate = ops.einsum("rd,%sfd->rf" % own, xs, gate_w)
    up = ops.einsum("rd,%sfd->rf" % own, xs, up_w)
    return ops.einsum("rf,%sdf->rd" % own, jax.nn.silu(gate) * up, down_w)


def _experts_looped(s, ops, y, local, weight, matrices):
    """What is trained: one expert's program, looped over the held experts,
    each over all rows with the weight of the rows that did not choose it
    0."""
    @jax.checkpoint
    def one(total, args):
        e, gate_w, up_w, down_w = args
        w_e = jnp.sum(jnp.where(local == e, weight, 0.0), axis=-1)
        out = _expert(ops, "", y, gate_w, up_w, down_w)
        return total + (out * w_e[:, None]).astype(y.dtype), None

    return jax.lax.scan(one, jnp.zeros_like(y),
                        (jnp.arange(s["held"]),) + matrices)[0]


def _experts_by_shapes(s, ops, y, local, weight, matrices, pairs):
    """What flops.py counts: the pairs that land here, gathered into a buffer
    of ``pairs`` rows, each multiplied with its own expert's matrices."""
    held, top_k = s["held"], s["top_k"]
    key = local.reshape(-1)
    first = jnp.argsort(key == held, stable=True)[:pairs]   # those here first
    valid = key[first] < held
    token, expert = first // top_k, jnp.where(valid, key[first], 0)
    xs = jnp.where(valid[:, None], y[token], 0.0)
    ys = _expert(ops, "r", xs, *(m[expert] for m in matrices))
    row_weight = jnp.where(valid, weight.reshape(-1)[first], 0.0)
    out = jnp.zeros_like(y).at[token].add(
        (ys * row_weight[:, None]).astype(y.dtype))
    return out, jnp.sum(key < held) > pairs


def reference_pairs(config, rows):
    """How many (token, slot) pairs the counted experts' buffer holds for
    ``rows`` tokens: the even load where the configuration says so, else
    every pair."""
    s = _sizes(config)
    if config.get("moe_reference_load") == "even":
        return rows * s["top_k"] * s["held"] // s["experts"]
    return rows * s["top_k"]


def network(config, ops, params, tokens, looped):
    """Logits (B, L, vocab), as trained (``looped``) or as counted.  Where
    the counted buffer overflows, the logits are NaN."""
    s = _sizes(config)
    B, L = tokens.shape
    pairs = reference_pairs(config, B * L)
    x = params["embed_weight"].astype(ops.dtype)[tokens]

    def layer(kind, dense, prefix):
        def run(x, weights):
            u = rms_norm(x, weights[prefix + "operator_norm_gamma"], s["eps"])
            if kind == "conv":
                h = x + short_conv(s, ops, weights, prefix, u)
            else:
                h = x + attention(s, ops, weights, prefix, u, looped)
            y = rms_norm(h, weights[prefix + "ffn_norm_gamma"],
                         s["eps"]).reshape(B * L, -1)
            if dense:
                out, overflow = dense_mlp(ops, weights, prefix, y), False
            else:
                out, overflow = moe(s, ops, weights, prefix, y, pairs, looped)
            return h + out.reshape(h.shape), overflow
        return run

    overflow = False
    for i, (kind, dense) in enumerate(s["held_layers"]):
        prefix = "layer%d_" % i
        run = layer(kind, dense, prefix)
        weights = {k: v for k, v in params.items() if k.startswith(prefix)}
        if looped:      # as trained: the layer recomputed, nothing to overflow
            x = jax.checkpoint(lambda x, w, run=run: run(x, w)[0])(x, weights)
        else:
            x, over = run(x, weights)
            overflow = overflow | over
    x = rms_norm(x, params["final_norm_gamma"], s["eps"])
    logits = ops.einsum("bld,vd->blv", x, params["embed_weight"])
    return jnp.where(overflow, jnp.nan, logits)


def forward(config, ops, params, aux, tokens, train):
    """The forward pass as flops.py counts it (under ``jax.eval_shape``; it
    is never compiled at the cell's size)."""
    return network(config, ops, params, tokens, looped=False), aux


def loss(config, ops, params, aux, batch):
    """The loss as it is trained (``network(..., looped=True)``)."""
    tokens, targets, weight = batch
    logits = network(config, ops, params, tokens, looped=True)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.sum(picked * weight) / weight.size, aux
