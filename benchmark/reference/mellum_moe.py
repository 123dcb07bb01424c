"""Family ``mellum_moe``: a causal decoder language model whose attention
layers differ in the keys they see and in their rotary form, over a mixture of
experts in every layer (Mellum2-12B-A2.5B,
https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct, ``model_type:
mellum``), as one chip of an expert-parallel deployment holds it.

The equations
-------------
``u`` is a block's normed input, ``eps`` = ``rms_norm_eps``, no bias
anywhere.  RMSNorm: ``x / sqrt(mean(x^2) + eps) * gamma``.

Input: ``tokens`` ``[B, L]``, ids of the held slice of the vocabulary;
``x = E[tokens]``.  The layers held here are ``deployment.layers`` (published
indices; default the first ``num_hidden_layers``); layer ``i`` takes its kind
from ``layer_types[i]``:
``h = x + Attn_i(RMSNorm(x))``, ``x' = h + MoE(RMSNorm(h))``; after the last
layer RMSNorm, then the untied head ``logits = x W_head^T`` over all ``L``
rows.

Attention: ``q = W_q u`` as ``num_attention_heads`` heads of ``head_dim``,
``k, v`` as ``num_key_value_heads`` heads; RMSNorm over each head's
dimensions of ``q`` and of ``k`` (``assumed``); rotary embedding on all of
them, rotate-half pairing, positions ``0..L-1``; ``softmax(q k^T /
sqrt(head_dim))`` over the visible keys; ``W_o``.

* ``sliding_attention``: query ``i`` sees key ``j`` iff ``i - W < j <= i``,
  ``W`` = ``sliding_window`` (``assumed``: ``W`` keys counting itself).
* ``full_attention``: query ``i`` sees every key ``j <= i``.

Each kind's rotary is ``rope_parameters[kind]``, frequency ``p`` of ``P`` =
``head_dim / 2`` being ``theta^(-p/P)``, where ``rope_type`` is "default";
where it is "yarn" (``assumed``: as published with the family's ``rope_type:
yarn``), with ``D`` = ``head_dim``, ``O`` =
``original_max_position_embeddings``:
``c(r) = D ln(O / (2 pi r)) / (2 ln theta)``, ``low = floor(c(beta_fast))``,
``high = ceil(c(beta_slow))`` (18 and 35 at the published values),
``ramp_p = clip((p - low) / (high - low), 0, 1)``,
``inv_freq_p = theta^(-p/P) * ((1 - ramp_p) + ramp_p / factor)``, and
``cos`` and ``sin`` times ``m`` = ``attention_factor``, so that the scores
of such a layer are scaled by ``m^2``.

MoE: ``logits = W_r u`` over all ``num_experts_total`` experts in float32,
``p = softmax(logits)``; ``S_t`` = the ``num_experts_per_tok`` experts of
largest ``p``, ties to the lower id (``jax.lax.top_k``);
``w_e = p_e / sum_{e' in S_t} p_e'`` (``norm_topk_prob``); ``out = sum_{e in
S_t, e held} w_e * W2_e (silu(W1_e u) * W3_e u)``, width
``moe_intermediate_size``, over the chosen experts **that are held here**
(``num_experts`` of them, from ``first_expert``).  What the others would add
is left out, as in the program.  No token is dropped.

Loss (``batch = (tokens, targets, weight)``, from
``generators/next_token.py``): ``sum_i weight_i CE(logits_i, targets_i) / (B
* L)``.

What is counted and what is trained
-----------------------------------
``forward`` is what ``flops.py`` walks under ``jax.eval_shape``, counting
every ``ops.einsum`` from its shapes, so its loops are Python's and its shapes
those of the required work: attention by chunks of ``CHUNK`` (128) queries,
a full layer's chunk against the keys up to its own end, **a sliding layer's
against its band alone**, the ``W + CHUNK - 1`` keys from ``W - 1`` before
its first query to its last (12% more pairs than the window's at ``W``
1,024; a causal square would be 8.3 times them at ``L`` 16,384); the experts
over the pairs routed here in a buffer of the even load where the
configuration says ``"moe_reference_load": "even"`` (every pair where it
says nothing), a load beyond it making the logits NaN; the head over all
rows.

``loss`` is what is trained and compared with the program: the same
``_attend_chunk`` and ``_expert``, looped by ``lax.map`` / ``lax.scan`` (one
chunk's program over the chunks: a full layer's against all keys under the
causal mask, a sliding layer's against its band; one expert's over the held
experts and all rows), each layer, chunk and expert recomputed in the
backward pass (``jax.checkpoint``) so that three steps at the timed sizes fit
the chip.  The layers differ, so they are Python's loop.
tests/test_window_moe_lm.py holds the two equal.

``window_of``, ``rope_of``, ``frequency_scale``, ``attention_factor`` and
``lands_here`` are the rules a check replaces to plant a fault
(benchmark/checks/faults_mellum.py); nothing here reads a switch.

Nothing here imports the program under test.
"""
from __future__ import annotations

import math
from collections import OrderedDict

import jax
import jax.numpy as jnp

CHUNK = 128          # queries to a chunk of attention
KINDS = ("sliding_attention", "full_attention")


# -- the rules a check may replace -------------------------------------------

def window_of(kind, window):
    """How many keys up to itself a query of a layer of ``kind`` sees
    (``None``: all of them)."""
    return window if kind == "sliding_attention" else None


def rope_of(kind, parameters):
    """The rotary parameters of a layer of ``kind``, of ``rope_parameters``
    by kind."""
    return parameters[kind]


def frequency_scale(rope, dim):
    """The factor on each of the ``dim / 2`` rotary frequencies: YaRN's
    ramp, or 1."""
    half = dim // 2
    if rope.get("rope_type", "default") != "yarn":
        return jnp.ones((half,), jnp.float32)
    theta, original = float(rope["rope_theta"]), \
        float(rope["original_max_position_embeddings"])

    def c(rotations):
        return dim * math.log(original / (2 * math.pi * rotations)) \
            / (2 * math.log(theta))
    low = max(math.floor(c(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(c(float(rope["beta_slow"]))), dim - 1)
    if high == low:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return (1.0 - ramp) + ramp / float(rope["factor"])


def attention_factor(rope):
    """What ``cos`` and ``sin`` are multiplied by."""
    if rope.get("rope_type", "default") != "yarn":
        return 1.0
    return float(rope["attention_factor"])


def lands_here(local, held):
    """Which (token, slot) pairs this chip computes: ``local`` is the chosen
    expert's id less the first held one's."""
    return (local >= 0) & (local < held)


# -- sizes, shapes, input ----------------------------------------------------

def _sizes(config):
    deployment = config.get("deployment", {})
    heads = config["num_attention_heads"]
    kinds = tuple(config["layer_types"][i] for i in deployment.get("layers")
                  or range(config["num_hidden_layers"]))
    if any(kind not in KINDS for kind in kinds):
        raise ValueError("a layer is one of %r, not %r" % (KINDS, kinds))
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("%d layers held of num_hidden_layers %d"
                         % (len(kinds), config["num_hidden_layers"]))
    return {
        "hidden": config["hidden_size"], "heads": heads,
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config.get("head_dim") or config["hidden_size"] // heads,
        "kinds": kinds, "window": config["sliding_window"],
        "rope": config["rope_parameters"],
        "width": config["moe_intermediate_size"],
        "held": config["num_experts"],
        "experts": deployment.get("num_experts_total", config["num_experts"]),
        "first": deployment.get("first_expert", 0),
        "top_k": config["num_experts_per_tok"],
        "layers": config["num_hidden_layers"], "vocab": config["vocab_size"],
        "eps": config["rms_norm_eps"],
    }


def param_shapes(config):
    """Names are those of the program's Gluon blocks
    (``mxnet_tpu/gluon/model_zoo/window_moe_lm.py``) behind the network's
    prefix.  The held experts' matrices are stacked in 2-D leaves, so that
    ``xavier_init`` takes the stacked fan."""
    s = _sizes(config)
    d, hd = s["hidden"], s["head_dim"]
    shapes = OrderedDict([("embed_weight", (s["vocab"], d))])
    for i in range(s["layers"]):
        p = "layer%d_" % i
        shapes.update([
            (p + "operator_norm_gamma", (d,)),
            (p + "attn_q_weight", (s["heads"] * hd, d)),
            (p + "attn_k_weight", (s["kv_heads"] * hd, d)),
            (p + "attn_v_weight", (s["kv_heads"] * hd, d)),
            (p + "attn_o_weight", (d, s["heads"] * hd)),
            (p + "attn_q_norm_gamma", (hd,)),
            (p + "attn_k_norm_gamma", (hd,)),
            (p + "ffn_norm_gamma", (d,)),
            (p + "moe_router_weight", (s["experts"], d)),
            (p + "moe_gate_weight", (s["held"] * s["width"], d)),
            (p + "moe_up_weight", (s["held"] * s["width"], d)),
            (p + "moe_down_weight", (s["held"] * d, s["width"]))])
    shapes.update([("final_norm_gamma", (d,)),
                   ("head_weight", (s["vocab"], d))])
    return shapes


def example_input(config, traffic):
    return (jax.ShapeDtypeStruct((1, traffic["seq_len"]), jnp.int32),)


# -- the layers -------------------------------------------------------------

def rms_norm(x, gamma, eps):
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * inv).astype(x.dtype) * gamma.astype(x.dtype)


def rotary(x, positions, rope):
    """``x``: (..., T, D); rotate-half pairing over all D, under the rotary
    parameters ``rope``."""
    half = x.shape[-1] // 2
    theta = float(rope["rope_theta"])
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half) \
        * frequency_scale(rope, x.shape[-1])
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    m = attention_factor(rope)
    cos, sin = jnp.cos(angle) * m, jnp.sin(angle) * m
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _attend_chunk(s, ops, rows, keys, window, q, k, v):
    """One chunk of queries at positions ``rows`` (T,) against the keys at
    positions ``keys`` (n,) handed over (a position below 0 is padding):
    ``q`` (B, kv, g, T, hd), ``k``/``v`` (B, kv, n, hd)."""
    scale = 1.0 / math.sqrt(s["head_dim"])
    keep = (keys[None, :] <= rows[:, None]) & (keys[None, :] >= 0)
    if window is not None:
        keep &= keys[None, :] > rows[:, None] - window
    att = ops.einsum("bhgtd,bhkd->bhgtk", q, k)
    att = jnp.where(keep[None, None, None], att.astype(jnp.float32) * scale,
                    -1e30)
    prob = jax.nn.softmax(att, axis=-1)
    return ops.einsum("bhgtk,bhkd->bhgtd", prob.astype(v.dtype), v)


def _attend_looped(s, ops, q, k, v, chunk, window):
    """What is trained: one chunk's program over the chunks, a full layer's
    against all keys, a sliding layer's against its band of ``window +
    chunk - 1`` keys (the keys padded with ``window - 1`` rows in front)."""
    B, kv, g, L, hd = q.shape
    n = L // chunk
    band, pad = (L, 0) if window is None else (window + chunk - 1, window - 1)
    k, v = (jnp.pad(t, ((0, 0), (0, 0), (pad, 0), (0, 0))) for t in (k, v))

    @jax.checkpoint
    def one(args):
        lo, q_c = args
        at = 0 if window is None else lo    # padded row ``at``: ``at - pad``
        keys = at - pad + jnp.arange(band)
        k_c, v_c = (jax.lax.dynamic_slice_in_dim(t, at, band, axis=2)
                    for t in (k, v))
        return _attend_chunk(s, ops, lo + jnp.arange(chunk), keys, window,
                             q_c, k_c, v_c)

    out = jax.lax.map(one, (jnp.arange(n) * chunk, jnp.moveaxis(
        q.reshape(B, kv, g, n, chunk, hd), 3, 0)))
    return jnp.moveaxis(out, 0, 3).reshape(B, kv, g, L, hd)


def _attend_by_shapes(s, ops, q, k, v, chunk, window):
    """What flops.py counts: every chunk against the keys from the first
    that its first query sees to its own end."""
    L = q.shape[3]
    outs = []
    for lo in range(0, L, chunk):
        first = 0 if window is None else max(0, lo - window + 1)
        outs.append(_attend_chunk(
            s, ops, jnp.arange(lo, lo + chunk), jnp.arange(first, lo + chunk),
            window, q[:, :, :, lo:lo + chunk], k[:, :, first:lo + chunk],
            v[:, :, first:lo + chunk]))
    return jnp.concatenate(outs, axis=3)


def attention(s, ops, params, prefix, kind, x, looped):
    """``x``: (B, L, hidden), the layer's normed input."""
    B, L, _ = x.shape
    hd, kv, heads = s["head_dim"], s["kv_heads"], s["heads"]
    chunk = CHUNK if L % CHUNK == 0 else L
    positions = jnp.arange(L)
    rope = rope_of(kind, s["rope"])

    def project(name, count, norm):
        t = ops.einsum("bld,ed->ble", x, params[prefix + name + "_weight"])
        t = t.reshape(B, L, count, hd)
        if norm:
            t = rms_norm(t, params[prefix + name + "_norm_gamma"], s["eps"])
        t = t.transpose(0, 2, 1, 3)                         # (B, heads, L, hd)
        return rotary(t, positions, rope) if norm else t

    q = project("attn_q", heads, True).reshape(B, kv, heads // kv, L, hd)
    k, v = project("attn_k", kv, True), project("attn_v", kv, False)
    attend = _attend_looped if looped else _attend_by_shapes
    out = attend(s, ops, q, k, v, chunk, window_of(kind, s["window"]))
    out = out.reshape(B, heads, L, hd).transpose(0, 2, 1, 3)
    return ops.einsum("ble,de->bld", out.reshape(B, L, heads * hd),
                      params[prefix + "attn_o_weight"])


def route(s, ops, params, prefix, y, given=None):
    """(weights (T, top_k), expert ids (T, top_k)) of the tokens ``y``
    (T, hidden), over all experts.  ``given`` (T, top_k), where handed over,
    are the experts to use in the place of the router's picks; the weights
    are then the router's own of those."""
    logits = ops.einsum("td,ed->te", y, params[prefix + "moe_router_weight"])
    prob = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert = given
    if expert is None:
        _, expert = jax.lax.top_k(prob, s["top_k"])
    picked = jnp.take_along_axis(prob, expert, axis=-1)
    return picked / jnp.sum(picked, axis=-1, keepdims=True), expert


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

    def layer(kind, prefix):
        def run(x, weights):
            u = rms_norm(x, weights[prefix + "operator_norm_gamma"], s["eps"])
            h = x + attention(s, ops, weights, prefix, kind, u, looped)
            y = rms_norm(h, weights[prefix + "ffn_norm_gamma"],
                         s["eps"]).reshape(B * L, -1)
            out, overflow = moe(s, ops, weights, prefix, y, pairs, looped)
            return h + out.reshape(h.shape), overflow
        return run

    overflow = False
    for i, kind in enumerate(s["kinds"]):
        prefix = "layer%d_" % i
        run = layer(kind, prefix)
        weights = {k: v for k, v in params.items() if k.startswith(prefix)}
        if looped:      # as trained: the layer recomputed, nothing to overflow
            x = jax.checkpoint(lambda x, w, run=run: run(x, w)[0])(x, weights)
        else:
            x, over = run(x, weights)
            overflow = overflow | over
    x = rms_norm(x, params["final_norm_gamma"], s["eps"])
    logits = ops.einsum("bld,vd->blv", x, params["head_weight"])
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
