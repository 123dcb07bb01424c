"""Family ``keye_dsa``: a causal decoder language model whose attention sees,
for each query, the keys a learned indexer picks, over a mixture-of-experts
feed-forward (the language model of Keye-VL-2.0-30B-A3B,
https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B, ``model_type:
KeyeVL2``; the selection is DeepSeek Sparse Attention's, as published with
DeepSeek-V3.2-Exp), as one chip of an expert-parallel deployment holds it.
The vision tower is not part of it: sequences are text.

The equations
-------------
Input: ``tokens`` ``[B, L]``, ids of the held slice of the vocabulary;
positions ``(3, L)``, the three rows equal ``0..L-1`` for text.

Layer ``l``: ``h = x + Attn(RMSNorm(x))``, ``x' = h + MoE(RMSNorm(h))``;
after the last layer a final RMSNorm; logits = untied head over all ``L``
rows.  RMSNorm: ``x / sqrt(mean(x^2) + eps) * gamma``.

Main attention: ``q, k, v`` without bias, ``num_attention_heads`` query to
``num_key_value_heads`` key/value heads of ``head_dim``; RMSNorm over each
head's dimensions of ``q`` and of ``k`` (``assumed``); rotary embedding,
rotate-half pairing, base ``rope_theta``, in sections ``mrope_section``
``[16, 24, 24]``: frequency ``i`` of the 64 takes its angle from position row
0 for ``i < 16``, row 1 for ``16 <= i < 40``, row 2 for ``i >= 40``; scale
``1 / sqrt(head_dim)``.

Indexer (``sa_config``), on ``u = stop_gradient(RMSNorm(x))``, the rows the
attention block is given: ``qI = W_qI u`` as ``indexer_num_heads`` heads of
``indexer_head_dim``; ``kI = LayerNorm(W_kI u)`` (one key head shared by them;
gamma, beta; eps 1e-6); rotary on all dimensions of ``qI`` and ``kI`` at
position row 0, same base; ``w = (W_w u) * indexer_num_heads^-1/2 *
indexer_head_dim^-1/2``; ``I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])``
for ``s <= t`` (a zero score is +0).  ``S_t``: the ``min(t + 1, topk)`` keys
``s <= t`` of largest ``I[t, s]``, ties to the lower ``s`` (what
``jax.lax.top_k`` gives): here the keys whose rank in the row's stable
descending order is under ``topk``.

Sparse attention: ``o[t, h] = sum_{s in S_t} softmax_{s in S_t}(q[t, h] .
k[s, g(h)] / sqrt(head_dim)) v[s, g(h)]``.

Indexer loss, per layer: ``pbar[t, s] = stop_gradient(mean_h P[t, h, s])``
for ``s in S_t``, ``P`` the softmax above; ``LI = sum_t sum_{s in S_t}
pbar[t, s] * (log pbar[t, s] - log softmax_{s in S_t}(I[t, .])[s]) / (B *
L)``.  The indexer's three matrices and its LayerNorm get a gradient from
``LI`` alone; nothing else gets one from it.

MoE: ``p = softmax(y W_r)`` over all ``num_experts_total`` experts in
float32, the ``num_experts_per_tok`` largest, their weights renormalised to
sum 1 (``norm_topk_prob``); expert ``e``: ``W_down(silu(W_gate y) * W_up
y)``; output ``sum_e w_e expert_e(y)`` over the chosen experts **that are
held here** (``num_experts`` of them, from ``first_expert``).  What the others
would add is left out, as in the program.  No token is dropped.

Loss (``batch = (tokens, targets, weight)``, from
``generators/next_token.py``): ``sum_i weight_i CE(logits_i, targets_i) / (B
* L) + indexer_loss_weight * sum_layers LI``.

What is counted and what is trained
-----------------------------------
``forward`` is what ``flops.py`` walks under ``jax.eval_shape``, counting
every ``ops.einsum`` from its shapes, so its loops are Python's and its shapes
those of the required work: by chunks of ``CHUNK`` (128) queries, the index
scores of each against the keys up to its own end (1.6% more pairs than the
causal ``L (L + 1) / 2`` at ``L`` 8192); the attention of a chunk that ends
at or before ``topk`` against those same keys, of a later one against each
query's own ``topk`` keys, gathered (0.9% more pairs than ``k (k + 1) / 2 +
(L - k) k``); ``pbar`` from the probabilities the attention has; the experts
over the pairs routed here in a buffer of the even load where the
configuration says ``"moe_reference_load": "even"`` (every pair where it says
nothing), a load beyond it making the logits NaN; the head over all rows.

``loss`` is what is trained and compared with the program: the same
``_attend_chunk`` and ``_expert``, looped by ``lax.scan`` / ``lax.map`` (one
layer's program over the layers, one chunk's over the chunks against all keys
under the picked pairs as a mask, one expert's over the held experts and all
rows), each layer, chunk and expert recomputed in the backward pass
(``jax.checkpoint``) so that three steps at the timed sizes fit the chip.
tests/test_sparse_causal_lm.py holds the two equal.

``picks``, ``attends``, ``indexer_input``, ``index_loss`` and ``lands_here`` are
the rules a check replaces to plant a fault (benchmark/checks/faults_keye.py); nothing
here reads a switch.

Nothing here imports the program under test.
"""
from __future__ import annotations

import math
from collections import OrderedDict

import jax
import jax.numpy as jnp

CHUNK = 128          # queries to a chunk of index scores and attention
LAYER_NORM_EPS = 1e-6


# -- the rules a check may replace -------------------------------------------

def picks(scores, causal, topk):
    """Which keys each query attends: ``scores`` (B, T, n) float32 index
    scores, ``causal`` (1, T, n) whether the key is at or before the query.
    The keys whose rank in the row's stable descending order (equal scores
    in the order of ``s``) is under ``topk``."""
    order = jnp.argsort(-jnp.where(causal, scores, -jnp.inf), axis=-1,
                        stable=True)
    rank = jnp.argsort(order, axis=-1)
    return (rank < topk) & causal


def attends(valid, causal):
    """Which keys the attention and the indexer's loss see of the picked
    ones ``valid`` (B, T, n): those, and no other."""
    return valid


def indexer_input(u):
    """What the indexer reads of the attention block's input: its value,
    with no gradient back."""
    return jax.lax.stop_gradient(u)


def index_loss(target, log_index, valid):
    """KL(target || softmax of the index scores) over the picked keys,
    summed over the rows; ``target`` is a constant."""
    log_target = jnp.log(jnp.where(target > 0, target, 1.0))
    return jnp.sum(jnp.where(valid, target * (log_target - log_index), 0.0))


def lands_here(local, held):
    """Which (token, slot) pairs this chip computes: ``local`` is the chosen
    expert's id less the first held one's."""
    return (local >= 0) & (local < held)


# -- sizes, shapes, input ------------------------------------------------------

def _sizes(config):
    deployment = config.get("deployment", {})
    indexer = config["sa_config"]
    return {
        "hidden": config["hidden_size"], "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"], "head_dim": config["head_dim"],
        "width": config["moe_intermediate_size"], "held": config["num_experts"],
        "experts": deployment.get("num_experts_total", config["num_experts"]),
        "first": deployment.get("first_expert", 0),
        "top_k": config["num_experts_per_tok"],
        "layers": config["num_hidden_layers"], "vocab": config["vocab_size"],
        "eps": config["rms_norm_eps"], "rope": float(config["rope_theta"]),
        "sections": tuple((config.get("rope_scaling") or {}).get(
            "mrope_section") or (config["head_dim"] // 2, 0, 0)),
        "index_heads": indexer["indexer_num_heads"],
        "index_dim": indexer["indexer_head_dim"], "topk": indexer["topk"],
        "index_loss_weight": float(config.get("indexer_loss_weight", 1.0)),
    }


def param_shapes(config):
    """Names are those of the program's Gluon blocks
    (``mxnet_tpu/gluon/model_zoo/sparse_causal_lm.py``) behind the network's
    prefix.  The held experts' matrices are stacked in 2-D leaves, so that
    ``xavier_init`` (which reads a 3-D leaf as a convolution) takes the
    stacked fan."""
    s = _sizes(config)
    d, hd = s["hidden"], s["head_dim"]
    shapes = OrderedDict([("embed_weight", (s["vocab"], d))])
    for i in range(s["layers"]):
        p = "layer%d_" % i
        shapes.update([
            (p + "attn_norm_gamma", (d,)),
            (p + "attn_q_weight", (s["heads"] * hd, d)),
            (p + "attn_k_weight", (s["kv_heads"] * hd, d)),
            (p + "attn_v_weight", (s["kv_heads"] * hd, d)),
            (p + "attn_o_weight", (d, s["heads"] * hd)),
            (p + "attn_q_norm_gamma", (hd,)),
            (p + "attn_k_norm_gamma", (hd,)),
            (p + "attn_index_q_weight", (s["index_heads"] * s["index_dim"], d)),
            (p + "attn_index_k_weight", (s["index_dim"], d)),
            (p + "attn_index_w_weight", (s["index_heads"], d)),
            (p + "attn_index_k_norm_gamma", (s["index_dim"],)),
            (p + "attn_index_k_norm_beta", (s["index_dim"],)),
            (p + "moe_norm_gamma", (d,)),
            (p + "moe_router_weight", (s["experts"], d)),
            (p + "moe_gate_weight", (s["held"] * s["width"], d)),
            (p + "moe_up_weight", (s["held"] * s["width"], d)),
            (p + "moe_down_weight", (s["held"] * d, s["width"])),
        ])
    shapes.update([("final_norm_gamma", (d,)),
                   ("head_weight", (s["vocab"], d))])
    return shapes


def example_input(config, traffic):
    return (jax.ShapeDtypeStruct((1, traffic["seq_len"]), jnp.int32),)


# -- the layers ------------------------------------------------------------------

def rms_norm(x, gamma, eps):
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * inv).astype(x.dtype) * gamma.astype(x.dtype)


def layer_norm(x, gamma, beta, eps):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    out = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return out.astype(x.dtype) * gamma.astype(x.dtype) + beta.astype(x.dtype)


def rotary(x, positions, base, sections=None):
    """``x``: (..., T, D); rotate-half pairing over all D.  ``positions``:
    (T,), or (3, T) with the frequencies' ``sections``."""
    half = x.shape[-1] // 2
    inv_freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    positions = positions.astype(jnp.float32)
    if positions.ndim == 2:
        row = [r for r, n in enumerate(sections) for _ in range(n)]
        positions = positions[jnp.asarray(row), :].T          # (T, D/2)
    else:
        positions = positions[:, None]
    angle = positions * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def index_scores(ops, index_q, index_k, weights):
    """``I[b, t, s]``, float32: ``index_q`` (B, J, T, D), ``index_k``
    (B, n, D), ``weights`` (B, T, J)."""
    products = ops.einsum("bjtd,bsd->bjts", index_q, index_k)
    scores = jnp.sum(jax.nn.relu(products.astype(jnp.float32))
                     * jnp.swapaxes(weights, 1, 2).astype(jnp.float32)[..., None],
                     axis=1)
    return jnp.where(scores == 0, 0.0, scores)


def _attend_chunk(s, ops, rows, q, index_q, weights, k, v, index_k, gather,
                  given=None):
    """One chunk of queries at positions ``rows`` (T,) against the keys
    handed over (their positions start at 0): ``q`` (B, kv, g, T, hd),
    ``index_q`` (B, J, T, di), ``weights`` (B, T, J), ``k``/``v`` (B, kv, n,
    hd), ``index_k`` (B, n, di).  ``gather``: each query against its own
    ``topk`` keys, gathered, and not against all ``n`` under a mask.
    ``given`` (B, T, n) takes the place of the indexer's picks.  Returns
    (rows (B, kv, g, T, hd), the chunk's part of the indexer loss's sum)."""
    n, scale = k.shape[2], 1.0 / math.sqrt(s["head_dim"])
    scores = index_scores(ops, index_q, index_k, weights)      # (B, T, n)
    causal = (jnp.arange(n)[None, :] <= rows[:, None])[None]
    valid = attends(picks(scores, causal, s["topk"]) if given is None
                    else given, causal)
    if gather:
        at = jnp.argsort(~valid, axis=-1, stable=True)[..., :s["topk"]]
        valid = jnp.take_along_axis(valid, at, axis=-1)
        scores = jnp.take_along_axis(scores, at, axis=-1)
        own = at[:, None, :, :, None]                          # (B,1,T,K,1)
        k_own = jnp.take_along_axis(k[:, :, None], own, axis=3)
        v_own = jnp.take_along_axis(v[:, :, None], own, axis=3)
        att = ops.einsum("bhgtd,bhtkd->bhgtk", q, k_own)
    else:
        att = ops.einsum("bhgtd,bhkd->bhgtk", q, k)
    keep = valid[:, None, None]
    att = jnp.where(keep, att.astype(jnp.float32) * scale, -1e30)
    prob = jnp.where(keep, jax.nn.softmax(att, axis=-1), 0.0)
    if gather:
        out = ops.einsum("bhgtk,bhtkd->bhgtd", prob.astype(v.dtype), v_own)
    else:
        out = ops.einsum("bhgtk,bhkd->bhgtd", prob.astype(v.dtype), v)
    target = jax.lax.stop_gradient(jnp.mean(prob, axis=(1, 2)))
    log_index = jax.nn.log_softmax(jnp.where(valid, scores, -1e30), axis=-1)
    return out, index_loss(target, log_index, valid)


def _projections(s, ops, params, prefix, x):
    """(q (B, kv, g, L, hd), k, v (B, kv, L, hd), the indexer's queries
    (B, J, L, di), its keys (B, L, di) and its heads' weights (B, L, J)) of
    the layer's normed input ``x`` (B, L, hidden)."""
    B, L, _ = x.shape
    hd, kv, heads = s["head_dim"], s["kv_heads"], s["heads"]
    position = jnp.arange(L)
    positions = jnp.stack([position] * 3)

    def project(x, name, count, dim):
        t = ops.einsum("bld,ed->ble", x, params[prefix + name + "_weight"])
        return t.reshape(B, L, count, dim)

    def main(name, count, norm):
        t = project(x, name, count, hd)
        if norm:
            t = rms_norm(t, params[prefix + name + "_norm_gamma"], s["eps"])
        t = t.transpose(0, 2, 1, 3)                         # (B, heads, L, hd)
        return rotary(t, positions, s["rope"], s["sections"]) if norm else t

    q = main("attn_q", heads, True).reshape(B, kv, heads // kv, L, hd)
    k, v = main("attn_k", kv, True), main("attn_v", kv, False)

    u = indexer_input(x)
    index_q = rotary(project(u, "attn_index_q", s["index_heads"],
                             s["index_dim"]).transpose(0, 2, 1, 3),
                     position, s["rope"])
    index_k = rotary(layer_norm(
        project(u, "attn_index_k", 1, s["index_dim"])[:, :, 0],
        params[prefix + "attn_index_k_norm_gamma"],
        params[prefix + "attn_index_k_norm_beta"], LAYER_NORM_EPS),
        position, s["rope"])
    weights = project(u, "attn_index_w", s["index_heads"], 1)[..., 0] * (
        s["index_heads"] ** -0.5 * s["index_dim"] ** -0.5)
    return q, k, v, index_q, index_k, weights


def attention(s, ops, params, prefix, x, looped, given=None):
    """``x``: (B, L, hidden), the layer's normed input.  Returns (the
    block's rows, the indexer loss ``LI``).  ``given`` (B, L, L), where
    handed over, are the picked pairs to use."""
    B, L, _ = x.shape
    chunk = CHUNK if L % CHUNK == 0 else L
    q, k, v, index_q, index_k, weights = _projections(s, ops, params, prefix,
                                                      x)
    attend = _attend_looped if looped else _attend_by_shapes
    out, index_sum = attend(s, ops, q, index_q, weights, k, v, index_k,
                            chunk, given)
    out = out.reshape(B, s["heads"], L, s["head_dim"]).transpose(0, 2, 1, 3)
    rows = ops.einsum("ble,de->bld", out.reshape(B, L, -1),
                      params[prefix + "attn_o_weight"])
    return rows, index_sum / (B * L)


def selected_pairs(s, ops, params, prefix, x):
    """The pairs the indexer picks for the layer's normed input ``x``:
    (B, L, L) booleans, by chunks of queries."""
    B, L, _ = x.shape
    chunk = CHUNK if L % CHUNK == 0 else L
    _, _, _, index_q, index_k, weights = _projections(s, ops, params, prefix,
                                                      x)
    key_at = jnp.arange(L)[None, :]

    def one(args):
        lo, index_q_c, weights_c = args
        causal = (key_at <= (lo + jnp.arange(chunk))[:, None])[None]
        return picks(index_scores(ops, index_q_c, index_k, weights_c),
                     causal, s["topk"])

    n = L // chunk
    found = jax.lax.map(one, (
        jnp.arange(n) * chunk,
        jnp.moveaxis(index_q.reshape(B, -1, n, chunk, s["index_dim"]), 2, 0),
        jnp.moveaxis(weights.reshape(B, n, chunk, -1), 1, 0)))
    return jnp.moveaxis(found, 0, 1).reshape(B, L, L)


def _attend_by_shapes(s, ops, q, index_q, weights, k, v, index_k, chunk,
                      given):
    """The layout flops.py counts: every chunk against the keys up to its
    own end, and past ``topk`` against each query's own keys alone."""
    L = q.shape[3]
    outs, total = [], 0.0
    for lo in range(0, L, chunk):
        hi = lo + chunk
        out, part = _attend_chunk(
            s, ops, jnp.arange(lo, hi), q[:, :, :, lo:hi],
            index_q[:, :, lo:hi], weights[:, lo:hi], k[:, :, :hi],
            v[:, :, :hi], index_k[:, :hi], hi > s["topk"],
            None if given is None else given[:, lo:hi, :hi])
        outs.append(out)
        total = total + part
    return jnp.concatenate(outs, axis=3), total


def _attend_looped(s, ops, q, index_q, weights, k, v, index_k, chunk, given):
    """The layout that is trained: one chunk's program, looped over the
    chunks, each against all keys under its picked pairs as a mask."""
    B, kv, group, L, hd = q.shape
    n = L // chunk

    def chunks(t, axis):    # the axis of L -> (n, ..., chunk, ...)
        shape = t.shape[:axis] + (n, chunk) + t.shape[axis + 1:]
        return jnp.moveaxis(t.reshape(shape), axis, 0)

    @jax.checkpoint
    def one(args):
        lo, q_c, index_q_c, weights_c, given_c = args
        return _attend_chunk(s, ops, lo + jnp.arange(chunk), q_c, index_q_c,
                             weights_c, k, v, index_k, False, given_c)

    out, parts = jax.lax.map(one, (
        jnp.arange(n) * chunk, chunks(q, 3), chunks(index_q, 2),
        chunks(weights, 1), None if given is None else chunks(given, 1)))
    return jnp.moveaxis(out, 0, 3).reshape(B, kv, group, L, hd), \
        jnp.sum(parts)


def moe(s, ops, params, prefix, y, pairs, looped):
    """``y``: (T, hidden).  Returns (the held experts' part, overflow)."""
    held, width, d = s["held"], s["width"], y.shape[1]
    logits = ops.einsum("td,ed->te", y, params[prefix + "moe_router_weight"])
    prob = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weight, expert = jax.lax.top_k(prob, s["top_k"])
    weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
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
    """(logits (B, L, vocab), the layers' summed indexer loss), as trained
    (``looped``) or as counted.  Where the counted buffer overflows, the
    logits are NaN."""
    s = _sizes(config)
    B, L = tokens.shape
    pairs = reference_pairs(config, B * L)
    x = params["embed_weight"].astype(ops.dtype)[tokens]

    def layer(x, weights, prefix):
        rows, index_part = attention(
            s, ops, weights, prefix,
            rms_norm(x, weights[prefix + "attn_norm_gamma"], s["eps"]), looped)
        h = x + rows
        y = rms_norm(h, weights[prefix + "moe_norm_gamma"], s["eps"])
        out, overflow = moe(s, ops, weights, prefix, y.reshape(B * L, -1),
                            pairs, looped)
        return h + out.reshape(h.shape), index_part, overflow

    overflow, index_total = False, 0.0
    if looped:
        # one layer's program, looped over the layers: step i picks layer
        # i's leaves (select_n copies one layer, and its transpose adds the
        # layer's gradient into that layer's leaves alone: nothing is stacked)
        names = [k[len("layer0_"):] for k in params if k.startswith("layer0_")]

        def step(x, i):
            weights = {"layer_" + n: jax.lax.select_n(
                i, *[params["layer%d_%s" % (j, n)]
                     for j in range(s["layers"])]) for n in names}
            x, index_part, _ = layer(x, weights, "layer_")
            return x, index_part

        x, parts = jax.lax.scan(jax.checkpoint(step), x,
                                jnp.arange(s["layers"]))
        index_total = jnp.sum(parts)
    else:
        for i in range(s["layers"]):
            x, index_part, over = layer(x, params, "layer%d_" % i)
            index_total = index_total + index_part
            overflow = overflow | over
    x = rms_norm(x, params["final_norm_gamma"], s["eps"])
    logits = ops.einsum("bld,vd->blv", x, params["head_weight"])
    return jnp.where(overflow, jnp.nan, logits), index_total


def forward(config, ops, params, aux, tokens, train):
    """The forward pass as flops.py counts it (under ``jax.eval_shape``; it
    is never compiled at the cell's size)."""
    return network(config, ops, params, tokens, looped=False)[0], aux


def loss(config, ops, params, aux, batch):
    """The loss as it is trained (``network(..., looped=True)``)."""
    tokens, targets, weight = batch
    logits, index_total = network(config, ops, params, tokens, looped=True)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    language = -jnp.sum(picked * weight) / weight.size
    return language + _sizes(config)["index_loss_weight"] * index_total, aux
