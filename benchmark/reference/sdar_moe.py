"""Family ``sdar_moe``: a decoder language model trained by block diffusion
over a mixture-of-experts feed-forward (SDAR-30B-A3B-Chat,
https://huggingface.co/JetLM/SDAR-30B-A3B-Chat, ``model_type: sdar_moe``),
as one chip of an expert-parallel deployment holds it.

The equations
-------------
Input: one sequence of ``L`` clean tokens ``x0`` and its noised copy ``xt``.
The network sees ``R = 2L`` rows, ``tokens = [xt; x0]``, at positions
``[0..L-1, 0..L-1]`` (a noised token has its clean twin's position);
``b(i) = floor(i / block_length)`` inside each copy.

Layer ``l``: ``h = x + Attn(RMSNorm(x))``, ``x' = h + MoE(RMSNorm(h))``;
after the last layer a final RMSNorm; logits = head over the **noised rows
only**.  RMSNorm: ``x / sqrt(mean(x^2) + eps) * gamma``.

Attention: ``q, k, v`` without bias; RMSNorm over each head's ``head_dim``
dimensions of ``q`` and of ``k`` (as the Qwen3-MoE code from which
``sdar_moe`` derives: ``assumed``); rotary embedding on all ``head_dim``
dimensions (rotate-half pairing), base ``rope_theta``; ``num_attention_heads
/ num_key_value_heads`` query heads to one key/value head; scale
``1 / sqrt(head_dim)``; softmax over the keys the mask allows.  Mask: a noised
query ``i`` sees noised keys ``j`` with ``b(j) = b(i)`` (both ways inside the
block) and clean keys with ``b(j) < b(i)``; a clean query ``i`` sees clean
keys with ``b(j) <= b(i)`` and no noised key.

MoE: ``p = softmax(y W_r)`` over all ``num_experts_total`` experts in float32,
the ``num_experts_per_tok`` largest, their weights renormalised to sum 1
(``norm_topk_prob``); expert ``e``: ``W_down(silu(W_gate y) * W_up y)``;
output ``sum_e w_e expert_e(y)`` over the chosen experts **that are held
here** (``num_experts`` of them, from ``first_expert``).  What the others
would add is left out, as in the program, and that partial sum goes on to the
next layer.  No token is dropped.

Loss (``batch = (tokens, targets, weight)``, from
``generators/block_diffusion.py``): each block drew ``t_b`` uniform on
``[t_min, 1]`` and each of its positions was replaced in ``xt`` by the mask id
with probability ``t_b``; ``weight = masked / t_b``;
``loss = sum_i weight_i CE(logits_i, x0_i) / (batch * L)``.

What is counted and what is trained
-----------------------------------
``forward`` is what ``flops.py`` walks under ``jax.eval_shape``, counting every
``ops.einsum`` from its shapes, so its loops are Python's and its shapes those
of the required work, not of a dense formulation (4 times it): attention by
chunks of ``CHUNK`` (128) positions, each against the clean keys up to its own
end (6% more pairs than are visible at ``L`` 4096, ``block_length`` 4); the
experts over the pairs routed here, each pair with its own expert's matrices,
in a buffer of the even load (``rows * top_k * held / experts`` pairs) where
the configuration says ``"moe_reference_load": "even"`` and of every pair
where it says nothing (the tiny test sizes); the head over the noised rows.
**A load beyond the buffer is no silent drop: the logits come out NaN.**

``loss`` is what is trained and compared with the program: the same
``_attend_chunk`` and ``_expert``, looped by ``lax.scan`` / ``lax.map`` (one
layer's program over the layers, one chunk's over the chunks against all
clean keys, one expert's over the held experts and all rows, the mask and the
weights doing the rest), because some 800 products a layer of their own shapes
are more than the TPU's compiler takes in a run's time.  Each layer, chunk and
expert is recomputed in the backward pass (``jax.checkpoint``) so that three
steps at the timed sizes fit the chip.  tests/test_block_diffusion.py holds
the two equal, logits and gradients.

``noised_sees_clean`` and ``lands_here`` are the two rules a check replaces to
plant a fault (benchmark/checks/faults_sdar.py); nothing here reads a switch.

Nothing here imports the program under test.
"""
from __future__ import annotations

import math
from collections import OrderedDict

import jax
import jax.numpy as jnp

CHUNK = 128          # positions to a chunk of attention


def noised_sees_clean(clean_block, q_block):
    """Which clean keys a noised query sees: those of earlier blocks."""
    return clean_block < q_block


def lands_here(local, held):
    """Which (token, slot) pairs this chip computes: ``local`` is the chosen
    expert's id less the first held one's."""
    return (local >= 0) & (local < held)


def _sizes(config):
    deployment = config.get("deployment", {})
    return {
        "hidden": config["hidden_size"], "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"], "head_dim": config["head_dim"],
        "width": config["moe_intermediate_size"], "held": config["num_experts"],
        "experts": deployment.get("num_experts_total", config["num_experts"]),
        "first": deployment.get("first_expert", 0),
        "top_k": config["num_experts_per_tok"],
        "layers": config["num_hidden_layers"], "vocab": config["vocab_size"],
        "eps": config["rms_norm_eps"], "rope": float(config["rope_theta"]),
        "block": config["block_length"],
    }


def param_shapes(config):
    """Names are those of the program's Gluon blocks
    (``mxnet_tpu/gluon/model_zoo/block_diffusion.py``) behind the network's
    prefix.  The held experts' matrices are stacked in 2-D leaves, so that
    ``xavier_init`` (which reads a 3-D leaf as a convolution) takes the
    stacked fan: their magnitude is then 2.26 (gate, up) and 3.45 (down)
    times smaller than per-expert Xavier at 16 experts of 768 x 2048."""
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
    return (jax.ShapeDtypeStruct((1, 2 * traffic["seq_len"]), jnp.int32),)


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


def _softmax_over(scores, keeps):
    """Joint softmax over the last axes of several score arrays, each under
    its mask; returns one probability array per score array."""
    masked = [jnp.where(k, s.astype(jnp.float32), -1e30)
              for s, k in zip(scores, keeps)]
    top = masked[0].max(axis=-1, keepdims=True)
    for m in masked[1:]:
        top = jnp.maximum(top, m.max(axis=-1, keepdims=True))
    exps = [jnp.where(k, jnp.exp(m - top), 0.0) for m, k in zip(masked, keeps)]
    total = sum(e.sum(axis=-1, keepdims=True) for e in exps)
    return [e / total for e in exps]


def attention(s, ops, params, prefix, x, looped):
    """``x``: (B, 2L, hidden), the noised rows then the clean ones."""
    B, R, _ = x.shape
    L, hd, kv = R // 2, s["head_dim"], s["kv_heads"]
    chunk = CHUNK if L % CHUNK == 0 else L
    if chunk % s["block"]:
        raise ValueError("block_length %d does not divide the chunk of %d "
                         "positions" % (s["block"], chunk))
    positions = jnp.concatenate([jnp.arange(L), jnp.arange(L)])

    def project(name, heads, norm):
        t = ops.einsum("brd,ed->bre", x, params[prefix + name + "_weight"])
        t = t.reshape(B, R, heads, hd)
        if norm:
            t = rms_norm(t, params[prefix + name + "_norm_gamma"], s["eps"])
        t = t.transpose(0, 2, 1, 3)                       # (B, heads, R, hd)
        return rotary(t, positions, s["rope"]) if norm else t

    q = project("attn_q", s["heads"], True).reshape(
        B, kv, s["heads"] // kv, R, hd)
    k = project("attn_k", kv, True)
    v = project("attn_v", kv, False)
    attend = _attend_looped if looped else _attend_by_shapes
    out = attend(s, ops, q, k, v, chunk)                    # (B,kv,g,R,hd)
    out = out.reshape(B, s["heads"], R, hd).transpose(0, 2, 1, 3)
    return ops.einsum("bre,de->brd", out.reshape(B, R, s["heads"] * hd),
                      params[prefix + "attn_o_weight"])


def _attend_chunk(s, ops, lo, q_noised, q_clean, k_clean, v_clean, k_own,
                  v_own):
    """One chunk of positions from ``lo``: its clean queries against the
    clean keys handed over (their positions start at 0), its noised queries
    against those and the chunk's own noised keys.  The query heads that
    share a key/value head are rows of one product, and so are the noised
    and the clean queries against the clean keys."""
    B, kv, group, chunk, hd = q_noised.shape
    bl, rows = s["block"], group * chunk
    scale = 1.0 / math.sqrt(hd)
    q_block = jnp.tile(((lo + jnp.arange(chunk)) // bl)[:, None], (group, 1))
    clean_block = (jnp.arange(k_clean.shape[2]) // bl)[None, :]
    own_block = ((lo + jnp.arange(chunk)) // bl)[None, :]
    both = jnp.concatenate([q_noised.reshape(B, kv, rows, hd),
                            q_clean.reshape(B, kv, rows, hd)], axis=2)

    # clean keys: of earlier blocks for a noised query, of its own and
    # earlier blocks for a clean one; noised keys: of a noised query's block
    s_clean = ops.einsum("bhqd,bhkd->bhqk", both, k_clean) * scale
    s_own = ops.einsum("bhqd,bhkd->bhqk", both[:, :, :rows], k_own) * scale
    p_noised, p_own = _softmax_over([s_clean[:, :, :rows], s_own],
                                    [noised_sees_clean(clean_block, q_block),
                                     own_block == q_block])
    p_clean, = _softmax_over([s_clean[:, :, rows:]],
                             [clean_block <= q_block])
    out = ops.einsum("bhqk,bhkd->bhqd", jnp.concatenate(
        [p_noised, p_clean], axis=2).astype(v_own.dtype), v_clean)
    out_noised = out[:, :, :rows] + ops.einsum(
        "bhqk,bhkd->bhqd", p_own.astype(v_own.dtype), v_own)
    shape = (B, kv, group, chunk, hd)
    return out_noised.reshape(shape), out[:, :, rows:].reshape(shape)


def _attend_by_shapes(s, ops, q, k, v, chunk):
    """The layout flops.py counts: every chunk against the keys up to its
    own end and no further."""
    L = q.shape[3] // 2
    noised, clean = [], []
    for lo in range(0, L, chunk):
        hi = lo + chunk
        out = _attend_chunk(
            s, ops, lo, q[:, :, :, lo:hi], q[:, :, :, L + lo:L + hi],
            k[:, :, L:L + hi], v[:, :, L:L + hi], k[:, :, lo:hi],
            v[:, :, lo:hi])
        noised.append(out[0])
        clean.append(out[1])
    return jnp.concatenate(noised + clean, axis=3)


def _attend_looped(s, ops, q, k, v, chunk):
    """The layout that is trained: one chunk's program, looped over the
    chunks, each against all clean keys under the same mask."""
    B, kv, group, R, hd = q.shape
    L = R // 2
    n = L // chunk

    def chunks(t):          # (..., L, hd) -> (n, ..., chunk, hd)
        t = t.reshape(t.shape[:-2] + (n, chunk, hd))
        return jnp.moveaxis(t, -3, 0)

    k_clean, v_clean = k[:, :, L:], v[:, :, L:]

    @jax.checkpoint
    def one(args):
        lo, q_noised, q_clean, k_own, v_own = args
        return _attend_chunk(s, ops, lo, q_noised, q_clean, k_clean, v_clean,
                             k_own, v_own)

    noised, clean = jax.lax.map(one, (
        jnp.arange(n) * chunk, chunks(q[:, :, :, :L]), chunks(q[:, :, :, L:]),
        chunks(k[:, :, :L]), chunks(v[:, :, :L])))

    def rows(t):            # (n, B, kv, g, chunk, hd) -> (B, kv, g, L, hd)
        return jnp.moveaxis(t, 0, 3).reshape(B, kv, group, L, hd)

    return jnp.concatenate([rows(noised), rows(clean)], axis=3)


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
    """Logits of the noised rows, (B, L, vocab), as trained (``looped``) or
    as counted.  Where the counted buffer overflows, the logits are NaN."""
    s = _sizes(config)
    B, R = tokens.shape
    pairs = reference_pairs(config, B * R)
    x = params["embed_weight"].astype(ops.dtype)[tokens]

    def layer(x, weights, prefix):
        h = x + attention(s, ops, weights, prefix,
                          rms_norm(x, weights[prefix + "attn_norm_gamma"],
                                   s["eps"]), looped)
        y = rms_norm(h, weights[prefix + "moe_norm_gamma"], s["eps"])
        out, overflow = moe(s, ops, weights, prefix, y.reshape(B * R, -1),
                            pairs, looped)
        return h + out.reshape(h.shape), overflow

    overflow = False
    if looped:
        # one layer's program, looped over the layers: step i picks layer
        # i's leaves (select_n copies one layer, and its transpose adds the
        # layer's gradient into that layer's leaves alone: nothing is stacked)
        names = [k[len("layer0_"):] for k in params if k.startswith("layer0_")]

        def step(x, i):
            weights = {"layer_" + n: jax.lax.select_n(
                i, *[params["layer%d_%s" % (j, n)]
                     for j in range(s["layers"])]) for n in names}
            return layer(x, weights, "layer_")[0], None

        x, _ = jax.lax.scan(jax.checkpoint(step), x,
                            jnp.arange(s["layers"]))
    else:
        for i in range(s["layers"]):
            x, over = layer(x, params, "layer%d_" % i)
            overflow = overflow | over
    x = rms_norm(x[:, :R // 2], params["final_norm_gamma"], s["eps"])
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
