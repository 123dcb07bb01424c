"""Family ``nemotron_h``: a causal decoder language model whose layers are
single blocks of three kinds, Mamba-2 state-space mixers, mixtures of relu²
experts beside a shared expert, and grouped-query attention without rotary
(NVIDIA-Nemotron-3-Nano-30B-A3B,
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16,
``model_type: nemotron_h``), as one chip of an expert-parallel deployment
holds it.

The equations
-------------
``u`` is a block's normed input, ``eps`` = ``layer_norm_epsilon``, no bias
anywhere but the convolution's.  RMSNorm: ``x / sqrt(mean(x^2) + eps) *
gamma``.

Input: ``tokens`` ``[B, L]``, ids of the held slice of the vocabulary;
``x = E[tokens]``.  The layers held here are ``deployment.layers`` (published
indices; default the first ``num_hidden_layers``); layer ``i`` is ``x' = x +
Block_i(RMSNorm(x))``, the block chosen by ``hybrid_override_pattern[i]``.
After the last layer RMSNorm, then the untied head ``logits = x W_head^T``
over all ``L`` rows.

``M``, the Mamba-2 mixer: ``H`` = ``mamba_num_heads`` heads of ``P`` =
``mamba_head_dim``, ``N`` = ``ssm_state_size``, ``G`` = ``n_groups``,
``D_in = H P`` (``assumed``: ``expand`` is not read).

* ``[z, xBC, dt] = W_in u``, widths ``[D_in, D_in + 2 G N, H]``;
* ``xBC = silu(conv(xBC) + b_conv)``: a causal depthwise convolution of
  ``K`` = ``conv_kernel`` taps, row ``t`` reading rows ``t - K + 1 .. t``,
  zeros before the first row;
* ``xBC`` split into ``x`` (``H`` heads of ``P``), ``B`` and ``C`` (``G``
  groups of ``N`` each), head ``h`` reading group ``h // (H / G)``;
* ``dt = softplus(dt + dt_bias)`` (a head each), ``A = -exp(A_log)`` (a
  head each), ``a_t = exp(dt_t A)``;
* ``S_t = a_t S_{t-1} + dt_t x_t B_t^T`` (``P x N`` a head, ``S_{-1} =
  0``), ``y_t = S_t C_t + D x_t``, ``D`` a head each;
* ``y = RMSNorm_grouped(y * silu(z))``: the RMS over each of ``G`` groups of
  ``D_in / G`` channels, eps ``eps``, times a weight of ``D_in``
  (``norm_before_gate`` false);
* ``out = W_out y``.

``chunk_size`` says how the scan is split, not what it computes.

``*``, attention: ``q = W_q u`` as ``num_attention_heads`` heads of
``head_dim``, ``k, v`` as ``num_key_value_heads`` heads; causal
``softmax(q k^T / sqrt(head_dim))``, then ``W_o``.  No norm of q or k and no
rotary (``assumed``: the released code applies neither; ``rope_theta`` and
``partial_rotary_factor`` feed no equation).

``E``, the experts: ``logits = W_r u`` over all ``num_experts_total``
experts in float32, ``s = sigmoid(logits)``; ``S_t`` = the
``num_experts_per_tok`` experts of largest ``s + e_score_correction_bias``,
ties to the lower id; ``w_e = routed_scaling_factor * s_e / (sum_{S_t} s +
router_norm_eps)`` (``assumed``: the released router's 1e-20, DeepSeek-V3's
form; ``n_group`` and ``topk_group`` are 1: no group step); ``out = sum_{e in
S_t, e held} w_e W2_e relu(W1_e u)^2 + W2_sh relu(W1_sh u)^2``, routed width
``moe_intermediate_size``, shared width ``moe_shared_expert_intermediate_size``
(``n_shared_experts`` of them).  The held experts are ``n_routed_experts``
of them from ``first_expert``; what the others would add is left out, as in
the program.  The shared expert is computed whole.  No token is dropped.

Loss (``batch = (tokens, targets, weight)``, from
``generators/next_token.py``): ``sum_i weight_i CE(logits_i, targets_i) / (B
* L)``.

What is counted and what is trained
-----------------------------------
``forward`` is what ``flops.py`` walks under ``jax.eval_shape``, counting
every ``ops.einsum`` from its shapes.  The scan is Mamba-2's chunked dual
form ("SSD minimal"): each chunk's rows from its own rows through a
``chunk x chunk`` square (``C B^T`` once a group, then a head's decays), the
chunks' states, the states between chunks by a segment-sum matrix over the
chunks (**no sequential carry**, so that no fault of the program's carry can
be shared), and each chunk's rows from the state at its start.  Attention
is ``mellum_moe``'s by chunks of queries; the experts hold the pairs routed
here in a buffer of the even load where the configuration says
``"moe_reference_load": "even"`` (every pair where it says nothing), a load
beyond it making the logits NaN; the head covers all rows.

``loss`` is what is trained and compared with the program: the same blocks,
the attention's chunks and the experts looped by ``lax.map`` / ``lax.scan``
(one expert's program over all rows with the weights of the rows that did
not pick it 0), each layer recomputed in the backward pass
(``jax.checkpoint``).  tests/test_hybrid_ssm_lm.py holds the two equal.

``carry_of``, ``skip_of``, ``gate_of``, ``norm_groups``, ``conv_front``,
``query_key``, ``activation``, ``routed_scale``, ``lands_here`` and
``shared_of`` are the rules a check replaces to plant a fault
(benchmark/checks/faults_nemotron.py); nothing here reads a switch.

Nothing here imports the program under test.
"""
from __future__ import annotations

from collections import OrderedDict

import jax
import jax.numpy as jnp

from benchmark.reference.mellum_moe import (_attend_by_shapes, _attend_looped,
                                            rms_norm)

CHUNK = 128          # queries to a chunk of attention
KINDS = ("M", "E", "*")
ROUTER_NORM_EPS = 1e-6   # where the configuration gives no router_norm_eps


# -- the rules a check may replace -------------------------------------------

def carry_of(decays):
    """What goes from chunk to chunk: the segment-sum matrix of the chunks'
    decays, ``(..., chunks + 1, chunks + 1)``."""
    return decays


def skip_of(d_skip):
    """The skip ``D`` a head."""
    return d_skip


def gate_of(z):
    """What multiplies the scan's output before the norm."""
    return jax.nn.silu(z)


def norm_groups(groups):
    """Over how many groups of channels the gated norm takes its RMS."""
    return groups


def conv_front(taps):
    """How many zero rows lie before the sequence in the convolution: row
    ``t`` reads rows ``t - front .. t - front + taps - 1``."""
    return taps - 1


def query_key(s, q, k):
    """The queries and keys the scores are made of, from their projections
    ``(B, heads, L, head_dim)``: as they are, with no rotary and no norm."""
    return q, k


def activation(u):
    """An expert's, on its up projection."""
    return jnp.square(jax.nn.relu(u))


def routed_scale(scale):
    """What the routed experts' weights are multiplied by."""
    return scale


def lands_here(local, held):
    """Which (token, slot) pairs this chip computes: ``local`` is the chosen
    expert's id less the first held one's."""
    return (local >= 0) & (local < held)


def shared_of(out):
    """The shared expert's rows, as they join the routed ones."""
    return out


# -- sizes, shapes, input ----------------------------------------------------

def _sizes(config):
    deployment = config.get("deployment", {})
    pattern = config["hybrid_override_pattern"]
    kinds = tuple(pattern[i] for i in deployment.get("layers")
                  or range(config["num_hidden_layers"]))
    if any(kind not in KINDS for kind in kinds):
        raise ValueError("a layer is one of %r, not %r" % (KINDS, kinds))
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("%d layers held of num_hidden_layers %d"
                         % (len(kinds), config["num_hidden_layers"]))
    heads = config["num_attention_heads"]
    return {
        "hidden": config["hidden_size"], "heads": heads,
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config.get("head_dim") or config["hidden_size"] // heads,
        "kinds": kinds, "eps": config["layer_norm_epsilon"],
        # no equation reads it: a planted fault's rotary does
        "rope_theta": float(config.get("rope_theta", 10000)),
        "ssm_heads": config["mamba_num_heads"],
        "ssm_head_dim": config["mamba_head_dim"],
        "state": config["ssm_state_size"], "groups": config["n_groups"],
        "taps": config["conv_kernel"], "chunk": config["chunk_size"],
        "width": config["moe_intermediate_size"],
        "shared_width": config["n_shared_experts"]
        * config["moe_shared_expert_intermediate_size"],
        "held": config["n_routed_experts"],
        "experts": deployment.get("num_experts_total",
                                  config["n_routed_experts"]),
        "first": deployment.get("first_expert", 0),
        "top_k": config["num_experts_per_tok"],
        "scale": float(config["routed_scaling_factor"]),
        "router_eps": float(config.get("router_norm_eps", ROUTER_NORM_EPS)),
        "layers": config["num_hidden_layers"], "vocab": config["vocab_size"],
    }


def param_shapes(config):
    """Names are those of the program's Gluon blocks
    (``mxnet_tpu/gluon/model_zoo/hybrid_ssm_lm.py``) behind the network's
    prefix.  The held experts' matrices are stacked in 2-D leaves, so that
    ``xavier_init`` takes the stacked fan."""
    s = _sizes(config)
    d, hd = s["hidden"], s["head_dim"]
    inner = s["ssm_heads"] * s["ssm_head_dim"]
    conv = inner + 2 * s["groups"] * s["state"]
    shapes = OrderedDict([("embed_weight", (s["vocab"], d))])
    for i, kind in enumerate(s["kinds"]):
        p = "layer%d_" % i
        shapes[p + "norm_gamma"] = (d,)
        if kind == "M":
            shapes.update([
                (p + "ssm_in_weight", (inner + conv + s["ssm_heads"], d)),
                (p + "ssm_out_weight", (d, inner)),
                (p + "ssm_conv_weight", (conv, s["taps"])),
                (p + "ssm_conv_bias", (conv,)),
                (p + "ssm_dt_bias", (s["ssm_heads"],)),
                (p + "ssm_A_log", (s["ssm_heads"],)),
                (p + "ssm_D", (s["ssm_heads"],)),
                (p + "ssm_norm_gamma", (inner,))])
        elif kind == "*":
            shapes.update([
                (p + "attn_q_weight", (s["heads"] * hd, d)),
                (p + "attn_k_weight", (s["kv_heads"] * hd, d)),
                (p + "attn_v_weight", (s["kv_heads"] * hd, d)),
                (p + "attn_o_weight", (d, s["heads"] * hd))])
        else:
            shapes.update([
                (p + "moe_expert_bias", (s["experts"],)),
                (p + "moe_router_weight", (s["experts"], d)),
                (p + "moe_up_weight", (s["held"] * s["width"], d)),
                (p + "moe_down_weight", (s["held"] * d, s["width"])),
                (p + "moe_shared_up_weight", (s["shared_width"], d)),
                (p + "moe_shared_down_weight", (d, s["shared_width"]))])
    shapes.update([("final_norm_gamma", (d,)),
                   ("head_weight", (s["vocab"], d))])
    return shapes


def example_input(config, traffic):
    return (jax.ShapeDtypeStruct((1, traffic["seq_len"]), jnp.int32),)


# -- the Mamba-2 mixer -------------------------------------------------------

def segsum(a):
    """``out[..., i, j] = sum_{j < k <= i} a[..., k]`` for ``j <= i``, else
    ``-inf``, by a cumulative sum of the masked copies (no difference of
    two long sums): ``a`` (..., T) gives (..., T, T)."""
    T = a.shape[-1]
    copies = jnp.where(jnp.tril(jnp.ones((T, T), bool), -1),
                       jnp.broadcast_to(a[..., :, None], a.shape + (T,)), 0.0)
    summed = jnp.cumsum(copies, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), summed, -jnp.inf)


def _chunk_rows(ops, group, a, xdt, Bc, Cc):
    """Steps 1 and 2 of ``ssd`` for chunks ``c``: each chunk's rows from its
    own rows, and its own state at its end.  ``a`` (Bt, H, c, Q), ``xdt``
    (Bt, c, Q, H, P), ``Bc``, ``Cc`` (Bt, c, Q, G, N)."""
    cum = jnp.cumsum(a, axis=-1)
    scores = ops.einsum("bclgn,bcsgn->bgcls", Cc, Bc)[:, group]
    inside = ops.einsum("bhcls,bcshp->bclhp",
                        scores * jnp.exp(segsum(a)).astype(scores.dtype), xdt)
    to_end = jnp.moveaxis(jnp.exp(cum[..., -1:] - cum), 1, 3)  # (Bt, c, Q, H)
    states = ops.einsum("bclhn,bclhp->bchpn", Bc[:, :, :, group],
                        xdt * to_end[..., None].astype(xdt.dtype))
    return inside, states


def _chunk_start(ops, group, a, Cc, starts):
    """Step 4 of ``ssd``: each chunk's rows from the state at its start."""
    from_start = jnp.moveaxis(jnp.exp(jnp.cumsum(a, axis=-1)), 1, 3)
    return ops.einsum("bclhn,bchpn->bclhp", Cc[:, :, :, group], starts) \
        * from_start[..., None].astype(starts.dtype)


def ssd(s, ops, x, dt, A, B, C, looped=False):
    """The scan without ``D``, the chunked dual form.  ``x`` (Bt, L, H, P),
    ``dt`` (Bt, L, H) float32, ``A`` (H,), ``B``, ``C`` (Bt, L, G, N); ``L``
    a multiple of the chunk.  ``looped``: steps 1, 2 and 4 one chunk at a
    time (``lax.map``, each chunk recomputed in the backward pass), so that
    no ``chunk x chunk`` square of every chunk is alive at once; the same
    products."""
    Bt, L, H, P = x.shape
    G, Q = B.shape[2], s["chunk"]
    n, per = L // Q, H // G
    group = jnp.arange(H) // per
    a = jnp.moveaxis((dt * A).reshape(Bt, n, Q, H), 3, 1)     # (Bt, H, n, Q)
    xdt = (x * dt[..., None].astype(x.dtype)).reshape(Bt, n, Q, H, P)
    Bc, Cc = (t.reshape(Bt, n, Q, G, -1) for t in (B, C))

    def by_chunk(f, arrays, axes):
        """``f`` of ``arrays`` whose chunk axes are ``axes``; looped, over
        each chunk alone.  Every result has its chunk axis at 1."""
        if not looped:
            return f(*arrays)

        def one(chunks):
            return f(*(jnp.expand_dims(t, axis)
                       for t, axis in zip(chunks, axes)))
        out = jax.lax.map(jax.checkpoint(one), [
            jnp.moveaxis(t, axis, 0) for t, axis in zip(arrays, axes)])
        return jax.tree_util.tree_map(
            lambda r: jnp.moveaxis(r[:, :, 0], 0, 1), out)

    # 1, 2. each chunk's rows from its own rows; its own state at its end
    inside, states = by_chunk(
        lambda a_, x_, B_, C_: _chunk_rows(ops, group, a_, x_, B_, C_),
        (a, xdt, Bc, Cc), (2, 1, 1, 1))
    # 3. the states between chunks, by the segment sum over the chunks
    ends = jnp.pad(jnp.sum(a, axis=-1), ((0, 0), (0, 0), (1, 0)))
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    starts = ops.einsum("bhzc,bchpn->bzhpn",
                        carry_of(jnp.exp(segsum(ends))).astype(states.dtype),
                        states)[:, :-1]
    # 4. each chunk's rows from the state at its start
    outside = by_chunk(
        lambda a_, C_, S_: _chunk_start(ops, group, a_, C_, S_),
        (a, Cc, starts), (2, 1, 1))
    return (inside + outside).reshape(Bt, L, H, P)


def causal_conv(s, x, weight, bias):
    """``silu(conv(x) + bias)``: ``x`` (Bt, L, c), ``weight`` (c, K)."""
    K, L = s["taps"], x.shape[1]
    front = conv_front(K)
    padded = jnp.pad(x, ((0, 0), (front, K - 1 - front), (0, 0)))
    w = weight.astype(x.dtype)
    out = sum(padded[:, j:j + L] * w[:, j] for j in range(K))
    return jax.nn.silu(out + bias.astype(x.dtype))


def mamba(s, ops, params, prefix, u, looped=False):
    """``u``: (Bt, L, hidden), the layer's normed input; ``looped`` as
    ``ssd`` takes it."""
    Bt, L, _ = u.shape
    H, P, G, N = s["ssm_heads"], s["ssm_head_dim"], s["groups"], s["state"]
    inner = H * P
    p = prefix + "ssm_"
    proj = ops.einsum("bld,ed->ble", u, params[p + "in_weight"])
    z, xbc, dt = (proj[..., :inner], proj[..., inner:2 * inner + 2 * G * N],
                  proj[..., 2 * inner + 2 * G * N:])
    xbc = causal_conv(s, xbc, params[p + "conv_weight"],
                      params[p + "conv_bias"])
    x = xbc[..., :inner].reshape(Bt, L, H, P)
    B = xbc[..., inner:inner + G * N].reshape(Bt, L, G, N)
    C = xbc[..., inner + G * N:].reshape(Bt, L, G, N)
    step = jax.nn.softplus((dt + params[p + "dt_bias"]).astype(jnp.float32))
    A = -jnp.exp(params[p + "A_log"].astype(jnp.float32))
    pad = -L % s["chunk"]
    y = ssd(s, ops, *(jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                      for t in (x, step)), A,
            *(jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0))) for t in (B, C)),
            looped=looped)
    y = y[:, :L] + skip_of(params[p + "D"]).astype(x.dtype)[:, None] * x
    h = y.reshape(Bt, L, inner).astype(jnp.float32) \
        * gate_of(z.astype(jnp.float32))
    parts = h.reshape(Bt, L, norm_groups(G), -1)
    parts = parts * jax.lax.rsqrt(jnp.mean(parts * parts, axis=-1,
                                           keepdims=True) + s["eps"])
    y = (parts.reshape(Bt, L, inner)
         * params[p + "norm_gamma"].astype(jnp.float32)).astype(u.dtype)
    return ops.einsum("ble,de->bld", y, params[p + "out_weight"])


# -- attention ---------------------------------------------------------------

def attention(s, ops, params, prefix, x, looped):
    """``x``: (B, L, hidden), the layer's normed input."""
    B, L, _ = x.shape
    hd, kv, heads = s["head_dim"], s["kv_heads"], s["heads"]
    chunk = CHUNK if L % CHUNK == 0 else L

    def project(name, count):
        t = ops.einsum("bld,ed->ble", x, params[prefix + name + "_weight"])
        return t.reshape(B, L, count, hd).transpose(0, 2, 1, 3)

    q, k = query_key(s, project("attn_q", heads), project("attn_k", kv))
    q, v = q.reshape(B, kv, heads // kv, L, hd), project("attn_v", kv)
    attend = _attend_looped if looped else _attend_by_shapes
    out = attend(s, ops, q, k, v, chunk, None)
    out = out.reshape(B, heads, L, hd).transpose(0, 2, 1, 3)
    return ops.einsum("ble,de->bld", out.reshape(B, L, heads * hd),
                      params[prefix + "attn_o_weight"])


# -- the experts -------------------------------------------------------------

def route(s, ops, params, prefix, y, given=None):
    """(weights (T, top_k), expert ids (T, top_k)) of the tokens ``y``
    (T, hidden), over all experts.  ``given`` (T, top_k), where handed over,
    are the experts to use in the place of the router's picks; the weights
    are then the router's own of those."""
    logits = ops.einsum("td,ed->te", y, params[prefix + "moe_router_weight"])
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    expert = given
    if expert is None:
        bias = params[prefix + "moe_expert_bias"].astype(jnp.float32)
        _, expert = jax.lax.top_k(scores + bias, s["top_k"])
    picked = jnp.take_along_axis(scores, expert, axis=-1)
    return picked / (jnp.sum(picked, axis=-1, keepdims=True)
                     + s["router_eps"]) * routed_scale(s["scale"]), expert


def _expert(ops, own, xs, up_w, down_w):
    """``down(relu(up x)^2)`` over rows ``r``; ``own`` is ``"r"`` where every
    row brings its own matrices, else empty."""
    up = ops.einsum("rd,%sfd->rf" % own, xs, up_w)
    return ops.einsum("rf,%sdf->rd" % own, activation(up).astype(up.dtype),
                      down_w)


def moe(s, ops, params, prefix, y, pairs, looped, given=None):
    """``y``: (T, hidden).  Returns (the held experts' part and the shared
    expert's, overflow); ``given`` as ``route`` takes it."""
    held, width, d = s["held"], s["width"], y.shape[1]
    weight, expert = route(s, ops, params, prefix, y, given)
    local = expert - s["first"]                                # (T, top_k)
    local = jnp.where(lands_here(local, held), local, held)    # held: not here
    matrices = (params[prefix + "moe_up_weight"].reshape(held, width, d),
                params[prefix + "moe_down_weight"].reshape(held, d, width))
    shared = shared_of(_expert(ops, "", y,
                               params[prefix + "moe_shared_up_weight"],
                               params[prefix + "moe_shared_down_weight"]))
    if looped:
        routed, overflow = _experts_looped(s, ops, y, local, weight,
                                           matrices), False
    else:
        routed, overflow = _experts_by_shapes(s, ops, y, local, weight,
                                              matrices, pairs)
    return routed + shared.astype(y.dtype), overflow


def _experts_looped(s, ops, y, local, weight, matrices):
    """What is trained: one expert's program, looped over the held experts,
    each over all rows with the weight of the rows that did not choose it
    0."""
    @jax.checkpoint
    def one(total, args):
        e, up_w, down_w = args
        w_e = jnp.sum(jnp.where(local == e, weight, 0.0), axis=-1)
        out = _expert(ops, "", y, up_w, down_w)
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


# -- the network -------------------------------------------------------------

def block(s, ops, params, prefix, kind, u, pairs, looped, given=None):
    """The block of a layer of ``kind`` on its normed input ``u`` (B, L,
    hidden): (rows, overflow)."""
    if kind == "M":
        return mamba(s, ops, params, prefix, u, looped), False
    if kind == "*":
        return attention(s, ops, params, prefix, u, looped), False
    out, overflow = moe(s, ops, params, prefix, u.reshape(-1, u.shape[-1]),
                        pairs, looped, given)
    return out.reshape(u.shape), overflow


def network(config, ops, params, tokens, looped):
    """Logits (B, L, vocab), as trained (``looped``) or as counted.  Where
    the counted buffer overflows, the logits are NaN."""
    s = _sizes(config)
    B, L = tokens.shape
    pairs = reference_pairs(config, B * L)
    x = params["embed_weight"].astype(ops.dtype)[tokens]

    def layer(kind, prefix):
        def run(x, weights):
            u = rms_norm(x, weights[prefix + "norm_gamma"], s["eps"])
            out, overflow = block(s, ops, weights, prefix, kind, u, pairs,
                                  looped)
            return x + out.astype(x.dtype), overflow
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
