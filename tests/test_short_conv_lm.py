"""A decoder whose layers differ (gated short convolutions and causal
grouped-query attention over dense and routed feed-forwards, a sigmoid router
with a selection bias, a tied head), at small sizes on the CPU, against the
benchmark's plain reference (benchmark/reference/lfm2_moe.py: float32,
``highest``, nothing of the program) and, the attention kernels interpreted,
against the XLA attention reference."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler
from mxnet_tpu.gluon.block import functional_call
from mxnet_tpu.gluon.model_zoo import short_conv_lm
from mxnet_tpu.gluon.nn import decoder_layers
from mxnet_tpu.ops import pallas_ops
from mxnet_tpu.ops.registry import get_op
from mxnet_tpu.parallel import moe as moe_mod

from benchmark.generators import next_token
from benchmark.reference import common as reference
from benchmark.reference import lfm2_moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
L, BATCH = 32, 2
# two layers of each operator and of each feed-forward; 4 of 16 experts held
CONFIG = dict(
    reference="lfm2_moe", hidden_size=64, num_attention_heads=8,
    num_key_value_heads=2, intermediate_size=96, moe_intermediate_size=24,
    conv_L_cache=3, conv_bias=False, num_experts=4, num_experts_per_tok=2,
    num_hidden_layers=4, num_dense_layers=2, norm_topk_prob=True,
    layer_types=["conv", "full_attention", "conv", "full_attention"],
    vocab_size=96, norm_eps=1e-5, routed_scaling_factor=1.5,
    use_expert_bias=True, rope_parameters={"rope_theta": 1000000},
    deployment={"num_experts_total": 16, "first_expert": 4})
LEAVES = (
    "embed_weight", "final_norm_gamma", "operator_norm_gamma",
    "ffn_norm_gamma", "conv_in_weight", "conv_taps_weight", "conv_out_weight",
    "attn_q_weight", "attn_k_weight", "attn_v_weight", "attn_o_weight",
    "attn_q_norm_gamma", "attn_k_norm_gamma", "mlp_gate_weight",
    "mlp_up_weight", "mlp_down_weight", "moe_router_weight",
    "moe_gate_weight", "moe_up_weight", "moe_down_weight")


def _batch(seed=0):
    return next_token.make_pool(CONFIG, {"batch": BATCH, "seq_len": L},
                                seed, 1)[0]


def _seeded(config, seed=7):
    """The seed's weights with the norms away from 1 and a selection bias
    of the size of the scores' spread, so that both matter."""
    params, _ = reference.xavier_init(config, seed)
    key = jax.random.PRNGKey(3)
    for i, name in enumerate(sorted(params)):
        if name.endswith("_gamma"):
            params[name] = params[name] + 0.3 * jax.random.normal(
                jax.random.fold_in(key, i), params[name].shape)
        elif name.endswith("_expert_bias"):
            params[name] = 0.2 * jax.random.normal(
                jax.random.fold_in(key, i), params[name].shape)
        elif name.endswith("router_weight"):
            params[name] = params[name] * 4
    return params


@pytest.fixture(scope="module")
def model():
    net = short_conv_lm.build(CONFIG)
    net.initialize(mx.init.Zero(), ctx=mx.current_context())
    return net, _seeded(CONFIG)


def _logits(net, values, tokens):
    full = {net.prefix + k: v for k, v in values.items()}
    for name, p in net.collect_params().items():    # the recorded state
        full.setdefault(name, p.data()._data)
    return functional_call(net, full, jnp.asarray(tokens), training=True)[0][0]


def _program_loss(net, values, batch):
    tokens, targets, weight = batch

    def loss(values):
        return short_conv_lm.loss(
            [mx.nd.NDArray(_logits(net, values, tokens))],
            mx.nd.NDArray(jnp.asarray(targets)),
            mx.nd.NDArray(jnp.asarray(weight)))._data.reshape(())
    return jax.value_and_grad(loss)(values)


def _reference_loss(config, params, batch):
    ops = reference.Ops()
    return jax.value_and_grad(lambda p: lfm2_moe.loss(
        config, ops, p, {}, tuple(jnp.asarray(a) for a in batch))[0])(params)


# -- the router, by hand ---------------------------------------------------------

def test_a_bias_changes_the_picks_and_not_the_weights():
    scores = jnp.asarray([[0.9, 0.5, 0.4, 0.1], [0.2, 0.3, 0.6, 0.7]])
    bias = jnp.asarray([0.0, -0.3, 0.0, 0.45])
    plain, picked = moe_mod.route_top_k(scores, 2)
    assert picked.tolist() == [[0, 1], [3, 2]]
    np.testing.assert_allclose(plain, [[0.9 / 1.4, 0.5 / 1.4],
                                       [0.7 / 1.3, 0.6 / 1.3]], rtol=1e-6)
    weights, picked = moe_mod.route_top_k(scores, 2, scores + bias, 1e-6, 2.5)
    # ranked by score + bias: expert 3 (0.55) beats 1 (0.2) and 2 (0.4) in
    # row 0; the weights are the unbiased scores', over their sum + 1e-6
    assert picked.tolist() == [[0, 3], [3, 2]]
    want = np.asarray([[0.9, 0.1], [0.7, 0.6]])
    want = want / (want.sum(-1, keepdims=True) + 1e-6) * 2.5
    np.testing.assert_allclose(weights, want, rtol=1e-6)
    # the 1e-6 is there: weights of tiny scores do not sum to the scale
    tiny, _ = moe_mod.route_top_k(jnp.full((1, 4), 1e-6), 2, None, 1e-6)
    np.testing.assert_allclose(tiny.sum(), 2e-6 / 3e-6, rtol=1e-5)
    # ties go to the lower id
    assert moe_mod.route_top_k(jnp.ones((1, 4)), 2)[1].tolist() == [[0, 1]]


def test_sigmoid_router_of_the_layer_is_the_formula():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.normal(0, 1, (24, 16)), jnp.float32)
    router = jnp.asarray(rng.normal(0, 1, (8, 16)), jnp.float32)
    bias = jnp.asarray(rng.normal(0, 0.3, (8,)), jnp.float32)
    gate, up = (jnp.asarray(rng.normal(0, 0.3, (8, 6, 16)), jnp.float32)
                for _ in range(2))
    down = jnp.asarray(rng.normal(0, 0.3, (8, 16, 6)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        out, load = moe_mod.moe_held_apply(
            x, router, gate, up, down, 3, scoring="sigmoid", scale=0.5,
            bias=bias)
        s = jax.nn.sigmoid(x @ router.T)
        _, picked = jax.lax.top_k(s + bias, 3)
        assert not bool(jnp.all(picked == jax.lax.top_k(s, 3)[1]))
        w = jnp.take_along_axis(s, picked, -1)
        w = w / (w.sum(-1, keepdims=True) + 1e-6) * 0.5
        want = jnp.zeros_like(x)
        for slot in range(3):
            e = picked[:, slot]
            hidden = jax.nn.silu(jnp.einsum("td,tfd->tf", x, gate[e])) \
                * jnp.einsum("td,tfd->tf", x, up[e])
            want = want + w[:, slot, None] * jnp.einsum("tf,tdf->td", hidden,
                                                        down[e])
    assert float(jnp.max(jnp.abs(out - want))) < 1e-5
    assert load.tolist() == [24 * 3, float(np.bincount(
        np.asarray(picked).ravel(), minlength=8).max())]
    with pytest.raises(ValueError, match="softmax or sigmoid"):
        moe_mod.moe_held_apply(x, router, gate, up, down, 3, scoring="tanh")


def test_the_layer_says_what_its_router_picks(model):
    """``HeldExpertsMoE.route`` (the operator ``_contrib_moe_route`` under the
    layer's own attrs): the reference's picks and weights for the same rows,
    under the layer's bias and scale."""
    net, params = model
    layer = net.layers[2].feed_forward
    rng = np.random.RandomState(4)
    y = jnp.asarray(rng.normal(0, 1, (BATCH, L, 64)), jnp.float32)
    router, bias = (params["layer2_moe_" + n]
                    for n in ("router_weight", "expert_bias"))
    weights, picked = layer.route(mx.nd, mx.nd.NDArray(y),
                                  mx.nd.NDArray(router), mx.nd.NDArray(bias))
    want_w, want = lfm2_moe.route(lfm2_moe._sizes(CONFIG), reference.Ops(),
                                  params, "layer2_", y.reshape(-1, 64))
    assert picked.shape == (BATCH * L, 2)
    assert (picked.asnumpy() == np.asarray(want)).all()
    np.testing.assert_allclose(weights.asnumpy(), want_w, rtol=1e-5)
    unbiased = mx.nd._contrib_moe_route(
        mx.nd.NDArray(y), mx.nd.NDArray(router), experts_per_token=2,
        scoring="sigmoid")[1]
    assert (unbiased.asnumpy() != np.asarray(want)).any()


def _flat_moe_held_apply(x, router_w, gate_w, up_w, down_w, k,
                         first_expert=0):
    """``moe_held_apply`` as it stood until the slot table (commit 7c0a9b7,
    softmax router, its recorder calls left out): every held expert's hidden
    units for every row as one feed-forward, an unpicked expert's multiplied
    by 0.  What the three decoder cells' steps were traced from; kept here as
    the oracle of the form that replaced it."""
    T, d = x.shape
    held, f, _ = gate_w.shape
    logits = jnp.dot(x.astype(jnp.float32), router_w.T.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    vals, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    weights = vals / jnp.sum(vals, axis=-1, keepdims=True)
    chosen = (experts - first_expert)[:, :, None] == jnp.arange(held)
    gates = jnp.sum(jnp.where(chosen, weights[:, :, None], 0.0), axis=1)
    per_expert = jnp.sum(chosen, axis=(0, 1))
    load = jnp.stack([jnp.sum(per_expert),
                      jnp.max(per_expert)]).astype(jnp.float32)
    gate = jnp.dot(x, gate_w.reshape(held * f, d).T)
    up = jnp.dot(x, up_w.reshape(held * f, d).T)
    hidden = (jax.nn.silu(gate) * up).reshape(T, held, f)
    hidden = hidden * gates.astype(x.dtype)[:, :, None]
    return jnp.einsum("tef,edf->td", hidden, down_w), load


def test_softmax_router_by_default_and_the_flat_form_s_results():
    """The slot table gives what computing every held expert for every row
    gave, value and gradients; and an operator called without the router's
    attrs traces the program of one called with softmax and scale 1."""
    shapes = [(40, 16), (8, 16), (4, 6, 16), (4, 6, 16), (4, 16, 6)]
    rng = np.random.RandomState(5)
    args = [jnp.asarray(rng.normal(0, 0.5, s), jnp.float32) for s in shapes]

    def step(f):
        return jax.value_and_grad(lambda *a: jnp.sum(jnp.sin(
            f(*a, 2, first_expert=2)[0])), (0, 1, 2, 3, 4))(*args)
    for got, want in zip(jax.tree_util.tree_leaves(step(
            moe_mod.moe_held_apply)), jax.tree_util.tree_leaves(step(
                _flat_moe_held_apply))):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert moe_mod.moe_held_apply(*args, 2, first_expert=2)[1].tolist() \
        == _flat_moe_held_apply(*args, 2, first_expert=2)[1].tolist()
    shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    op = get_op("_contrib_moe_held_experts").fcompute
    attrs = {"experts_per_token": 2, "expert_width": 6, "first_expert": 2}
    flat = [shapes[0], shapes[1]] + [jax.ShapeDtypeStruct(
        (s.shape[0] * s.shape[1], s.shape[2]), jnp.float32)
        for s in shapes[2:]]
    by_default = str(jax.make_jaxpr(lambda *a: op(attrs, *a))(*flat))
    said = str(jax.make_jaxpr(lambda *a: op(
        dict(attrs, scoring="softmax", scale=1.0), *a))(*flat))
    assert by_default == said


# -- the shares of an expert-parallel layer --------------------------------------

def test_the_shares_of_a_routed_layer_add_up_to_the_uncut_layer(model):
    """4 chips that hold 4 of 16 experts each, by the program's layer, against
    the reference given all 16: what every chip computes alike (the router,
    the bias) is in each share's weights and counted once in the sum."""
    _, params = model
    prefix = "layer2_"
    rng = np.random.RandomState(1)
    y = jnp.asarray(rng.normal(0, 1, (BATCH * L, 64)), jnp.float32)
    uncut = dict(CONFIG, num_experts=16, deployment={})
    whole = {k: v for k, v in reference.xavier_init(uncut, 11)[0].items()
             if k.startswith(prefix)}
    whole[prefix + "moe_expert_bias"] = params[prefix + "moe_expert_bias"]
    whole[prefix + "moe_router_weight"] = 4 * whole[
        prefix + "moe_router_weight"]
    s = lfm2_moe._sizes(uncut)
    want, _ = lfm2_moe.moe(s, reference.Ops(), whole, prefix, y, None, True)
    weight, expert = lfm2_moe.route(s, reference.Ops(), whole, prefix, y)
    assert len(np.unique(np.asarray(expert) // 4)) == 4     # every chip works
    op = get_op("_contrib_moe_held_experts").fcompute
    total, pairs = jnp.zeros_like(y), 0
    with jax.default_matmul_precision("highest"):
        for chip in range(4):
            rows = slice(chip * 4 * 24, (chip + 1) * 4 * 24)
            out, load = op(
                {"experts_per_token": 2, "expert_width": 24,
                 "first_expert": 4 * chip, "scoring": "sigmoid",
                 "scale": 1.5},
                y, whole[prefix + "moe_router_weight"],
                whole[prefix + "moe_gate_weight"][rows],
                whole[prefix + "moe_up_weight"][rows],
                whole[prefix + "moe_down_weight"].reshape(16, 64, 24)[
                    4 * chip:4 * chip + 4].reshape(-1, 24),
                whole[prefix + "moe_expert_bias"])
            total, pairs = total + out, pairs + float(load[0])
    assert pairs == BATCH * L * 2
    assert float(jnp.max(jnp.abs(total - want))) < 1e-5 * float(
        jnp.max(jnp.abs(want)))


# -- causality of the two operators ----------------------------------------------

@pytest.mark.parametrize("kind", ["conv", "full_attention"])
def test_a_changed_row_moves_nothing_before_it_or_beside_it(kind):
    rng = np.random.RandomState(2)
    if kind == "conv":
        block = decoder_layers.GatedShortConv(64, 3)
    else:
        block = decoder_layers.CausalAttention(64, 8, 2, 8, 1e6, 1e-5)
    block.initialize(mx.init.Xavier(), ctx=mx.current_context())
    x = rng.normal(0, 1, (BATCH, L, 64)).astype(np.float32)
    positions = mx.nd.array(np.arange(L), dtype="int32")
    at = 11
    moved = x.copy()
    moved[0, at] += 1.0
    out, out_moved = (block(mx.nd.array(a), positions).asnumpy()
                      for a in (x, moved))
    changed = np.abs(out_moved - out).max(-1) > 0
    assert not changed[0, :at].any() and not changed[1].any()
    assert changed[0, at]
    if kind == "conv":      # three taps: rows at, at + 1, at + 2 and no other
        assert changed[0].nonzero()[0].tolist() == [at, at + 1, at + 2]
    else:
        assert changed[0, at:].all()


def test_gated_short_conv_is_the_sum_over_its_taps():
    rng = np.random.RandomState(3)
    streams = rng.normal(0, 1, (2, 9, 12)).astype(np.float32)
    taps = rng.normal(0, 1, (4, 3)).astype(np.float32)
    got = mx.nd._contrib_gated_short_conv(mx.nd.array(streams),
                                          mx.nd.array(taps)).asnumpy()
    z = streams[..., :4] * streams[..., 8:]
    for t in range(9):
        c = sum(taps[:, j] * z[:, t - 2 + j] for j in range(3)
                if t - 2 + j >= 0)
        np.testing.assert_allclose(got[:, t], streams[:, t, 4:8] * c,
                                   rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="three streams"):
        mx.nd._contrib_gated_short_conv(mx.nd.array(streams[..., :11]),
                                        mx.nd.array(taps))


@pytest.mark.parametrize("batch,rows,channels,tile", [
    (2, 64, 256, 16), (1, 48, 128, 16), (2, 32, 640, 32)])
def test_short_conv_kernels_match_xla(batch, rows, channels, tile):
    """The two kernels, interpreted, over several tiles of rows (a tile's
    first rows read the last of the tile before it, and its gradient the
    first of the tile after), one chunk of channels and two, against the
    shifted copies in XLA: output, the streams' gradient and the taps'."""
    rng = np.random.RandomState(0)
    streams = jnp.asarray(rng.normal(0, 1, (batch, rows, 3 * channels)),
                          jnp.float32)
    taps = jnp.asarray(rng.normal(0, 1, (channels, 3)), jnp.float32)
    cot = jnp.asarray(rng.normal(0, 1, (batch, rows, channels)), jnp.float32)

    def kernels(s, w):
        out = pallas_ops.gated_short_conv(s, w, interpret=True, rows=tile)
        return jnp.sum(out * cot), out

    def oracle(s, w):
        out = pallas_ops._gated_short_conv_reference(s, w)
        return jnp.sum(out * cot), out

    got, out = jax.grad(kernels, (0, 1), has_aux=True)(streams, taps)
    want, out_w = jax.grad(oracle, (0, 1), has_aux=True)(streams, taps)
    assert float(jnp.max(jnp.abs(out - out_w))) < 1e-5
    for a, b in zip(got, want):
        assert a.shape == b.shape
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4
    names = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.add(eqn.params["name"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(jax.grad(lambda s, w: kernels(s, w)[0], (0, 1)))(
        streams, taps).jaxpr)
    assert names == {"short_conv_fwd", "short_conv_bwd"}
    # a width that is no multiple of 128 lanes takes XLA's form
    narrow = pallas_ops.gated_short_conv(streams[..., :3 * 96], taps[:96],
                                         interpret=True, rows=tile)
    assert narrow.shape == (batch, rows, 96)


# -- the kernels at head size 64, four query heads a key/value head --------------

@pytest.mark.parametrize("rows,tiles,batch", [
    (128, (32, 32), 1), (256, (64, 32), 2), (100, (32, 64), 2)])
def test_causal_kernels_match_reference_at_head_size_64(rows, tiles, batch):
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (batch, h, rows, 64)), jnp.float32)
               for h in (8, 2, 2))

    def kernels(q, k, v):
        out = pallas_ops.causal_attention(
            q, k, v, precision="highest", interpret=True, block_q=tiles[0],
            block_k=tiles[1])
        return jnp.sum(jnp.sin(out)), out

    def oracle(q, k, v):
        out = pallas_ops._attention_reference(q, k, v, True, 0.125)
        return jnp.sum(jnp.sin(out)), out

    profiler.reset_spans()
    got, out = jax.grad(kernels, (0, 1, 2), has_aux=True)(q, k, v)
    totals = profiler.totals()
    assert totals["attn.grid_steps"]["count"] \
        == totals["attn.tiles_visited"]["count"] \
        < totals["attn.tiles_total"]["count"]
    want, out_w = jax.grad(oracle, (0, 1, 2), has_aux=True)(q, k, v)
    assert float(jnp.max(jnp.abs(out - out_w))) < 1e-5
    for a, b in zip(got, want):
        assert a.shape == b.shape
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4
    with pytest.raises(ValueError, match="one square"):
        pallas_ops.causal_attention(q, k[:, :, :64], v[:, :, :64])


# -- the dense feed-forward's operator ------------------------------------------

def _plain_mlp(x, gate_w, up_w, down_w, product=lambda a, w: a @ w.T):
    """The three products as three ``Dense`` make them, differentiated by
    JAX."""
    g = product(x, gate_w)
    return product(jax.nn.silu(g) * product(x, up_w), down_w)


def _bf16(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


@jax.custom_vjp
def _mxu_product(a, w):
    """``a @ w.T`` whose operands, in both passes, are rounded as the TPU's
    matrix units take float32 at the default precision; float32 sums."""
    return jnp.matmul(_bf16(a), _bf16(w).T, precision="highest")


def _mxu_product_bwd(kept, g):
    a, w = kept
    rows = lambda t: _bf16(t).reshape(-1, t.shape[-1])
    return (jnp.matmul(_bf16(g), _bf16(w), precision="highest"),
            jnp.matmul(rows(g).T, rows(a), precision="highest"))


_mxu_product.defvjp(lambda a, w: (_mxu_product(a, w), (a, w)),
                    _mxu_product_bwd)


def _mlp_grads(layer, x, weights, dy):
    return jax.value_and_grad(lambda x, w: jnp.sum(layer(x, *w) * dy),
                              (0, 1))(x, weights)


@pytest.fixture(scope="module")
def published_mlp():
    """256 rows at the fifth cell's widths (2,048 to 11,776), a seeded
    cotangent; the plain form's value and gradients in float32."""
    keys = jax.random.split(jax.random.PRNGKey(38), 5)
    d, f = 2048, 11776
    x = jax.random.normal(keys[0], (2, 128, d))
    weights = tuple(jax.random.normal(k, s) / s[1] ** 0.5 for k, s in zip(
        keys[1:4], ((f, d), (f, d), (d, f))))
    dy = jax.random.normal(keys[4], (2, 128, d))
    with jax.default_matmul_precision("highest"):
        return x, weights, dy, _mlp_grads(_plain_mlp, x, weights, dy)


def _gaps(got, want):
    """Each leaf's error over its norm."""
    return [float(jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(
        b.ravel())) for a, b in zip(jax.tree.leaves(got),
                                    jax.tree.leaves(want))]


def test_gated_mlp_is_the_plain_form_in_float32(published_mlp):
    x, weights, dy, want = published_mlp
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: _mlp_grads(lambda *b: get_op(
            "_contrib_gated_mlp").fcompute({}, *b), *a))(x, weights, dy)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), got) == jax.tree.map(
        lambda a: (a.shape, a.dtype), want)
    assert max(_gaps(got, want)) < 1e-6


def test_gated_mlp_feeds_the_tpu_s_operands_to_its_products(published_mlp,
                                                            monkeypatch):
    """The backward pass in bfloat16 operands as on the TPU: the gradients
    of the plain form whose every product's operands are rounded as the
    TPU's default precision rounds them, to the tolerance of that rounding
    itself (a float32 within an ulp of a bfloat16 boundary rounds either
    way: the same form in float32 and float64 arithmetic reads 2.6e-5 to
    3.4e-5 apart, the operator 3.0e-5 to 4.8e-5 from the float32 one); the
    forward pass is the plain form's."""
    from mxnet_tpu.ops.decoder_ops import gated_mlp
    x, weights, dy, plain = published_mlp
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    got = jax.jit(lambda *a: _mlp_grads(gated_mlp, *a))(x, weights, dy)
    want = jax.jit(lambda *a: _mlp_grads(lambda *b: _plain_mlp(
        *b, product=_mxu_product), *a))(x, weights, dy)
    assert max(_gaps(got[0], plain[0])) < 1e-6
    gaps = _gaps(got[1], want[1])
    assert max(gaps) < 1e-4, gaps
    # and what the rounding is: far above that, from the float32 form's
    assert min(_gaps(got[1], plain[1])) > 1e-3


def test_a_recomputed_layer_s_gradients_are_those_of_the_plain_form(
        monkeypatch):
    """A ``DecoderLayer`` with a dense feed-forward under
    ``hybridize(remat=True)``: the input's and every parameter's gradient,
    the operator against the three products differentiated by JAX."""
    from mxnet_tpu.ops import decoder_ops
    layer = decoder_layers.DecoderLayer(
        64, lambda: decoder_layers.GatedShortConv(64, 3, prefix="conv_"),
        lambda: decoder_layers.GatedMLP(64, 96, prefix="mlp_"),
        prefix="layer0_")
    layer.initialize(mx.init.Xavier(), ctx=mx.current_context())
    layer.hybridize(remat=True)
    values = {k: p.data()._data for k, p in layer.collect_params().items()}
    assert sorted(k for k in values if "_mlp_" in k) == [
        "layer0_mlp_down_weight", "layer0_mlp_gate_weight",
        "layer0_mlp_up_weight"]
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.normal(0, 1, (BATCH, L, 64)), jnp.float32)
    dy = jnp.asarray(rng.normal(0, 1, (BATCH, L, 64)), jnp.float32)
    positions = jnp.arange(L, dtype=jnp.int32)
    wrapped = []
    checkpoint = jax.checkpoint
    monkeypatch.setattr(jax, "checkpoint", lambda f, **kw: (
        wrapped.append(f.__name__), checkpoint(f, **kw))[1])

    def grads():
        return jax.grad(lambda v, x: jnp.sum(functional_call(
            layer, v, x, positions, training=True)[0][0] * dy), (0, 1))(
                values, x)

    with jax.default_matmul_precision("highest"):
        got = grads()
        monkeypatch.setattr(decoder_ops, "gated_mlp", _plain_mlp)
        want = grads()
    assert wrapped and set(wrapped) == {"pure"}      # recomputed
    assert max(_gaps(got, want)) < 1e-6


# -- the model against the plain reference ---------------------------------------

def test_parameters_carry_the_reference_names(model):
    net, params = model
    shapes = lfm2_moe.param_shapes(CONFIG)
    held = {k[len(net.prefix):]: p for k, p in net.collect_params().items()
            if p.grad_req != "null"}
    assert sorted(held) == sorted(shapes) == sorted(params)
    for name, p in held.items():
        assert tuple(p.shape) == tuple(shapes[name]), name
    # tied: the head's weight is the embedding's leaf, and no other
    assert net.head.weight is net.embed.weight
    assert not any("head" in name for name in shapes)
    assert shapes["layer2_moe_expert_bias"] == (16,)


def test_layer_types_and_dense_layers_are_honoured(model):
    net, _ = model
    kinds = [(type(l.operator).__name__, type(l.feed_forward).__name__)
             for l in net.layers]
    assert kinds == [("GatedShortConv", "GatedMLP"),
                     ("CausalAttention", "GatedMLP"),
                     ("GatedShortConv", "HeldExpertsMoE"),
                     ("CausalAttention", "HeldExpertsMoE")]
    # a stage's layers by their published indices: the cell's cut of 40
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2_24b_a2b_ep8.json")) as f:
        cell = json.load(f)
    assert len(cell["layer_types"]) == 40 and cell["num_dense_layers"] == 2
    assert short_conv_lm.held_layers(cell) == [
        ("conv", True), ("full_attention", False), ("conv", False),
        ("conv", False), ("conv", False)]
    assert lfm2_moe._sizes(cell)["held_layers"] == tuple(
        short_conv_lm.held_layers(cell))
    with pytest.raises(ValueError, match="layers held"):
        short_conv_lm.held_layers(dict(cell, num_hidden_layers=4))
    with pytest.raises(ValueError, match="conv or full_attention"):
        short_conv_lm.build(dict(CONFIG, layer_types=["conv", "mamba", "conv",
                                                      "conv"]))
    with pytest.raises(ValueError, match="norm_topk_prob"):
        short_conv_lm.build(dict(CONFIG, norm_topk_prob=False))


def test_logits_match_the_reference(model):
    net, params = model
    tokens = jnp.asarray(_batch()[0])
    with jax.default_matmul_precision("highest"):
        logits = _logits(net, params, tokens)
    want = lfm2_moe.network(CONFIG, reference.Ops(), params, tokens, True)
    assert logits.shape == (BATCH, L, 96)
    assert float(jnp.max(jnp.abs(logits - want))) < 1e-4


@pytest.mark.parametrize("kind", LEAVES + ("moe_expert_bias",))
def test_loss_and_gradient_leaves_match_the_reference(model, kind, _cache={}):
    net, params = model
    if not _cache:
        with jax.default_matmul_precision("highest"):
            _cache["program"] = _program_loss(net, params, _batch())
        _cache["reference"] = _reference_loss(CONFIG, params, _batch())
    (loss, grads), (want_loss, want) = _cache["program"], _cache["reference"]
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    leaves = [k for k in want if k.endswith(kind)]
    assert leaves and sorted(grads) == sorted(want)
    for name in leaves:
        if kind == "moe_expert_bias":   # read by the selection alone
            assert float(jnp.max(jnp.abs(grads[name]))) == 0, name
            assert float(jnp.max(jnp.abs(want[name]))) == 0, name
            continue
        scale = float(jnp.max(jnp.abs(want[name])))
        assert scale > 0, name
        assert float(jnp.max(jnp.abs(grads[name] - want[name]))) \
            < 1e-4 * scale, name


def test_the_bias_moves_the_picks_of_the_model(model):
    net, params = model
    tokens = jnp.asarray(_batch()[0])
    unbiased = {k: jnp.zeros_like(v) if k.endswith("_expert_bias") else v
                for k, v in params.items()}
    ops = reference.Ops()
    assert float(jnp.max(jnp.abs(
        lfm2_moe.network(CONFIG, ops, params, tokens, True)
        - lfm2_moe.network(CONFIG, ops, unbiased, tokens, True)))) > 1e-3


@pytest.mark.parametrize("fault", [
    "bias_not_in_selection", "bias_in_weights", "softmax_router",
    "not_normalised", "conv_looks_ahead", "no_output_gate", "drop_expert"])
def test_reference_faults_move_the_loss_or_a_gradient(model, fault):
    from benchmark.checks import faults_lfm2
    _, params = model
    sound, sound_grads = _reference_loss(CONFIG, params, _batch())
    with faults_lfm2.planted(fault):
        faulty, grads = _reference_loss(CONFIG, params, _batch())
    moved = max(float(jnp.max(jnp.abs(grads[k] - sound_grads[k])))
                for k in grads)
    assert abs(float(faulty) - float(sound)) > 1e-4 or moved > 1e-4


def test_reference_layouts_agree(model, monkeypatch):
    """The layout flops.py counts (every chunk of queries against the keys up
    to its end, the routed pairs gathered into a buffer) and the layout that
    is trained (one chunk's and one expert's program, looped, all keys under
    the mask): the same logits and gradients."""
    _, params = model
    monkeypatch.setattr(lfm2_moe, "CHUNK", 8)       # 4 chunks
    tokens = jnp.asarray(_batch()[0])
    ops = reference.Ops()

    def total(looped):
        return jax.value_and_grad(lambda p: jnp.sum(jnp.tanh(
            lfm2_moe.network(CONFIG, ops, p, tokens, looped))))(params)

    (a, ga), (b, gb) = total(False), total(True)
    assert abs(float(a) - float(b)) < 1e-4 * abs(float(a))
    for name in ga:
        scale = float(jnp.max(jnp.abs(ga[name]))) + 1e-12
        assert float(jnp.max(jnp.abs(ga[name] - gb[name]))) < 1e-4 * scale, name


# -- required work ---------------------------------------------------------------

def test_reference_counts_required_work(monkeypatch):
    """flops.py's walk over the reference: by hand, for the tiny size; the
    three taps are elementwise and counted nowhere."""
    from benchmark import flops
    monkeypatch.setattr(lfm2_moe, "CHUNK", 8)
    flops._forward_macs.cache_clear()

    class Cell:
        config, traffic = CONFIG, {"seq_len": L}
    d, hd, heads, kv = 64, 8, 8, 2
    conv = L * d * 4 * d
    attn = L * d * (2 * heads * hd + 2 * kv * hd) \
        + 8 * (8 + 16 + 24 + 32) * heads * hd * 2
    dense = L * 3 * d * 96
    pairs = lfm2_moe.reference_pairs(CONFIG, L)
    assert pairs == L * 2           # no even load asked for: every pair
    routed = L * 16 * d + pairs * 3 * d * 24
    assert flops.forward_macs(Cell) == 2 * conv + 2 * attn + 2 * dense \
        + 2 * routed + L * 96 * d
    flops._forward_macs.cache_clear()


def test_count_by_hand_of_the_cell():
    """ISSUE 35's count of a trained sequence at the cell's size, and
    flops.py's walk within 1% above it (its chunks of 128 queries end past
    the diagonal)."""
    from benchmark import flops, harness
    T, d = 8192, 2048
    conv = d * 3 * d + d * d                        # MAC a row
    dense = 3 * d * 11776
    projections = 2 * d * d + 2 * d * 512
    causal = T * (T + 1) // 2
    scores = causal * 32 * 64 * 2                   # a sequence
    experts = 64 * d + 4 * 3 * d * 1536 * 8 // 64
    head = 8192 * d
    for got, want in ((conv, 16.777), (dense, 72.352), (projections, 10.486),
                      (scores / T, 16.779), (experts, 0.131 + 4.719),
                      (head, 16.777)):
        assert abs(got / 1e6 - want) < 0.001
    by_hand = T * (conv + dense) + T * (projections + experts) + scores \
        + 3 * T * (conv + experts) + T * head
    assert abs(by_hand / 1e9 - 1662.1) < 0.1
    assert abs(6 * by_hand / 1e9 - 9973) < 1
    cell = harness.Cell("lfm2_24b_a2b_ep8.sft_b2_s8192", ROOT)
    walked = flops.forward_macs(cell)
    assert walked == by_hand + (T * (T + 128) // 2 - causal) * 32 * 64 * 2
    assert 0 < walked / by_hand - 1 < 0.01
    # as computed: all 8 held experts for every row
    computed = by_hand + 4 * T * (8 - 0.5) * 3 * d * 1536
    assert abs(computed / 1e9 - 3981) < 1


def test_roofline_and_routed_share_count_by_hand():
    import importlib.util

    def reader(name):
        spec = importlib.util.spec_from_file_location(
            name.replace(".", "_"), os.path.join(
                ROOT, "benchmark", "metrics", name + ".py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2_24b_a2b_ep8.json")) as f:
        config = json.load(f)
    roofline = reader("causal_attn_roofline.train")
    # 33,558,528 pairs x 32 heads x 64 x 2 products x 2 FLOPs x 3 passes,
    # one attention layer: 4.2 ms of a v5e's peak a sequence
    flops = roofline.required_flops(config, {"seq_len": 8192})
    assert flops == 33558528 * 32 * 64 * 2 * 2 * 3
    assert abs(flops / 197e12 * 1e3 - 4.19) < 0.01
    conv = reader("short_conv_roofline.train")
    # 4 convolution layers x 11 passes of 8,192 rows of 2,048 float32
    assert conv.required_bytes(config, {"seq_len": 8192}) \
        == 4 * 11 * 8192 * 2048 * 4
    share = reader("moe_routed_share.train")
    assert share.routed_share([(4096.0, 600.0), (4000.0, 700.0)],
                              rows=16384, held=8) \
        == 100.0 * 8096 / (2 * 16384 * 8)


# -- through the compiled step: recomputation, counters, gauges ------------------

def test_compiled_step_trains_recomputes_and_records(monkeypatch):
    from mxnet_tpu.module.compiled_step import CompiledTrainStep
    profiler.reset_spans()
    # under the biases below the 4 held experts draw few of the 128 pairs a
    # layer, and from some initial weights none: one seed, not the run's
    np.random.seed(5)
    mx.random.seed(5)
    net = short_conv_lm.build(CONFIG)
    net.initialize(mx.init.Xavier(), ctx=mx.current_context())
    biases = {k: p for k, p in net.collect_params().items()
              if k.endswith("_expert_bias")}
    for i, p in enumerate(biases.values()):
        p.set_data(mx.nd.array(np.linspace(-0.2, 0.2, 16) * (i + 1)))
    before = {k: p.data().asnumpy() for k, p in biases.items()}
    wrapped = []
    checkpoint = jax.checkpoint
    monkeypatch.setattr(jax, "checkpoint", lambda f, **kw: (
        wrapped.append((f.__name__, kw.get("policy"))),
        checkpoint(f, **kw))[1])
    step = CompiledTrainStep.from_block(
        net, short_conv_lm.loss,
        mx.optimizer.create("adam", learning_rate=1e-3),
        n_inputs=short_conv_lm.N_INPUTS)
    batch = tuple(mx.nd.array(a, dtype=a.dtype) for a in _batch())
    losses = [float(step.step(*batch).asnumpy()[0]) for _ in range(4)]
    assert losses[-1] < losses[0]
    # every layer is recomputed, under the policy that keeps the attention
    # kernel's two residuals
    assert [name for name, _ in wrapped] == ["pure"] * 4
    assert all(policy is not None for _, policy in wrapped)
    assert all(layer._flags == {
        "remat": True, "remat_policy": ("attn.out", "attn.lse", "moe.table")}
        for layer in net.layers)
    # Adam leaves the selection biases where they were, to the bit
    assert len(biases) == 2
    for k, p in biases.items():
        assert (p.data().asnumpy() == before[k]).all(), k
    totals = profiler.totals()
    # two routed layers were traced, each over the same rows
    assert totals["moe.rows"]["count"] == 2 * BATCH * L
    assert totals["moe.rows"]["max"] == BATCH * L
    assert totals["moe.experts_held"]["max"] == 4
    loads = [v for k, v in totals.items()
             if k.startswith("moe.load." + net.prefix)]
    assert len(loads) == 2
    for v in loads:
        assert 0 < v["count"] <= BATCH * L * 2 and v["max"] <= BATCH * L


def test_operators_are_registered_for_nd_and_sym():
    for name in ("_contrib_gated_short_conv", "_contrib_causal_attention",
                 "_contrib_moe_held_experts"):
        assert callable(getattr(mx.nd, name)) and callable(
            getattr(mx.sym, name))
    rng = np.random.RandomState(5)
    q, k, v = (mx.nd.array(rng.normal(0, 1, (BATCH, h, L, 8)))
               for h in (8, 2, 2))
    assert mx.nd._contrib_causal_attention(q, k, v).shape == (BATCH, 8, L, 8)
