"""A decoder whose attention layers differ in the keys they see and in their
rotary form (sliding-window attention with the default rotary, full causal
attention with YaRN's), over experts under a softmax router and an untied
head, at small sizes on the CPU, against the benchmark's plain reference
(benchmark/reference/mellum_moe.py: float32, ``highest``, nothing of the
program); the window mask's list of tiles and, the attention kernels
interpreted, the kernels under it against the XLA attention reference;
YaRN's frequencies against their formula; and the programs of what was there
before: the default rotary's and the layer-typed stack's for the model it
first built."""
import hashlib
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler
from mxnet_tpu.gluon.block import functional_call
from mxnet_tpu.gluon.model_zoo import short_conv_lm, window_moe_lm
from mxnet_tpu.gluon.nn import decoder_layers
from mxnet_tpu.ops import decoder_ops, pallas_ops
from mxnet_tpu.ops.registry import get_op

from benchmark.generators import next_token
from benchmark.reference import common as reference
from benchmark.reference import mellum_moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
L, BATCH, WINDOW = 48, 2, 8
SLIDING, FULL = "sliding_attention", "full_attention"
YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 4,
        "original_max_position_embeddings": 16, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 0.1 * math.log(4) + 1}
# one period of the layer kinds; YaRN's original context of 16 positions,
# which the sequence of 48 passes; 4 of 16 experts held, from the fifth
CONFIG = dict(
    reference="mellum_moe", attention_bias=False, head_dim=64,
    hidden_act="silu", hidden_size=256, layer_types=[SLIDING] * 3 + [FULL],
    mlp_layer_types=["sparse"] * 4, moe_intermediate_size=32,
    norm_topk_prob=True, num_attention_heads=4, num_key_value_heads=2,
    num_experts=4, num_experts_per_tok=4, num_hidden_layers=4,
    rms_norm_eps=1e-6, sliding_window=WINDOW, tie_word_embeddings=False,
    vocab_size=96, use_sliding_window=True,
    rope_parameters={FULL: YARN, SLIDING: {"rope_type": "default",
                                           "rope_theta": 500000}},
    deployment={"num_experts_total": 16, "first_expert": 4})
LEAVES = (
    "embed_weight", "head_weight", "final_norm_gamma", "operator_norm_gamma",
    "ffn_norm_gamma", "attn_q_weight", "attn_k_weight", "attn_v_weight",
    "attn_o_weight", "attn_q_norm_gamma", "attn_k_norm_gamma",
    "moe_router_weight", "moe_gate_weight", "moe_up_weight",
    "moe_down_weight")


def _batch(seed=0):
    return next_token.make_pool(CONFIG, {"batch": BATCH, "seq_len": L},
                                seed, 1)[0]


def _seeded(config, seed=7):
    """The seed's weights with the norms away from 1 and the router's
    logits spread, so that both matter."""
    params, _ = reference.xavier_init(config, seed)
    key = jax.random.PRNGKey(3)
    for i, name in enumerate(sorted(params)):
        if name.endswith("_gamma"):
            params[name] = params[name] + 0.3 * jax.random.normal(
                jax.random.fold_in(key, i), params[name].shape)
        elif name.endswith("router_weight"):
            params[name] = params[name] * 4
    return params


@pytest.fixture(scope="module")
def model():
    net = window_moe_lm.build(CONFIG)
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
        return window_moe_lm.loss(
            [mx.nd.NDArray(_logits(net, values, tokens))],
            mx.nd.NDArray(jnp.asarray(targets)),
            mx.nd.NDArray(jnp.asarray(weight)))._data.reshape(())
    return jax.value_and_grad(loss)(values)


def _reference_loss(config, params, batch):
    ops = reference.Ops()
    return jax.value_and_grad(lambda p: mellum_moe.loss(
        config, ops, p, {}, tuple(jnp.asarray(a) for a in batch))[0])(params)


def _cell_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mellum2_12b_a2p5b_ep8.json")) as f:
        return json.load(f)


# -- the window mask and its tiles -------------------------------------------

def test_mask_visible_at_the_window_edge():
    mask = ("window", WINDOW)
    i = np.arange(30)
    assert not pallas_ops.mask_visible(mask, i, i - WINDOW).any()
    assert pallas_ops.mask_visible(mask, i, i - WINDOW + 1)[
        i - WINDOW + 1 >= 0].all()
    assert pallas_ops.mask_visible(mask, i, i).all()
    assert not pallas_ops.mask_visible(mask, i, i + 1).any()
    # a window of 1 is the diagonal alone
    q, k = np.meshgrid(i, i, indexing="ij")
    assert (pallas_ops.mask_visible(("window", 1), q, k) == (q == k)).all()


def _band_tiles(length, block, window):
    """{(query tile, key tile): 1 partly, 2 wholly visible}, counted pair by
    pair without ``mask_visible``."""
    n = length // block
    found = {}
    cols = np.arange(length)
    for qi in range(n):
        rows = np.arange(qi * block, (qi + 1) * block)[:, None]
        seen = ((cols <= rows) & (cols > rows - window)).reshape(
            block, n, block)
        for ki in range(n):
            if seen[:, ki].all():
                found[(qi, ki)] = 2
            elif seen[:, ki].any():
                found[(qi, ki)] = 1
    return found


@pytest.mark.parametrize("length,block,window,visited", [
    (16384, 512, 1024, 93), (8192, 512, 1024, 45), (256, 32, 8, 15),
    (256, 32, 32, 15), (256, 32, 40, 21), (96, 32, 200, 6)])
def test_window_tile_list_is_a_count_by_pairs(length, block, window, visited):
    n = length // block
    q_tile, k_tile, flag = pallas_ops._tile_tables(
        ("window", window), length, length, n, n, block, block)
    state = flag & pallas_ops._STATE
    assert len(flag) == visited
    assert {(int(q), int(k)): int(s) for q, k, s in zip(q_tile, k_tile,
                                                        state)} \
        == _band_tiles(length, block, window)
    # each query tile's entries ascend, first and last marked once
    for qi in range(n):
        at = np.nonzero(q_tile == qi)[0]
        assert (np.diff(k_tile[at]) > 0).all()
        assert flag[at[0]] & pallas_ops._FIRST
        assert flag[at[-1]] & pallas_ops._LAST
    if length == 16384:
        # query tile 0 has 1 tile, tile 1 has 2, every other 3: the band's
        # two edges masked, the tile between them wholly visible
        per = np.bincount(q_tile, minlength=n)
        assert per[0] == 1 and per[1] == 2 and (per[2:] == 3).all()
        assert (state[q_tile == 5] == [1, 2, 1]).all()


@pytest.mark.parametrize("rows,tiles,window,batch", [
    (128, (32, 32), 40, 1), (256, (64, 32), 64, 2), (100, (32, 64), 24, 2),
    (96, (32, 32), 8, 1)])
def test_window_kernels_match_reference(rows, tiles, window, batch):
    """Forward and backward, interpreted, four query heads a key/value
    head, against the dense XLA attention under the same mask."""
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (batch, h, rows, 64)),
                           jnp.float32) for h in (8, 2, 2))

    def kernels(q, k, v):
        out = pallas_ops.window_attention(
            q, k, v, window, precision="highest", interpret=True,
            block_q=tiles[0], block_k=tiles[1])
        return jnp.sum(jnp.sin(out)), out

    def oracle(q, k, v):
        out = pallas_ops._attention_reference(q, k, v, None, 0.125,
                                              mask=("window", window))
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
    # the window's reference is not the causal one
    causal = pallas_ops._attention_reference(q, k, v, True, 0.125)
    assert float(jnp.max(jnp.abs(causal - out_w))) > 1e-2
    with pytest.raises(ValueError, match="one square"):
        pallas_ops.window_attention(q, k[:, :, :64], v[:, :, :64], window)
    with pytest.raises(ValueError, match="a window of"):
        pallas_ops.window_attention(q, k, v, 0)


# -- the rotary forms ------------------------------------------------------------

def test_yarn_frequencies_are_the_formula():
    """At the published values: c(r) = 128 ln(8192 / (2 pi r)) / (2 ln 5e5),
    low = floor(c(32)) = 18, high = ceil(c(1)) = 35; 1 below low, 1 / 16
    above high, the linear ramp between."""
    def c(r):
        return 128 * math.log(8192 / (2 * math.pi * r)) / (2 * math.log(5e5))
    low, high = math.floor(c(32)), math.ceil(c(1))
    assert (low, high) == (18, 35)
    scale = decoder_ops.yarn_frequency_scale(128, 5e5, 16, 8192, 32, 1)
    assert scale.shape == (64,)
    for p in range(64):
        ramp = min(max((p - low) / (high - low), 0.0), 1.0)
        assert abs(scale[p] - ((1 - ramp) + ramp / 16)) < 1e-12
    assert scale[18] == 1 and scale[35] == 1 / 16
    # the reference's own count of the same, in float32
    np.testing.assert_allclose(mellum_moe.frequency_scale(dict(
        rope_type="yarn", rope_theta=5e5, factor=16,
        original_max_position_embeddings=8192, beta_fast=32, beta_slow=1),
        128), scale, rtol=1e-6)


def test_yarn_rotary_is_the_formula_written_out():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.normal(0, 1, (2, 3, L, 64)), jnp.float32)
    positions = jnp.arange(L, dtype=jnp.int32)
    attrs = dict(base=5e5, **{k: v for k, v in YARN.items()
                              if k != "rope_theta"})
    got = get_op("_contrib_rotary_embedding").fcompute(attrs, x, positions)
    inv = 5e5 ** (-np.arange(32) / 32) * decoder_ops.yarn_frequency_scale(
        64, 5e5, 4, 16, 32, 1)
    angle = np.arange(L)[:, None] * inv[None, :]
    m = YARN["attention_factor"]
    cos, sin = np.cos(angle) * m, np.sin(angle) * m
    x1, x2 = np.asarray(x[..., :32], np.float64), np.asarray(x[..., 32:],
                                                             np.float64)
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # scores between two rotated heads are scaled by m^2
    q = get_op("_contrib_rotary_embedding").fcompute(attrs, x[:1, :1], positions)
    plain = get_op("_contrib_rotary_embedding").fcompute(
        dict(attrs, attention_factor=1.0), x[:1, :1], positions)
    np.testing.assert_allclose(jnp.sum(q * q), m * m * jnp.sum(plain * plain),
                               rtol=1e-5)
    with pytest.raises(ValueError, match="default or yarn"):
        get_op("_contrib_rotary_embedding").fcompute(
            dict(attrs, rope_type="linear"), x, positions)


def _digest(text):
    """A jaxpr's text without the addresses of the functions it names."""
    return hashlib.sha256(re.sub(r" at 0x[0-9a-f]+", "", text).encode()
                          ).hexdigest()[:16]


def test_default_rotary_traces_the_program_it_traced_before():
    """The digests of the two default forms' jaxprs as they were traced
    before YaRN was added (one position row; three, in sections)."""
    op = get_op("_contrib_rotary_embedding").fcompute
    x = jax.ShapeDtypeStruct((1, 4, 48, 64), jnp.float32)
    one = jax.ShapeDtypeStruct((48,), jnp.int32)
    three = jax.ShapeDtypeStruct((3, 48), jnp.int32)
    assert _digest(str(jax.make_jaxpr(lambda x, p: op(
        {"base": 5e5}, x, p))(x, one))) == "acc4387925665c0e"
    assert _digest(str(jax.make_jaxpr(lambda x, p: op(
        {"base": 1e7, "sections": (16, 8, 8)}, x, p))(x, three))) \
        == "0f22f84c47e4b4ae"


def test_the_first_layer_typed_model_traces_the_program_it_traced_before():
    """``short_conv_lm.build`` over the shared stack: the digest of its
    gradient's jaxpr at tests/test_short_conv_lm.py's size as it was traced
    before the stack was shared."""
    from test_short_conv_lm import CONFIG as SHORT_CONV
    net = short_conv_lm.build(SHORT_CONV)
    net.initialize(mx.init.Zero(), ctx=mx.current_context())
    values = {k: p.data()._data for k, p in net.collect_params().items()}

    def total(v, tokens):
        return jnp.sum(functional_call(net, v, tokens, training=True)[0][0])
    text = str(jax.make_jaxpr(jax.grad(total))(
        values, jnp.zeros((2, 32), jnp.int32)))
    assert _digest(text) == "273a70d9ff95fa07"


# -- the model against the plain reference ---------------------------------------

def test_parameters_carry_the_reference_names(model):
    net, params = model
    shapes = mellum_moe.param_shapes(CONFIG)
    held = {k[len(net.prefix):]: p for k, p in net.collect_params().items()
            if p.grad_req != "null"}
    assert sorted(held) == sorted(shapes) == sorted(params)
    for name, p in held.items():
        assert tuple(p.shape) == tuple(shapes[name]), name
    # untied: the head has a leaf of its own
    assert net.head.weight is not net.embed.weight
    assert shapes["head_weight"] == (96, 256)
    assert shapes["layer3_moe_router_weight"] == (16, 256)


def test_layer_kinds_choose_window_and_rotary(model):
    net, _ = model
    found = [(type(l.operator).__name__, l.operator._window,
              l.operator._rope.get("rope_type"), type(l.feed_forward).__name__)
             for l in net.layers]
    assert found == [("CausalAttention", WINDOW, None, "HeldExpertsMoE")] * 3 \
        + [("CausalAttention", None, "yarn", "HeldExpertsMoE")]
    assert net.layers[3].operator._rope["attention_factor"] \
        == YARN["attention_factor"]
    cell = _cell_config()
    assert len(cell["layer_types"]) == 28 and cell["sliding_window"] == 1024
    assert short_conv_lm.held_layers(cell) == [(SLIDING, False)] * 3 + [
        (FULL, False)]
    assert mellum_moe._sizes(cell)["kinds"] == (SLIDING,) * 3 + (FULL,)
    for change, said in ((dict(tie_word_embeddings=True), "tie_word"),
                         (dict(layer_types=["sliding_attention", "mamba",
                                            SLIDING, FULL]), "operator is"),
                         (dict(mlp_layer_types=["dense"] * 4), "dense"),
                         (dict(mlp_layer_types=["moe"] * 4), "dense or sparse"),
                         (dict(rope_parameters={FULL: dict(
                             YARN, rope_type="longrope"), SLIDING: YARN}),
                          "default or yarn")):
        with pytest.raises(ValueError, match=said):
            window_moe_lm.build(dict(CONFIG, **change))


def test_logits_match_the_reference(model):
    net, params = model
    tokens = jnp.asarray(_batch()[0])
    with jax.default_matmul_precision("highest"):
        logits = _logits(net, params, tokens)
    want = mellum_moe.network(CONFIG, reference.Ops(), params, tokens, True)
    assert logits.shape == (BATCH, L, 96)
    assert float(jnp.max(jnp.abs(logits - want))) < 1e-4


@pytest.mark.parametrize("kind", LEAVES)
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
        scale = float(jnp.max(jnp.abs(want[name])))
        assert scale > 0, name
        assert float(jnp.max(jnp.abs(grads[name] - want[name]))) \
            < 1e-4 * scale, name


@pytest.mark.parametrize("kind", [SLIDING, FULL])
def test_a_changed_row_moves_nothing_before_it_nor_past_the_window(kind):
    rng = np.random.RandomState(2)
    block = decoder_layers.CausalAttention(
        64, 4, 2, 16, 5e5, 1e-6, window=WINDOW if kind == SLIDING else None,
        rope=window_moe_lm.rotary(CONFIG["rope_parameters"][kind])[1])
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
    if kind == SLIDING:     # rows at .. at + W - 1 and no other
        assert changed[0].nonzero()[0].tolist() == list(range(at, at + WINDOW))
    else:
        assert changed[0, at:].all()


def test_the_shares_of_a_routed_layer_add_up_to_the_uncut_layer():
    """4 chips that hold 4 of 16 experts each, by the program's layer, against
    the reference given all 16: the router, which every chip computes alike,
    is in each share and each pair lands on one share."""
    prefix = "layer3_"
    rng = np.random.RandomState(1)
    y = jnp.asarray(rng.normal(0, 1, (BATCH * L, 256)), jnp.float32)
    uncut = dict(CONFIG, num_experts=16, deployment={})
    whole = {k: v for k, v in reference.xavier_init(uncut, 11)[0].items()
             if k.startswith(prefix)}
    whole[prefix + "moe_router_weight"] = 4 * whole[
        prefix + "moe_router_weight"]
    s = mellum_moe._sizes(uncut)
    want, _ = mellum_moe.moe(s, reference.Ops(), whole, prefix, y, None, True)
    _, expert = mellum_moe.route(s, reference.Ops(), whole, prefix, y)
    assert len(np.unique(np.asarray(expert) // 4)) == 4     # every chip works
    op = get_op("_contrib_moe_held_experts").fcompute
    total, pairs = jnp.zeros_like(y), 0
    with jax.default_matmul_precision("highest"):
        for chip in range(4):
            rows = slice(chip * 4 * 32, (chip + 1) * 4 * 32)
            out, load = op(
                {"experts_per_token": 4, "expert_width": 32,
                 "first_expert": 4 * chip},
                y, whole[prefix + "moe_router_weight"],
                whole[prefix + "moe_gate_weight"][rows],
                whole[prefix + "moe_up_weight"][rows],
                whole[prefix + "moe_down_weight"].reshape(16, 256, 32)[
                    4 * chip:4 * chip + 4].reshape(-1, 32))
            total, pairs = total + out, pairs + float(load[0])
    assert pairs == BATCH * L * 4
    assert float(jnp.max(jnp.abs(total - want))) < 1e-5 * float(
        jnp.max(jnp.abs(want)))


def test_reference_layouts_agree(model, monkeypatch):
    """The layout flops.py counts (every chunk of queries against the keys
    from the first its first query sees to its end, the routed pairs
    gathered into a buffer) and the layout that is trained (one chunk's and
    one expert's program, looped, a sliding layer's chunk against its band):
    the same logits and gradients."""
    _, params = model
    monkeypatch.setattr(mellum_moe, "CHUNK", 8)       # 6 chunks
    tokens = jnp.asarray(_batch()[0])
    ops = reference.Ops()

    def total(looped):
        return jax.value_and_grad(lambda p: jnp.sum(jnp.tanh(
            mellum_moe.network(CONFIG, ops, p, tokens, looped))))(params)

    (a, ga), (b, gb) = total(False), total(True)
    assert abs(float(a) - float(b)) < 1e-4 * abs(float(a))
    for name in ga:
        scale = float(jnp.max(jnp.abs(ga[name]))) + 1e-12
        assert float(jnp.max(jnp.abs(ga[name] - gb[name]))) < 1e-4 * scale, name


@pytest.mark.parametrize("fault", [
    "no_window", "window_doubled", "no_yarn", "no_attention_factor",
    "rotary_swapped", "drop_expert"])
def test_reference_faults_move_the_loss_or_a_gradient(model, fault):
    from benchmark.checks import faults_mellum
    _, params = model
    sound, sound_grads = _reference_loss(CONFIG, params, _batch())
    with faults_mellum.planted(fault):
        faulty, grads = _reference_loss(CONFIG, params, _batch())
    moved = max(float(jnp.max(jnp.abs(grads[k] - sound_grads[k])))
                for k in grads)
    assert abs(float(faulty) - float(sound)) > 1e-4 or moved > 1e-4


# -- required work ---------------------------------------------------------------

def _reader(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), os.path.join(ROOT, "benchmark", "metrics",
                                             name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_count_by_hand_of_the_cell():
    """The count of a trained sequence at the cell's size by hand (3,770.0
    GMAC forward), and flops.py's walk within 2% above it: its chunks of 128
    queries end past the diagonal, and a sliding layer's band begins 1,023
    keys before a chunk's first query."""
    from benchmark import flops, harness
    T, d, W = 16384, 2304, 1024
    projections = T * d * (4096 + 512 + 512 + 4096) * 4
    full = T * (T + 1) // 2
    window = W * (W + 1) // 2 + (T - W) * W
    assert (full, window) == (134225920, 16253440)
    scores = (full + 3 * window) * 32 * 128 * 2
    head = T * d * 12288
    experts = T * 3 * d * 896 * 8 * 8 // 64 * 4
    router = T * d * 64 * 4
    for got, want in ((projections, 1391.6), (full * 8192, 1099.6),
                      (3 * window * 8192, 399.4), (head, 463.9),
                      (experts, 405.9), (router, 9.7)):
        assert abs(got / 1e9 - want) < 0.1
    by_hand = projections + scores + head + experts + router
    assert abs(by_hand / 1e9 - 3770.0) < 0.1
    assert abs(6 * by_hand / 1e9 - 22620) < 1
    cell = harness.Cell("mellum2_12b_a2p5b_ep8.sft_b1_s16384", ROOT)
    walked = flops.forward_macs(cell)
    band = sum(min(lo + 128, W + 127) for lo in range(0, T, 128)) * 128
    assert walked == by_hand + ((T * (T + 128) // 2 - full)
                                + 3 * (band - window)) * 32 * 128 * 2
    assert 0 < walked / by_hand - 1 < 0.02


def test_rooflines_count_by_hand():
    config = _cell_config()
    window = _reader("window_attn_roofline.train")
    assert window.visible_pairs(16384, 1024) == 16253440
    assert window.visible_pairs(100, 1024) == 5050
    # pairs x 32 heads x 128 x 2 products x 2 FLOPs x 3 passes x 3 layers:
    # 12.1 ms of a v5e's peak a sequence
    flops = window.required_flops(config, {"seq_len": 16384})
    assert flops == 16253440 * 32 * 128 * 2 * 2 * 3 * 3
    assert abs(flops / 197e12 * 1e3 - 12.16) < 0.01
    full = _reader("full_attn_roofline.train")
    flops = full.required_flops(config, {"seq_len": 16384})
    assert flops == 134225920 * 32 * 128 * 2 * 2 * 3
    assert abs(flops / 197e12 * 1e3 - 33.49) < 0.01
    assert window.forward_bytes(config, {"seq_len": 16384}) \
        == 4 * 128 * 32 * 16384 * 8 * 3

    # a model with no sliding layer, or no table to read: silent
    class Cell:
        config = json.load(open(os.path.join(
            ROOT, "benchmark", "configs", "lfm2_24b_a2b_ep8.json")))
        traffic = {"seq_len": 8192, "batch": 2}
    run = {"cell": Cell, "peaks": {"flops_per_s": 197e12}, "trace": None}
    assert window.read(run) is None and full.read(run) is None
    Cell.config = config
    assert window.read(dict(run)) is None and full.read(dict(run)) is None


# -- through the compiled step ---------------------------------------------------

def test_compiled_step_trains_and_recomputes(monkeypatch):
    from mxnet_tpu.module.compiled_step import CompiledTrainStep
    profiler.reset_spans()
    np.random.seed(5)
    mx.random.seed(5)
    net = window_moe_lm.build(CONFIG)
    net.initialize(mx.init.Xavier(), ctx=mx.current_context())
    wrapped = []
    checkpoint = jax.checkpoint
    monkeypatch.setattr(jax, "checkpoint", lambda f, **kw: (
        wrapped.append((f.__name__, kw.get("policy"))),
        checkpoint(f, **kw))[1])
    step = CompiledTrainStep.from_block(
        net, window_moe_lm.loss,
        mx.optimizer.create("adam", learning_rate=1e-3),
        n_inputs=window_moe_lm.N_INPUTS)
    batch = tuple(mx.nd.array(a, dtype=a.dtype) for a in _batch())
    losses = [float(step.step(*batch).asnumpy()[0]) for _ in range(4)]
    assert losses[-1] < losses[0]
    assert [name for name, _ in wrapped] == ["pure"] * 4
    assert all(layer._flags == {
        "remat": True, "remat_policy": ("attn.out", "attn.lse", "moe.table")}
        for layer in net.layers)
    totals = profiler.totals()
    assert totals["moe.rows"]["count"] == 4 * BATCH * L
    assert totals["moe.experts_held"]["max"] == 4


def test_window_operator_is_registered_for_nd_and_sym():
    assert callable(mx.nd._contrib_window_attention) and callable(
        mx.sym._contrib_window_attention)
    rng = np.random.RandomState(5)
    q, k, v = (mx.nd.array(rng.normal(0, 1, (BATCH, h, L, 16)))
               for h in (4, 2, 2))
    got = mx.nd._contrib_window_attention(q, k, v, window=WINDOW)
    want = pallas_ops._attention_reference(
        q._data, k._data, v._data, None, 0.25, mask=("window", WINDOW))
    np.testing.assert_allclose(got.asnumpy(), want, rtol=1e-5, atol=1e-5)
