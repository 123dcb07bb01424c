"""A decoder whose layers are single blocks of three kinds (Mamba-2 state-space
mixers, relu² experts beside a shared expert under a sigmoid router, and
attention without rotary or query/key norm), at small sizes on the CPU,
against the benchmark's plain reference (benchmark/reference/nemotron_h.py:
float32, ``highest``, nothing of the program); the state-space scan, in XLA
and in its kernels interpreted, against the sequential recurrence across
chunk boundaries; the convolution's kernels and the relu² experts' kernels,
interpreted, against their XLA forms; and the programs of what was there
before: the SiLU-gated experts' and the sixth cell's stack."""
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
from mxnet_tpu.gluon.model_zoo import hybrid_ssm_lm, short_conv_lm
from mxnet_tpu.ops import pallas_ops
from mxnet_tpu.ops.registry import get_op
from mxnet_tpu.parallel import moe as moe_mod

from benchmark.generators import next_token
from benchmark.reference import common as reference
from benchmark.reference import nemotron_h

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
L, BATCH = 40, 2
# five layers, each kind once or twice: 4 Mamba-2 heads of 8 with a state of
# 16 in 2 groups, chunks of 8 rows; 4 of 16 relu² experts held, 3 picked
CONFIG = dict(
    reference="nemotron_h", hidden_size=64, hybrid_override_pattern="MEM*E",
    num_hidden_layers=5, layer_norm_epsilon=1e-5, mamba_num_heads=4,
    mamba_head_dim=8, ssm_state_size=16, n_groups=2, conv_kernel=4,
    chunk_size=8, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    moe_intermediate_size=24, moe_shared_expert_intermediate_size=48,
    n_shared_experts=1, n_routed_experts=4, num_experts_per_tok=3,
    routed_scaling_factor=2.5, router_norm_eps=1e-20, vocab_size=96,
    mlp_hidden_act="relu2", tie_word_embeddings=False,
    deployment={"num_experts_total": 16, "first_expert": 4})
LEAVES = (
    "embed_weight", "head_weight", "final_norm_gamma", "norm_gamma",
    "ssm_in_weight", "ssm_out_weight", "ssm_conv_weight", "ssm_conv_bias",
    "ssm_dt_bias", "ssm_A_log", "ssm_D", "ssm_norm_gamma", "attn_q_weight",
    "attn_k_weight", "attn_v_weight", "attn_o_weight", "moe_router_weight",
    "moe_up_weight", "moe_down_weight", "moe_shared_up_weight",
    "moe_shared_down_weight")
CELL = "nemotron3_nano_30b_a3b_ep16.ssm_sft_b1_s8192"


def _batch(seed=0):
    return next_token.make_pool(CONFIG, {"batch": BATCH, "seq_len": L},
                                seed, 1)[0]


def _published_steps(key, heads):
    """(A_log, dt_bias) as Mamba-2 initialises them: ``A`` uniform on [1,
    16], ``dt`` log-uniform on [1e-3, 1e-1]."""
    a, b = jax.random.split(key)
    step = jnp.exp(jax.random.uniform(b, heads, minval=math.log(1e-3),
                                      maxval=math.log(1e-1)))
    return (jnp.log(jax.random.uniform(a, heads, minval=1.0, maxval=16.0)),
            step + jnp.log(-jnp.expm1(-step)))


def _seeded(config, seed=7):
    """The seed's weights with the norms away from 1, the router's logits
    spread, a selection bias, the convolution's taps at their published
    size and the decays, steps and skip as Mamba-2 initialises them, so that
    all of them matter."""
    params, _ = reference.xavier_init(config, seed)
    key = jax.random.PRNGKey(3)
    for i, name in enumerate(sorted(params)):
        k = jax.random.fold_in(key, i)
        shape = params[name].shape
        if name.endswith("_gamma"):
            params[name] = params[name] + 0.3 * jax.random.normal(k, shape)
        elif name.endswith("router_weight"):
            params[name] = params[name] * 4
        elif name.endswith("expert_bias"):
            params[name] = 0.1 * jax.random.normal(k, shape)
        elif name.endswith(("conv_weight", "conv_bias")):
            params[name] = jax.random.uniform(k, shape, minval=-0.5,
                                              maxval=0.5)
        elif name.endswith("ssm_D"):
            params[name] = 1.0 + 0.1 * jax.random.normal(k, shape)
        elif name.endswith("A_log"):
            params[name], params[name.replace("A_log", "dt_bias")] = \
                _published_steps(k, shape)
    return params


@pytest.fixture(scope="module")
def model():
    net = hybrid_ssm_lm.build(CONFIG)
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
        return hybrid_ssm_lm.loss(
            [mx.nd.NDArray(_logits(net, values, tokens))],
            mx.nd.NDArray(jnp.asarray(targets)),
            mx.nd.NDArray(jnp.asarray(weight)))._data.reshape(())
    return jax.value_and_grad(loss)(values)


def _reference_loss(config, params, batch):
    ops = reference.Ops()
    return jax.value_and_grad(lambda p: nemotron_h.loss(
        config, ops, p, {}, tuple(jnp.asarray(a) for a in batch))[0])(params)


def _cell_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron3_nano_30b_a3b_ep16.json")) as f:
        return json.load(f)


def _max_gap(got, want):
    return float(jnp.max(jnp.abs(got - want))) / (
        float(jnp.max(jnp.abs(want))) + 1e-30)


# -- the scan ----------------------------------------------------------------

def _sequential(x, dt, A, B, C, D):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D
    x_t``, one row at a time."""
    heads, groups = x.shape[2], B.shape[2]
    Bh, Ch = (jnp.repeat(t, heads // groups, axis=2) for t in (B, C))

    def row(S, t):
        xt, dtt, bt, ct = t
        S = jnp.exp(dtt * A)[..., None, None] * S \
            + dtt[..., None, None] * xt[..., :, None] * bt[..., None, :]
        return S, jnp.einsum("bhpn,bhn->bhp", S, ct)
    S0 = jnp.zeros(x.shape[:1] + (heads, x.shape[3], B.shape[3]))
    _, ys = jax.lax.scan(row, S0, tuple(jnp.moveaxis(t, 1, 0)
                                        for t in (x, dt, Bh, Ch)))
    return jnp.moveaxis(ys, 0, 1) + D[:, None] * x


def _scan_operands(length, seed=0, heads=4, groups=2, width=8):
    """``heads`` heads of ``width`` in ``groups`` groups of a state of 16,
    with published-style decays and steps."""
    r = np.random.RandomState(seed)
    step = np.exp(r.uniform(np.log(1e-3), np.log(1e-1),
                            (BATCH, length, heads)))
    return [jnp.asarray(a, jnp.float32) for a in (
        r.normal(size=(BATCH, length, heads, width)), step,
        -r.uniform(1, 16, heads), r.normal(size=(BATCH, length, groups, 16)),
        r.normal(size=(BATCH, length, groups, 16)), r.normal(size=heads))]


@pytest.mark.parametrize("form,length,heads,groups,width", [
    pytest.param(form, length, 4, 2, 8, id="%d-%s" % (length, form))
    for length in (40, 44) for form in ("xla", "kernels")] + [
    # a group of 8 heads, as the seventh cell's, whose shared products
    # cover all 8: one group, and two
    pytest.param("kernels", 44, 8, 1, 8, id="44-kernels-8x1"),
    pytest.param("kernels", 44, 16, 2, 8, id="44-kernels-16x2"),
    # heads of 64, two to a tile of 128 lanes, as the cell's: two tiles
    pytest.param("kernels", 44, 4, 1, 64, id="44-kernels-4x1-of-64")])
def test_scan_is_the_sequential_recurrence(form, length, heads, groups,
                                           width):
    """Forward and every gradient, chunks of 8 over 40 rows and over 44 (the
    last chunk padded), the kernels interpreted."""
    operands = _scan_operands(length, heads=heads, groups=groups,
                              width=width)
    cot = jax.random.normal(jax.random.PRNGKey(1), operands[0].shape)
    interpret = True if form == "kernels" else None

    def total(scan):
        return lambda *a: jnp.sum(scan(*a) * cot)

    def scan(*a):
        return pallas_ops.ssd_scan(*a, chunk=8, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        got = scan(*operands)
        want = _sequential(*operands)
        grads = jax.grad(total(scan), range(6))(*operands)
        wants = jax.grad(total(_sequential), range(6))(*operands)
    assert _max_gap(got, want) < 1e-5
    for g, w in zip(grads, wants):
        assert g.shape == w.shape and _max_gap(g, w) < 1e-5
    # a state that did not cross the chunks' edges is another recurrence
    cut = _sequential(*[t[:, :8] for t in operands[:2]], operands[2],
                      *[t[:, :8] for t in operands[3:5]], operands[5])
    assert _max_gap(got[:, :8], cut) < 1e-5
    dropped = jnp.concatenate([_sequential(
        *[t[:, i:i + 8] for t in operands[:2]], operands[2],
        *[t[:, i:i + 8] for t in operands[3:5]], operands[5])
        for i in range(0, length, 8)], axis=1)
    assert _max_gap(dropped, want) > 1e-3


def _pallas_names(jaxpr):
    """The names of the ``pallas_call``s a jaxpr holds, at any depth."""
    names = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.add(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _pallas_names(sub)
    return names


def _group_heads():
    return profiler.totals()["ssm.scan_group_heads"]["max"]


def test_scan_counts_its_chunks_and_runs_two_kernels():
    operands = _scan_operands(44)
    profiler.reset_spans()
    step = jax.grad(lambda *a: jnp.sum(pallas_ops.ssd_scan(
        *a, chunk=8, interpret=True)), range(6))
    assert _pallas_names(jax.make_jaxpr(step)(*operands).jaxpr) == {
        "ssd_scan_fwd", "ssd_scan_bwd"}
    assert profiler.totals()["ssm.chunks"]["max"] == BATCH * 6
    assert _group_heads() == 2
    profiler.reset_spans()
    jax.make_jaxpr(step)(*_scan_operands(44, heads=16, groups=2))
    assert _group_heads() == 8
    profiler.reset_spans()
    jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(pallas_ops.ssd_scan(
        *a, chunk=8)), range(6)))(*operands)
    assert _group_heads() == 0
    with pytest.raises(ValueError, match="whole groups"):
        pallas_ops.ssd_scan(operands[0][:, :, :3], *operands[1:3],
                            *operands[3:])


# -- the convolution's kernels ------------------------------------------------

def _conv_operands(batch, rows, width, channels, taps=4, seed=0):
    r = np.random.RandomState(seed)
    return [jnp.asarray(a, jnp.float32) for a in (
        r.normal(size=(batch, rows, width)), r.normal(size=(channels, taps)),
        r.normal(size=channels))]


def _conv_tiles():
    return profiler.totals()["ssm.conv_tiles"]["max"]


@pytest.mark.parametrize("batch,rows,channels,tile,begin,width", [
    (2, 64, 256, 16, 0, 256), (1, 48, 128, 24, 0, 128),
    (2, 32, 128, 16, 128, 384), (1, 64, 256, 32, 256, 520)])
def test_ssm_conv_kernels_match_xla(batch, rows, channels, tile, begin,
                                    width):
    """The two kernels, interpreted, over several tiles of rows (a tile's
    first rows read the last of the tile before it, and its gradient the
    first of the tile after), against XLA's shifted copies: the output and
    the gradients of ``x``, the taps and the bias; handed a wider array, the
    channels from ``begin`` are convolved and the others' gradient is 0."""
    x, w, b = _conv_operands(batch, rows, width, channels)
    cot = jax.random.normal(jax.random.PRNGKey(2), (batch, rows, channels))

    def kernels(x, w, b):
        out = pallas_ops.ssm_conv(x, w, b, begin=begin, interpret=True,
                                  rows=tile)
        return jnp.sum(out * cot), out

    def oracle(x, w, b):
        out = pallas_ops._ssm_conv_reference(
            x[..., begin:begin + channels], w, b)
        return jnp.sum(out * cot), out

    profiler.reset_spans()
    got, out = jax.grad(kernels, (0, 1, 2), has_aux=True)(x, w, b)
    assert _conv_tiles() == batch * (rows // tile)
    want, out_w = jax.grad(oracle, (0, 1, 2), has_aux=True)(x, w, b)
    assert _max_gap(out, out_w) < 1e-5
    for a, c in zip(got, want):
        assert a.shape == c.shape and _max_gap(a, c) < 1e-5
    outside = np.ones(width, bool)
    outside[begin:begin + channels] = False
    assert not np.any(np.asarray(got[0])[..., outside])
    assert _pallas_names(jax.make_jaxpr(jax.grad(
        lambda *a: kernels(*a)[0], (0, 1, 2)))(x, w, b).jaxpr) == {
        "ssm_conv_fwd", "ssm_conv_bwd"}


def test_ssm_conv_kernels_see_no_row_ahead_and_zeros_before():
    """A changed row moves no output before it, in its tile or the one
    before; each sequence's first rows see zeros before them, not the
    previous sequence's last rows."""
    x, w, b = _conv_operands(2, 48, 128, 128)

    def conv(x):
        return pallas_ops.ssm_conv(x, w, b, interpret=True, rows=16)
    out = conv(x)
    for row in (16, 21, 47):
        moved = conv(x.at[:, row].add(1.0))
        assert _max_gap(moved[:, :row], out[:, :row]) == 0
        assert float(jnp.min(jnp.max(jnp.abs(
            moved[:, row] - out[:, row]), axis=-1))) > 0
    for t in range(3):
        u = b + sum(w[:, 3 - s] * x[:, t - s] for s in range(t + 1))
        assert _max_gap(out[:, t], jax.nn.silu(u)) < 1e-5


@pytest.mark.parametrize("rows,channels,taps,tile,begin,width", [
    (48, 96, 4, 16, 0, 96),         # channels no multiple of 128
    (40, 128, 4, 16, 0, 128),       # rows no multiple of the tile
    (48, 128, 10, 16, 0, 128),      # 9 rows before a row: past the halo
    (48, 128, 4, 16, 64, 256)])     # channels beginning inside a block
def test_ssm_conv_falls_back_to_xla_where_kernels_cannot_run(
        rows, channels, taps, tile, begin, width):
    x, w, b = _conv_operands(2, rows, width, channels, taps)
    profiler.reset_spans()
    step = jax.grad(lambda *a: jnp.sum(pallas_ops.ssm_conv(
        *a, begin=begin, interpret=True, rows=tile)), (0, 1, 2))
    assert _pallas_names(jax.make_jaxpr(step)(x, w, b).jaxpr) == set()
    assert _conv_tiles() == 0
    got = pallas_ops.ssm_conv(x, w, b, begin=begin, interpret=True,
                              rows=tile)
    assert _max_gap(got, pallas_ops._ssm_conv_reference(
        x[..., begin:begin + channels], w, b)) == 0
    with pytest.raises(ValueError, match="channels"):
        pallas_ops.ssm_conv(x, w, b, begin=width - channels + 1)


# -- the blocks against the reference's ---------------------------------------

def test_mamba_block_matches_the_reference(model):
    net, params = model
    block = net.layers[0].block
    u = jax.random.normal(jax.random.PRNGKey(4), (BATCH, L, 64))
    cot = jax.random.normal(jax.random.PRNGKey(5), u.shape)
    weights = {k: v for k, v in params.items() if k.startswith("layer0_ssm_")}
    s = nemotron_h._sizes(CONFIG)

    def program(w, u):
        values = {net.prefix + k: v for k, v in w.items()}
        return functional_call(block, values, u, jnp.arange(L),
                               training=True)[0][0]

    def plain(w, u):
        return nemotron_h.mamba(s, reference.Ops(), w, "layer0_", u)

    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(program, weights, u)
        got = vjp(cot)
    want, vjp_w = jax.vjp(plain, weights, u)
    wants = vjp_w(cot)
    assert sorted(weights) == sorted(k[len(net.prefix):] for k in
                                     block.collect_params())
    assert _max_gap(out, want) < 1e-5
    assert _max_gap(got[1], wants[1]) < 1e-5
    for name in weights:
        assert _max_gap(got[0][name], wants[0][name]) < 1e-4, name


def _held_relu2(x, router_w, up_w, down_w, k, first, bias):
    return moe_mod.moe_held_apply(x, router_w, None, up_w, down_w, k,
                                  first_expert=first, scoring="sigmoid",
                                  scale=2.5, bias=bias, act="relu2",
                                  eps=1e-20)


def test_relu2_experts_are_a_dense_loop_over_the_experts():
    """Each row's held picks, expert by expert over all rows, weighted by the
    router's renormalised sigmoid scores times 2.5, and the gradients of
    every operand."""
    rng = np.random.RandomState(3)
    T, d, f, E, held, k = 48, 32, 24, 16, 4, 3
    x, router_w = (jnp.asarray(rng.normal(0, 1, s), jnp.float32)
                   for s in ((T, d), (E, d)))
    up_w, down_w = (jnp.asarray(rng.normal(0, 0.3, s), jnp.float32)
                    for s in ((held, f, d), (held, d, f)))
    bias = jnp.asarray(rng.normal(0, 0.1, E), jnp.float32)

    def loop(x, router_w, up_w, down_w):
        scores = jax.nn.sigmoid(jnp.dot(x, router_w.T, precision="highest"))
        _, picks = jax.lax.top_k(scores + bias, k)
        picked = jnp.take_along_axis(scores, picks, axis=-1)
        weights = 2.5 * picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
        out = jnp.zeros_like(x)
        for e in range(held):
            w_e = jnp.sum(jnp.where(picks == 4 + e, weights, 0.0), axis=-1)
            h = jnp.square(jax.nn.relu(x @ up_w[e].T))
            out = out + w_e[:, None] * (h @ down_w[e].T)
        return out

    def held_layer(*a):
        return _held_relu2(*a, k, 4, bias)[0]
    cot = jnp.asarray(rng.normal(0, 1, (T, d)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(held_layer, x, router_w, up_w, down_w)
        want, vjp_w = jax.vjp(loop, x, router_w, up_w, down_w)
        grads, wants = vjp(cot), vjp_w(cot)
    assert _max_gap(got, want) < 1e-5
    for g, w in zip(grads, wants):
        assert _max_gap(g, w) < 1e-5


@pytest.mark.parametrize("f", [128, 1000])
def test_relu2_kernels_match_their_xla_form(f):
    """The relu² forms of the grouped products, interpreted, against the
    same products in XLA over one table of 4 experts' tiles of 16 slots; at
    1,000 hidden units, no multiple of 128, the weights' gradients go in
    blocks of 512, the last ragged (488 units), and no unit past the width
    reaches a result."""
    rng = np.random.RandomState(4)
    d, held, tm = 128, 4, 16
    te = jnp.asarray([0, 0, 1, 2, 3, 3], jnp.int32)
    S = te.shape[0] * tm
    x_s, dy_s = (jnp.asarray(rng.normal(0, 1, (S, d)), jnp.float32)
                 for _ in range(2))
    w_s = jnp.asarray(rng.uniform(0, 1, S), jnp.float32)
    up_w = jnp.asarray(rng.normal(0, 0.1, (held, f, d)), jnp.float32)
    down_w = jnp.asarray(rng.normal(0, 0.1, (held, d, f)), jnp.float32)
    found = []
    for interpret in (True, None):
        experts = pallas_ops._Experts(te, tm, d, f, held, interpret=interpret,
                                      act="relu2")
        assert experts.kernels == bool(interpret)
        experts.cdt = None          # float32 products on both sides
        hw, u = experts.hidden(x_s, w_s, None, up_w, keep=True)
        y = experts.down(hw, down_w)
        dx, dw, dg, du = experts.backward(dy_s, None, u, w_s, None, up_w,
                                          down_w)
        found.append((hw, u, y, dx, dw, du) + tuple(
            experts.weight_gradients(x_s, dy_s, dg, du, hw)))
        assert dg is None
    with pytest.raises(ValueError, match="swiglu or relu2"):
        pallas_ops._Experts(te, tm, d, f, held, act="gelu")
    for got, want in zip(*found):
        assert got.shape == want.shape and _max_gap(got, want) < 1e-5


def test_the_shares_of_a_routed_layer_add_up_to_the_uncut_layer():
    """4 chips that hold 2 of 8 experts each, by the program's layer, against
    the reference given all 8: the router, which every chip computes alike,
    is in each share, each pair lands on one share, and the shared expert,
    which every chip computes whole, is counted once."""
    prefix = "layer1_"
    rng = np.random.RandomState(1)
    y = jnp.asarray(rng.normal(0, 1, (BATCH * L, 64)), jnp.float32)
    uncut = dict(CONFIG, n_routed_experts=8, deployment={})
    whole = {k: v for k, v in _seeded(uncut, 11).items()
             if k.startswith(prefix)}
    s = nemotron_h._sizes(uncut)
    want, _ = nemotron_h.moe(s, reference.Ops(), whole, prefix, y, None, True)
    _, expert = nemotron_h.route(s, reference.Ops(), whole, prefix, y)
    assert len(np.unique(np.asarray(expert) // 2)) == 4     # every chip works
    op = get_op("_contrib_moe_held_experts").fcompute
    relu2 = get_op("_contrib_relu2_mlp").fcompute
    total, pairs = jnp.zeros_like(y), 0
    with jax.default_matmul_precision("highest"):
        for chip in range(4):
            rows = slice(chip * 2 * 24, (chip + 1) * 2 * 24)
            out, load = op(
                {"experts_per_token": 3, "expert_width": 24,
                 "first_expert": 2 * chip, "act": "relu2",
                 "scoring": "sigmoid", "scale": 2.5, "router_eps": 1e-20},
                y, whole[prefix + "moe_router_weight"],
                whole[prefix + "moe_up_weight"][rows],
                whole[prefix + "moe_down_weight"].reshape(8, 64, 24)[
                    2 * chip:2 * chip + 2].reshape(-1, 24),
                whole[prefix + "moe_expert_bias"])
            total, pairs = total + out, pairs + float(load[0])
        total = total + relu2({}, y, whole[prefix + "moe_shared_up_weight"],
                              whole[prefix + "moe_shared_down_weight"])
    assert pairs == BATCH * L * 3
    assert _max_gap(total, want) < 1e-5


# -- the stack ----------------------------------------------------------------

def test_held_layers_read_the_pattern(model):
    net, _ = model
    assert short_conv_lm.held_layers(CONFIG) == [
        (kind, None) for kind in "MEM*E"]
    assert {type(l).__name__ for l in net.layers} == {"ResidualLayer"}
    assert [type(l.block).__name__ for l in net.layers] == [
        "Mamba2Mixer", "HeldExpertsMoE", "Mamba2Mixer", "CausalAttention",
        "HeldExpertsMoE"]
    attention = net.layers[3].block
    assert attention.q_norm is None and attention._rope_base is None
    cell = _cell_config()
    assert len(cell["hybrid_override_pattern"]) == 52
    assert [k for k, _ in short_conv_lm.held_layers(cell)] == list(
        "MEMEM*EME")
    assert nemotron_h._sizes(cell)["kinds"] == tuple("MEMEM*EME")
    for change, said in ((dict(tie_word_embeddings=True), "tie_word"),
                         (dict(mlp_hidden_act="silu"), "mlp_hidden_act"),
                         (dict(hybrid_override_pattern="MEM-E"), "M, E or"),
                         (dict(deployment=dict(CONFIG["deployment"],
                                               layers=[0, 1, 2])),
                          "layers held")):
        with pytest.raises(ValueError, match=said):
            hybrid_ssm_lm.build(dict(CONFIG, **change))


def test_parameters_carry_the_reference_names(model):
    net, params = model
    shapes = nemotron_h.param_shapes(CONFIG)
    held = {k[len(net.prefix):]: p for k, p in net.collect_params().items()
            if p.grad_req != "null"}
    assert sorted(held) == sorted(shapes) == sorted(params)
    for name, p in held.items():
        assert tuple(p.shape) == tuple(shapes[name]), name
    assert net.head.weight is not net.embed.weight
    # z, xBC (32 + 2 x 2 x 16) and dt (4)
    assert shapes["layer0_ssm_in_weight"] == (32 + 96 + 4, 64)
    assert shapes["layer1_moe_router_weight"] == (16, 64)


def test_logits_match_the_reference(model):
    net, params = model
    tokens = jnp.asarray(_batch()[0])
    with jax.default_matmul_precision("highest"):
        logits = _logits(net, params, tokens)
    want = nemotron_h.network(CONFIG, reference.Ops(), params, tokens, True)
    assert logits.shape == (BATCH, L, 96)
    assert _max_gap(logits, want) < 1e-5


@pytest.mark.parametrize("kind", LEAVES)
def test_loss_and_gradient_leaves_match_the_reference(model, kind, _cache={}):
    net, params = model
    if not _cache:
        with jax.default_matmul_precision("highest"):
            _cache["program"] = _program_loss(net, params, _batch())
        _cache["reference"] = _reference_loss(CONFIG, params, _batch())
    (loss, grads), (want_loss, want) = _cache["program"], _cache["reference"]
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    leaves = [k for k in want if k.endswith(kind)
              and not (kind == "norm_gamma" and "ssm_norm" in k)]
    assert leaves and sorted(grads) == sorted(want)
    for name in leaves:
        scale = float(jnp.max(jnp.abs(want[name])))
        assert scale > 0, name
        assert float(jnp.max(jnp.abs(grads[name] - want[name]))) \
            < 1e-4 * scale, name


def test_reference_layouts_agree(model, monkeypatch):
    """The layout flops.py counts (attention by chunks of queries against
    the keys up to each chunk's end, the routed pairs gathered into a
    buffer) and the layout that is trained (looped): the same logits and
    gradients."""
    _, params = model
    monkeypatch.setattr(nemotron_h, "CHUNK", 8)
    tokens = jnp.asarray(_batch()[0])
    ops = reference.Ops()

    def total(looped):
        return jax.value_and_grad(lambda p: jnp.sum(jnp.tanh(
            nemotron_h.network(CONFIG, ops, p, tokens, looped))))(params)

    (a, ga), (b, gb) = total(False), total(True)
    assert abs(float(a) - float(b)) < 1e-4 * abs(float(a))
    for name in ga:
        scale = float(jnp.max(jnp.abs(ga[name]))) + 1e-12
        assert float(jnp.max(jnp.abs(ga[name] - gb[name]))) < 1e-4 * scale, \
            name


@pytest.mark.parametrize("fault", [
    "no_carry", "no_skip", "no_gate", "one_group", "conv_ahead", "relu",
    "no_scale", "drop_expert", "no_shared"])
def test_reference_faults_move_the_loss_or_a_gradient(model, fault):
    from benchmark.checks import faults_nemotron
    _, params = model
    sound, sound_grads = _reference_loss(CONFIG, params, _batch())
    with faults_nemotron.planted(fault):
        faulty, grads = _reference_loss(CONFIG, params, _batch())
    moved = max(float(jnp.max(jnp.abs(grads[k] - sound_grads[k])))
                for k in grads)
    assert abs(float(faulty) - float(sound)) > 1e-4 or moved > 1e-4


# -- required work ------------------------------------------------------------

def _reader(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), os.path.join(ROOT, "benchmark", "metrics",
                                             name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_count_by_hand_of_the_cell():
    """The count of a trained sequence at the cell's size by hand (2,948.2
    GMAC forward), and flops.py's walk 4.3 GMAC above it: its chunks of 128
    queries end past the diagonal."""
    from benchmark import flops, harness
    T, d = 8192, 2688
    n, Q, H, P, G, N = 64, 128, 64, 64, 8, 128
    mamba = T * d * (2 * 4096 + 2 * G * N + H) + T * 4096 * d
    scan = n * Q * Q * G * N + n * H * Q * Q * P + 2 * T * H * N * P \
        + H * (n + 1) ** 2 * P * N
    attn = T * d * (4096 + 2 * 256 + 4096) + T * (T + 1) // 2 * 32 * 128 * 2
    routed = T * d * 128 + T * 6 * 8 // 128 * 2 * 1856 * d
    shared = T * 2 * 3712 * d
    head = T * d * 16384
    for got, want in ((mamba, 317.1), (scan, 16.2), (attn, 466.6),
                      (routed, 33.5), (shared, 163.5), (head, 360.8)):
        assert abs(got / 1e9 - want) < 0.1
    by_hand = 4 * (mamba + scan + routed + shared) + attn + head
    assert abs(by_hand / 1e9 - 2948.2) < 0.1
    cell = harness.Cell(CELL, ROOT)
    walked = flops.forward_macs(cell)
    assert walked == by_hand + (T * (T + 128) // 2 - T * (T + 1) // 2) \
        * 32 * 128 * 2
    assert 0 < walked / by_hand - 1 < 0.02


def test_rooflines_count_by_hand():
    config = _cell_config()
    scan = _reader("ssd_scan_roofline.train")
    flops, moved = scan.required(config, 64)
    macs = 64 * (8 * 128 ** 3 + 64 * (128 * 128 * 64 + 2 * 128 * 64 * 128))
    assert flops == 6 * macs and abs(flops / 1e9 - 83.75) < 0.01
    assert abs(moved / 1e9 - 1.147) < 0.001
    assert scan.ssm_layers(config) == 4
    relu2 = _reader("relu2_experts_roofline.train")
    assert relu2.routed_layers(config) == 4
    assert relu2.required_flops(config, {"seq_len": 8192}) \
        == 6 * 3072 * 2 * 1856 * 2688 * 4
    shared = _reader("shared_expert_roofline.train")
    assert shared.required_flops(config, {"seq_len": 8192}) \
        == 6 * 8192 * 2 * 2688 * 3712 * 4
    conv = _reader("ssm_conv_roofline.train")
    assert conv.required_bytes(config, {"batch": 1, "seq_len": 8192}) \
        == 5 * 8192 * (64 * 64 + 2 * 8 * 128) * 4 == 1006632960
    assert conv.PASSES == 5 and scan.ssm_layers(config) == 4

    # a model without these layers, or no table to read: silent
    class Cell:
        config = json.load(open(os.path.join(
            ROOT, "benchmark", "configs", "lfm2_24b_a2b_ep8.json")))
        traffic = {"seq_len": 8192, "batch": 2}
    run = {"cell": Cell, "peaks": {"flops_per_s": 197e12,
                                   "hbm_bytes_per_s": 819e9}, "trace": None}
    for reader in (scan, relu2, shared, conv, _reader("ssm_layer_ms.train")):
        assert reader.read(dict(run)) is None
    Cell.config = config
    for reader in (scan, relu2, shared, conv):
        assert reader.read(dict(run)) is None


# -- through the compiled step ------------------------------------------------

def test_compiled_step_trains_and_recomputes():
    from mxnet_tpu.module.compiled_step import CompiledTrainStep
    profiler.reset_spans()
    np.random.seed(5)
    mx.random.seed(5)
    net = hybrid_ssm_lm.build(CONFIG)
    net.initialize(mx.init.Xavier(), ctx=mx.current_context())
    step = CompiledTrainStep.from_block(
        net, hybrid_ssm_lm.loss,
        mx.optimizer.create("adam", learning_rate=1e-3),
        n_inputs=hybrid_ssm_lm.N_INPUTS)
    batch = tuple(mx.nd.array(a, dtype=a.dtype) for a in _batch())
    losses = [float(step.step(*batch).asnumpy()[0]) for _ in range(4)]
    assert losses[-1] < losses[0]
    assert all(layer._flags == {
        "remat": True, "remat_policy": ("attn.out", "attn.lse", "moe.table")}
        for layer in net.layers)
    totals = profiler.totals()
    assert totals["ssm.chunks"]["max"] == BATCH * L // 8
    assert totals["ssm.conv_tiles"]["max"] == 0       # XLA's form here
    assert totals["moe.experts_held"]["max"] == 4


def test_mixer_operators_are_registered_for_nd_and_sym():
    for name in ("_contrib_ssd_scan", "_contrib_ssm_conv",
                 "_contrib_gated_rms_norm", "_contrib_relu2_mlp"):
        assert callable(getattr(mx.nd, name)) and callable(
            getattr(mx.sym, name))
    rng = np.random.RandomState(6)
    x = rng.normal(0, 1, (BATCH, L, 12)).astype(np.float32)
    w = rng.normal(0, 1, (12, 4)).astype(np.float32)
    b = rng.normal(0, 1, 12).astype(np.float32)
    got = mx.nd._contrib_ssm_conv(mx.nd.array(x), mx.nd.array(w),
                                  mx.nd.array(b)).asnumpy()
    padded = np.pad(x, ((0, 0), (3, 0), (0, 0)))
    want = sum(padded[:, j:j + L] * w[:, j] for j in range(4)) + b
    want = want / (1 + np.exp(-want))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # the norm's groups: each group of 6 channels at unit RMS
    z = np.full((1, 2, 12), 30.0, np.float32)
    normed = mx.nd._contrib_gated_rms_norm(
        mx.nd.array(x[:1, :2]), mx.nd.array(z), mx.nd.ones((12,)), groups=2,
        eps=1e-5).asnumpy().reshape(1, 2, 2, 6)
    np.testing.assert_allclose(np.sqrt(np.mean(normed ** 2, -1)), 1.0,
                               rtol=1e-4)


# -- what was there before ----------------------------------------------------

def _digest(text):
    """A jaxpr's text without the addresses of the functions it names."""
    return hashlib.sha256(re.sub(r" at 0x[0-9a-f]+", "", text).encode()
                          ).hexdigest()[:16]


@pytest.mark.parametrize("backend,scoring,digest", [
    ("cpu", "softmax", "07c8e08d0e4c2ba2"),
    ("cpu", "sigmoid", "3112133d986bb7ff"),
    ("tpu", "softmax", "12a9437e92b4ad63"),
    ("tpu", "sigmoid", "2d80ceee1399f04d")])
def test_swiglu_experts_trace_the_program_they_traced_before(
        monkeypatch, backend, scoring, digest):
    """The SiLU-gated held experts' gradient's jaxpr, XLA's products and
    (the backend said to be a TPU) the kernels', softmax router and sigmoid
    router with a selection bias, as traced before relu² experts, the
    router's epsilon and the shared expert were added."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    op = get_op("_contrib_moe_held_experts").fcompute
    s = jax.ShapeDtypeStruct
    d, f, held, total = 128, 256, 4, 16
    args = (s((64, d), jnp.float32), s((total, d), jnp.float32),
            s((held * f, d), jnp.float32), s((held * f, d), jnp.float32),
            s((held * d, f), jnp.float32))
    attrs = {"experts_per_token": 4, "expert_width": f, "first_expert": 4}
    if scoring == "sigmoid":
        attrs.update(scoring="sigmoid", scale=2.5)
        args = args + (s((total,), jnp.float32),)

    def loss(*a):
        out, load = op(attrs, *a)
        return jnp.sum(out * out) + load[1]
    assert _digest(str(jax.make_jaxpr(jax.grad(loss, tuple(range(5))))(
        *args))) == digest


def test_the_sixth_cell_s_stack_traces_the_program_it_traced_before():
    """``window_moe_lm.build`` at tests/test_window_moe_lm.py's size: the
    digest of its gradient's jaxpr as traced before the stack built layers
    of one block (the fifth cell's is held in that file)."""
    from mxnet_tpu.gluon.model_zoo import window_moe_lm
    from test_window_moe_lm import CONFIG as WINDOW, L as LENGTH
    net = window_moe_lm.build(WINDOW)
    net.initialize(mx.init.Zero(), ctx=mx.current_context())
    values = {k: p.data()._data for k, p in net.collect_params().items()}

    def total(v, tokens):
        return jnp.sum(functional_call(net, v, tokens, training=True)[0][0])
    assert _digest(str(jax.make_jaxpr(jax.grad(total))(
        values, jnp.zeros((2, LENGTH), jnp.int32)))) == "a8600034240bf589"
