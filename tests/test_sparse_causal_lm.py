"""Causal attention over the keys a learned indexer picks, and the model
built of it, at small sizes on the CPU with ``topk`` well under the sequence,
against the benchmark's plain reference (benchmark/reference/keye_dsa.py:
float32, ``highest``, nothing of the program) and against the XLA attention
reference (the Pallas kernels run interpreted)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler
from mxnet_tpu.gluon.block import functional_call
from mxnet_tpu.gluon.model_zoo import sparse_causal_lm
from mxnet_tpu.ops import decoder_ops, pallas_ops
from mxnet_tpu.ops.registry import get_op

from benchmark.generators import next_token
from benchmark.reference import common as reference
from benchmark.reference import keye_dsa

L, BATCH, TOPK = 32, 2, 8
CONFIG = dict(
    reference="keye_dsa", hidden_size=64, num_attention_heads=8,
    num_key_value_heads=2, head_dim=16, moe_intermediate_size=24,
    num_experts=4, num_experts_per_tok=2, num_hidden_layers=2, vocab_size=96,
    rms_norm_eps=1e-6, rope_theta=10000000,
    rope_scaling={"mrope_section": [2, 3, 3]},
    sa_config={"indexer_num_heads": 4, "indexer_head_dim": 8, "topk": TOPK},
    indexer_loss_weight=1.0,
    deployment={"num_experts_total": 8, "first_expert": 0})
INDEXER_LEAVES = ("index_q_weight", "index_k_weight", "index_w_weight",
                  "index_k_norm_gamma", "index_k_norm_beta")


def _batch(seed=0):
    return next_token.make_pool(CONFIG, {"batch": BATCH, "seq_len": L},
                                seed, 1)[0]


# -- the selection -------------------------------------------------------------

def _top_k_pairs(scores, k):
    """What ``jax.lax.top_k`` picks of each row's causal scores, a zero of
    either sign being one value (the selection's convention and the
    reference's; the CPU's ``top_k`` puts +0 before -0)."""
    scores = np.asarray(scores)
    scores = np.where(scores == 0, np.float32(0), scores)
    B, T, _ = scores.shape
    causal = np.tril(np.ones((T, T), bool))
    _, index = jax.lax.top_k(jnp.where(causal[None], scores, -jnp.inf),
                             min(k, T))
    want = np.zeros((B, T, T), bool)
    for b in range(B):
        for t in range(T):
            want[b, t, np.asarray(index[b, t, :min(t + 1, k)])] = True
    return want


@pytest.mark.parametrize("case", ["distinct", "ties", "zeros_of_both_signs",
                                  "topk_over_the_sequence"])
def test_selection_is_what_top_k_gives(case):
    rng = np.random.RandomState(0)
    scores = rng.normal(0, 1, (2, 48, 48)).astype(np.float32)
    k = 12
    if case == "ties":          # few distinct values: every row has ties
        scores = np.round(scores * 2) / 2
    elif case == "zeros_of_both_signs":
        scores = np.where(rng.rand(2, 48, 48) < 0.5, 0.0, scores)
        scores = np.where(rng.rand(2, 48, 48) < 0.5, -scores, scores)
        assert np.signbit(scores[scores == 0]).any()
    elif case == "topk_over_the_sequence":
        k = 64
    pairs = np.asarray(decoder_ops.select_top_k(jnp.asarray(scores), k)) != 0
    assert (pairs == _top_k_pairs(scores, k)).all()
    # min(t + 1, k) keys a query, none after it
    assert (pairs.sum(-1) == np.minimum(np.arange(48) + 1, k)).all()
    assert not np.triu(pairs, 1).any()


@pytest.mark.parametrize("case", ["distinct", "ties", "zeros_of_both_signs"])
def test_selection_kernel_finds_what_the_search_in_xla_finds(case):
    """``pallas_ops.select_thresholds`` (interpreted; rows of 64 held while
    every pass runs) against the XLA search and against ``top_k``."""
    rng = np.random.RandomState(1)
    scores = rng.normal(0, 1, (2, 256, 256)).astype(np.float32)
    if case == "ties":
        scores = np.round(scores * 2) / 2
    elif case == "zeros_of_both_signs":
        scores = np.where(rng.rand(2, 256, 256) < 0.6, 0.0, scores)
        scores = np.where(rng.rand(2, 256, 256) < 0.5, -scores, scores)
    kernel = decoder_ops.select_top_k(jnp.asarray(scores), 40, interpret=True)
    search = decoder_ops.select_top_k(jnp.asarray(scores), 40)
    assert bool(jnp.all(kernel == search))
    assert (np.asarray(kernel != 0) == _top_k_pairs(scores, 40)).all()
    bits = jnp.where(jnp.tril(jnp.ones((256, 256), bool)),
                     decoder_ops._order_bits(jnp.asarray(scores)), 0)
    threshold, cut = pallas_ops.select_thresholds(jnp.asarray(scores), 40,
                                                  interpret=True)
    want, want_cut = decoder_ops._search_thresholds(bits, 40)
    assert bool(jnp.all(jax.lax.bitcast_convert_type(
        threshold, jnp.uint32) == want)) and bool(jnp.all(cut == want_cut))


def test_ties_go_to_the_lower_key():
    scores = jnp.zeros((1, 16, 16), jnp.float32).at[0, :, 5].set(1.0)
    pairs = np.asarray(decoder_ops.select_top_k(scores, 4))[0] != 0
    assert sorted(np.nonzero(pairs[15])[0]) == [0, 1, 2, 5]
    assert sorted(np.nonzero(pairs[3])[0]) == [0, 1, 2, 3]


def test_index_scores_are_the_reference_s():
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.normal(0, 1, (2, 4, 1024, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (2, 1024, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 1, (2, 1024, 4)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = decoder_ops.index_scores(q, k, w)         # two chunks of 512
    want = keye_dsa.index_scores(reference.Ops(), q, k, w)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


def test_selection_counts_its_pairs():
    profiler.reset_spans()
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.normal(0, 1, (BATCH, 4, L, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (BATCH, L, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 1, (BATCH, L, 4)), jnp.float32)
    scores, pairs = mx.nd._contrib_index_select(
        mx.nd.NDArray(q), mx.nd.NDArray(k), mx.nd.NDArray(w), topk=TOPK)
    totals = profiler.totals()
    assert totals["dsa.pairs_causal"]["count"] == BATCH * L * (L + 1) // 2
    assert totals["dsa.pairs_selected"]["count"] == int(
        pairs.asnumpy().astype(bool).sum()) == BATCH * (36 + 24 * 8)


# -- the kernels under a data mask, against the XLA reference ------------------

def _qkv(rng, heads, kv_heads, rows, dim=32, batch=1):
    def normal(h):
        return jnp.asarray(rng.normal(0, 1, (batch, h, rows, dim)),
                           jnp.float32)
    return normal(heads), normal(kv_heads), normal(kv_heads)


def _picked(rng, batch, rows, k, empty=False):
    scores = rng.normal(0, 1, (batch, rows, rows)).astype(np.float32)
    if empty:       # late queries pick nothing among the first keys
        scores[:, rows // 2:, :rows // 4] = -9.0
    return decoder_ops.select_top_k(jnp.asarray(scores), k)


@pytest.mark.parametrize("rows,tiles,batch", [
    (128, (32, 32), 1), (128, (64, 32), 2), (100, (32, 64), 2),
    (256, (32, 32), 2)])
def test_data_mask_kernels_match_reference_8_heads_to_1(rows, tiles, batch):
    """Forward and backward under picked pairs, grouped heads, a batch of
    masks, a length that is no multiple of the tiles, and 8 query tiles
    whose lists hold 1 to 8 entries."""
    rng = np.random.RandomState(0)
    q, k, v = _qkv(rng, 8, 1, rows, batch=batch)
    pairs = _picked(rng, batch, rows, 24)

    def kernels(q, k, v):
        out, lse = pallas_ops.sparse_attention(
            q, k, v, pairs, precision="highest", interpret=True,
            block_q=tiles[0], block_k=tiles[1])
        return jnp.sum(jnp.sin(out)), (out, lse)

    def oracle(q, k, v):
        out, lse = pallas_ops._attention_reference(
            q, k, v, None, 1.0 / np.sqrt(32), mask=pallas_ops.DATA_MASK,
            pairs=pairs)
        return jnp.sum(jnp.sin(out)), (out, lse)

    got, (out, lse) = jax.grad(kernels, (0, 1, 2), has_aux=True)(q, k, v)
    want, (out_w, lse_w) = jax.grad(oracle, (0, 1, 2), has_aux=True)(q, k, v)
    assert float(jnp.max(jnp.abs(out - out_w))) < 1e-5
    assert float(jnp.max(jnp.abs(lse - lse_w))) < 1e-5
    for a, b in zip(got, want):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4


def test_a_tile_with_nothing_picked_is_visited_and_adds_nothing():
    """The grid is the causal mask's whatever is picked (the recorder counts
    it when the kernels are traced), and a tile whose pairs are all 0 moves
    neither the output nor a gradient."""
    rng = np.random.RandomState(1)
    rows, tile = 128, 32
    pairs = _picked(rng, 1, rows, 24, empty=True)
    n = rows // tile
    some = (np.asarray(pairs)[0] != 0).reshape(n, tile, n, tile).any((1, 3))
    assert not some[2:, 0].any() and some.sum() < 10
    q, k, v = _qkv(rng, 2, 1, rows)
    scale = 1.0 / np.sqrt(32)

    def kernels(q, k, v):
        return jnp.sum(jnp.sin(pallas_ops.sparse_attention(
            q, k, v, pairs, precision="highest", interpret=True,
            block_q=tile, block_k=tile)[0]))

    def oracle(q, k, v):
        return jnp.sum(jnp.sin(pallas_ops._attention_reference(
            q, k, v, None, scale, mask=pallas_ops.DATA_MASK, pairs=pairs)[0]))

    profiler.reset_spans()
    got = jax.grad(kernels, (0, 1, 2))(q, k, v)
    totals = profiler.totals()
    # forward and backward, both heads: 10 causal tiles of the 16
    assert totals["attn.tiles_visited"]["count"] == 2 * 2 * 10
    assert totals["attn.tiles_total"]["count"] == 2 * 2 * 16
    assert totals["attn.grid_steps"]["count"] == 2 * 2 * 10
    for a, b in zip(got, jax.grad(oracle, (0, 1, 2))(q, k, v)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4


@pytest.mark.parametrize("rows,tiles,batch,empty,kv_heads", [
    (128, (32, 32), 1, True, 2), (100, (32, 64), 2, False, 2),
    (256, (32, 32), 2, False, 1)])
def test_head_mean_kernel_matches_the_chunks_in_xla(rows, tiles, batch,
                                                    empty, kv_heads):
    """The attention's distribution averaged over 8 heads to 2 (to 1 over
    8 query tiles of 1 to 8 entries), the heads innermost in the grid,
    against XLA's chunks; read under the pairs (a tile above the diagonal
    is never written)."""
    rng = np.random.RandomState(3)
    q, k, v = _qkv(rng, 8, kv_heads, rows, batch=batch)
    pairs = _picked(rng, batch, rows, 24, empty=empty)
    _, lse = pallas_ops.sparse_attention(
        q, k, v, pairs, precision="highest", interpret=True,
        block_q=tiles[0], block_k=tiles[1])
    got = pallas_ops.head_mean_probabilities(
        q, k, lse, pairs, precision="highest", interpret=True,
        block_q=tiles[0], block_k=tiles[1])
    want = pallas_ops._head_mean_reference(q, k, lse, pairs,
                                           1.0 / np.sqrt(32))
    got = jnp.where(pairs != 0, got, 0.0)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-6
    assert float(jnp.max(jnp.abs(jnp.sum(got, -1) - 1.0))) < 1e-5


def test_index_loss_reads_the_target_under_the_pairs_alone(monkeypatch):
    """The target kernel never writes a tile above the diagonal; whatever
    an unpicked pair holds must reach neither the loss nor the scores'
    gradient (a NaN times a zero cotangent is a NaN)."""
    rng = np.random.RandomState(4)
    q, k, v = _qkv(rng, 4, 2, 64)
    pairs = _picked(rng, 1, 64, 8)
    scores = jnp.asarray(rng.normal(0, 1, (1, 64, 64)), jnp.float32)
    out, lse = pallas_ops.sparse_attention(q, k, v, pairs)
    fcompute = get_op("_contrib_index_loss").fcompute

    def loss(scores):
        return fcompute({}, scores, pairs, q, k, lse, out)[0][0]

    want, want_grad = jax.value_and_grad(loss)(scores)
    clean = pallas_ops.head_mean_probabilities
    monkeypatch.setattr(pallas_ops, "head_mean_probabilities",
                        lambda *a, **kw: jnp.where(pairs != 0, clean(*a, **kw),
                                                   jnp.nan))
    got, got_grad = jax.value_and_grad(loss)(scores)
    assert float(got) == float(want) and bool(jnp.all(got_grad == want_grad))
    assert bool(jnp.all(jnp.isfinite(got_grad)))


def test_a_data_mask_s_tables_are_the_causal_mask_s_every_tile_masked():
    plan = pallas_ops._Plan((1, 2, 64, 16), (1, 1, 64, 16),
                            pallas_ops.DATA_MASK, 0.25, 32, 32, jnp.float32,
                            True)
    causal = pallas_ops._Plan((1, 2, 64, 16), (1, 1, 64, 16), ("causal", 0),
                              0.25, 32, 32, jnp.float32, True)
    assert plan.steps == causal.steps == 3
    assert plan.q_tile.tolist() == causal.q_tile.tolist() == [0, 1, 1]
    assert plan.k_tile.tolist() == causal.k_tile.tolist() == [0, 0, 1]
    first, last = pallas_ops._FIRST, pallas_ops._LAST
    # the tile under the diagonal is wholly visible to the causal mask; a
    # data mask's is masked from the pairs like the others
    assert causal.flag.tolist() == [1 | first | last, 2 | first, 1 | last]
    assert plan.flag.tolist() == [1 | first | last, 1 | first, 1 | last]


def test_no_gradient_reaches_the_log_sum_exp():
    rng = np.random.RandomState(2)
    q, k, v = _qkv(rng, 2, 1, 64)
    pairs = _picked(rng, 1, 64, 8)
    for interpret in (None, True):      # the XLA path, the kernels
        grads = jax.grad(lambda q, k, v: jnp.sum(pallas_ops.sparse_attention(
            q, k, v, pairs, interpret=interpret, block_q=32,
            block_k=32)[1]), (0, 1, 2))(q, k, v)
        assert all(float(jnp.max(jnp.abs(g))) == 0 for g in grads)


# -- rotary in sections ----------------------------------------------------------

def test_sectioned_rotary_takes_each_frequency_from_its_row():
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.normal(0, 1, (2, 3, 10, 16)), jnp.float32)
    rows = jnp.asarray(rng.randint(0, 50, (3, 10)), jnp.int32)
    got = get_op("_contrib_rotary_embedding").fcompute(
        {"base": 1e7, "sections": (2, 3, 3)}, x, rows)
    want = keye_dsa.rotary(x, rows, 1e7, (2, 3, 3))
    assert float(jnp.max(jnp.abs(got - want))) < 1e-6
    # by hand: frequency 1 from row 0, frequency 4 from row 1, 7 from row 2
    for freq, row in ((1, 0), (4, 1), (7, 2)):
        angle = np.asarray(rows[row], np.float64) * 1e7 ** (-freq / 8)
        first = np.asarray(x[..., freq]) * np.cos(angle) \
            - np.asarray(x[..., freq + 8]) * np.sin(angle)
        assert np.abs(np.asarray(got[..., freq]) - first).max() < 1e-4


def test_equal_rows_are_the_plain_operator_bit_for_bit():
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.normal(0, 1, (2, 3, 10, 16)), jnp.float32)
    row = jnp.arange(10, dtype=jnp.int32)
    op = get_op("_contrib_rotary_embedding").fcompute
    plain = op({"base": 1e7}, x, row)
    sectioned = op({"base": 1e7, "sections": (2, 3, 3)}, x,
                   jnp.stack([row] * 3))
    assert bool(jnp.all(plain == sectioned))
    with pytest.raises(ValueError, match="sections"):
        op({"base": 1e7, "sections": (2, 3)}, x, jnp.stack([row] * 3))


# -- the model against the plain reference ---------------------------------------

@pytest.fixture(scope="module")
def model():
    net = sparse_causal_lm.build(CONFIG)
    net.initialize(mx.init.Zero(), ctx=mx.current_context())
    params, _ = reference.xavier_init(CONFIG, 7)
    # norms and the indexer away from their initial values, so that a
    # gamma, the LayerNorm's beta and the indexer's picks all matter
    key = jax.random.PRNGKey(3)
    for i, name in enumerate(sorted(params)):
        if not name.endswith("_weight"):
            params[name] = params[name] + 0.3 * jax.random.normal(
                jax.random.fold_in(key, i), params[name].shape)
        elif "_index_" in name:
            params[name] = params[name] * 3
    return net, params


def _outputs(net, values, tokens):
    full = {net.prefix + k: v for k, v in values.items()}
    for name, p in net.collect_params().items():    # the recorded state
        full.setdefault(name, p.data()._data)
    return functional_call(net, full, jnp.asarray(tokens), training=True)[0]


def _program_loss(net, values, batch, parts=(1.0, 1.0)):
    tokens, targets, weight = batch

    def loss(values):
        logits, index_loss = _outputs(net, values, tokens)
        return sparse_causal_lm.loss(
            [mx.nd.NDArray(parts[0] * logits),
             mx.nd.NDArray(parts[1] * index_loss)],
            mx.nd.NDArray(jnp.asarray(targets)),
            mx.nd.NDArray(jnp.asarray(weight)))._data.reshape(())
    return jax.value_and_grad(loss)(values)


def _reference_loss(config, params, batch):
    ops = reference.Ops()
    return jax.value_and_grad(lambda p: keye_dsa.loss(
        config, ops, p, {}, tuple(jnp.asarray(a) for a in batch))[0])(params)


def test_parameters_carry_the_reference_names(model):
    net, params = model
    shapes = keye_dsa.param_shapes(CONFIG)
    held = {k[len(net.prefix):]: p for k, p in net.collect_params().items()
            if p.grad_req != "null"}
    assert sorted(held) == sorted(shapes) == sorted(params)
    for name, p in held.items():
        assert tuple(p.shape) == tuple(shapes[name]), name
    for leaf in INDEXER_LEAVES:
        assert "layer1_attn_" + leaf in shapes


def test_logits_and_selection_match_the_reference(model):
    net, params = model
    tokens = jnp.asarray(_batch()[0])
    with jax.default_matmul_precision("highest"):
        logits, index_loss = _outputs(net, params, tokens)
    want, want_index = keye_dsa.network(CONFIG, reference.Ops(), params,
                                        tokens, True)
    assert float(jnp.max(jnp.abs(logits - want))) < 1e-4
    assert abs(float(index_loss[0]) - float(want_index)) \
        < 1e-5 * float(want_index)
    assert float(want_index) > 0.1          # the indexer is far from trained


def test_first_block_picks_the_reference_s_pairs(model):
    from benchmark.comparisons import keye_layers

    class Cell:
        config = dict(CONFIG, network="mxnet_tpu.gluon.model_zoo."
                                      "sparse_causal_lm")
    _, params = model
    s, ops = keye_dsa._sizes(CONFIG), reference.Ops()
    weights = {k: v for k, v in params.items() if k.startswith("layer0_")}
    x = keye_dsa.rms_norm(params["embed_weight"][jnp.asarray(_batch()[0])],
                          weights["layer0_attn_norm_gamma"], 1e-6)
    cots = [jnp.ones_like(x), jnp.ones_like(x)]
    with jax.default_matmul_precision("highest"):
        got = keye_layers.program_probe(Cell, weights, x, cots)
    want = np.asarray(keye_dsa.selected_pairs(s, ops, weights, "layer0_", x))
    assert (got["pairs"] == want).all()
    assert (want.sum(-1) == np.minimum(np.arange(L) + 1, TOPK)).all()
    assert float(np.abs(got["index_dx"]).max()) == 0    # read detached


@pytest.mark.parametrize("kind", (
    "embed_weight", "head_weight", "final_norm_gamma", "attn_norm_gamma",
    "attn_q_weight", "attn_k_weight", "attn_v_weight", "attn_o_weight",
    "attn_q_norm_gamma", "attn_k_norm_gamma", "moe_norm_gamma",
    "moe_router_weight", "moe_gate_weight", "moe_up_weight",
    "moe_down_weight") + INDEXER_LEAVES)
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


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_two_losses_move_disjoint_leaves(model, side):
    """The indexer's loss moves no leaf outside the indexer, and the language
    model's loss moves none inside it."""
    net, params = model
    if side == "program":
        _, language = _program_loss(net, params, _batch(), (1.0, 0.0))
        _, indexer = _program_loss(net, params, _batch(), (0.0, 1.0))
    else:
        batch = tuple(jnp.asarray(a) for a in _batch())
        ops = reference.Ops()
        language = jax.grad(lambda p: keye_dsa.loss(
            dict(CONFIG, indexer_loss_weight=0.0), ops, p, {}, batch)[0])(
                params)
        indexer = jax.grad(lambda p: keye_dsa.network(
            CONFIG, ops, p, batch[0], True)[1])(params)
    for name in params:
        inside = name.endswith(INDEXER_LEAVES)
        moved_by_language = float(jnp.max(jnp.abs(language[name]))) > 0
        moved_by_indexer = float(jnp.max(jnp.abs(indexer[name]))) > 0
        assert moved_by_indexer == inside, name
        assert moved_by_language == (not inside), name


@pytest.mark.parametrize("fault", ["dense_causal", "half_topk",
                                   "no_index_loss", "attached_indexer",
                                   "drop_expert"])
def test_reference_faults_move_the_loss_or_a_gradient(model, fault):
    from benchmark.checks import faults_keye
    _, params = model
    sound, sound_grads = _reference_loss(CONFIG, params, _batch())
    with faults_keye.planted(fault):
        faulty, grads = _reference_loss(CONFIG, params, _batch())
    moved = max(float(jnp.max(jnp.abs(grads[k] - sound_grads[k])))
                for k in grads)
    assert abs(float(faulty) - float(sound)) > 1e-4 or moved > 1e-4


def test_reference_layouts_agree(model, monkeypatch):
    """The layout flops.py counts (every chunk against the keys up to its
    end, and past ``topk`` against each query's own keys, gathered) and the
    layout that is trained (one layer's, one chunk's and one expert's
    program, looped, all keys under a mask): the same logits, indexer loss
    and gradients."""
    _, params = model
    monkeypatch.setattr(keye_dsa, "CHUNK", 8)       # 4 chunks, 3 gathered
    tokens = jnp.asarray(_batch()[0])
    ops = reference.Ops()

    def total(looped):
        def f(p):
            logits, index_loss = keye_dsa.network(CONFIG, ops, p, tokens,
                                                  looped)
            return jnp.sum(jnp.tanh(logits)) + index_loss
        return jax.value_and_grad(f)(params)

    (a, ga), (b, gb) = total(False), total(True)
    assert abs(float(a) - float(b)) < 1e-4 * abs(float(a))
    for name in ga:
        scale = float(jnp.max(jnp.abs(ga[name]))) + 1e-12
        assert float(jnp.max(jnp.abs(ga[name] - gb[name]))) < 1e-4 * scale, name


def test_reference_counts_required_work(monkeypatch):
    """flops.py's walk over the reference: by hand, for the tiny size."""
    from benchmark import flops
    monkeypatch.setattr(keye_dsa, "CHUNK", 8)
    flops._forward_macs.cache_clear()

    class Cell:
        config, traffic = CONFIG, {"seq_len": L}
    d, hd, heads, kv, f = 64, 16, 8, 2, 24
    index = d * (4 * 8 + 8 + 4)             # the indexer's three projections
    # chunks of 8 queries: index scores against the keys up to the chunk's
    # end; attention against those for the first chunk (8 = topk keys), and
    # against each query's own 8 keys after it
    index_pairs = 8 * (8 + 16 + 24 + 32)
    picked_pairs = 8 * 8 + 24 * 8
    pairs = keye_dsa.reference_pairs(CONFIG, L)
    assert pairs == L * 2           # no even load asked for: every pair
    per_layer = L * d * (heads * hd * 2 + 2 * kv * hd) + L * index \
        + index_pairs * 4 * 8 + picked_pairs * heads * hd * 2 \
        + L * 8 * d + pairs * 3 * d * f
    assert flops.forward_macs(Cell) == 2 * per_layer + L * 96 * d
    flops._forward_macs.cache_clear()


def test_count_by_hand_of_the_cell_s_layer():
    """ISSUE 33's count of one layer at the cell's size, from the same
    formulas: 368.57 GMAC forward, and flops.py's walk 0.44% over it (its
    chunks of 128 queries end past the diagonal)."""
    T, k, d = 8192, 2048, 2048
    causal, picked = T * (T + 1) // 2, k * (k + 1) // 2 + (T - k) * k
    by_hand = T * d * (4096 * 2 + 2 * 512) + T * d * 128 \
        + T * 8 * 768 * d * 3 * 16 // 128 + T * d * (1024 + 64 + 16) \
        + causal * 1024 + picked * 32 * 128 * 2
    assert abs(by_hand / 1e9 - 368.57) < 0.01
    walked = by_hand + (T * (T + 128) // 2 - causal) * 1024 + (
        128 * 128 * 136 + (T - k) * k - picked) * 8192
    assert 0.004 < walked / by_hand - 1 < 0.005


# -- through the compiled step: recomputation, counters, gauges ------------------

def test_compiled_step_trains_recomputes_and_records(monkeypatch):
    from mxnet_tpu.module.compiled_step import CompiledTrainStep
    profiler.reset_spans()
    net = sparse_causal_lm.build(CONFIG)
    net.initialize(mx.init.Xavier(), ctx=mx.current_context())
    wrapped = []
    checkpoint = jax.checkpoint
    monkeypatch.setattr(jax, "checkpoint", lambda f, **kw: (
        wrapped.append((f.__name__, kw.get("policy"))),
        checkpoint(f, **kw))[1])
    step = CompiledTrainStep.from_block(
        net, sparse_causal_lm.loss,
        mx.optimizer.create("adam", learning_rate=1e-3),
        n_inputs=sparse_causal_lm.N_INPUTS)
    batch = tuple(mx.nd.array(a, dtype=a.dtype) for a in _batch())
    losses = [float(step.step(*batch).asnumpy()[0]) for _ in range(4)]
    assert losses[-1] < losses[0]
    # the two decoder layers are recomputed under a policy of names; the
    # chunks of index scores inside them wholly
    layers = [policy for name, policy in wrapped if name == "pure"]
    assert len(layers) == 2 and all(p is not None for p in layers)
    assert [name for name, _ in wrapped if name != "pure"] == ["one", "one"]
    totals = profiler.totals()
    # two routed layers were traced, each over the same rows
    assert totals["moe.rows"]["count"] == 2 * totals["moe.rows"]["max"]
    assert totals["dsa.pairs_selected"]["count"] * 528 \
        == totals["dsa.pairs_causal"]["count"] * 228
    found = [v for k, v in totals.items()
             if k.startswith("dsa.index_loss." + net.prefix)]
    assert len(found) == 2
    for v in found:         # [1, the layer's loss]
        assert v["count"] == 1 and 0 < v["max"] <= 20


def test_build_keeps_the_selection_beside_the_attention_residuals():
    net = sparse_causal_lm.build(CONFIG)
    assert all(layer._flags == {
        "remat": True, "remat_policy": ("attn.out", "attn.lse", "moe.table",
                                        "dsa.threshold", "dsa.tie_cut")}
        for layer in net.layers)


def test_recomputed_layers_search_no_threshold_again():
    """With the rows' thresholds and cuts kept by name, the backward pass of
    a recomputed layer holds no loop of the search: the forward's two (the
    threshold's bits, the cut) are all."""
    net = sparse_causal_lm.build(dict(CONFIG, num_hidden_layers=1))
    net.initialize(mx.init.Xavier(), ctx=mx.current_context())
    values = {k: p.data()._data for k, p in net.collect_params().items()}
    tokens = jnp.asarray(_batch()[0])

    def f(values):
        logits, index_loss = functional_call(net, values, tokens,
                                             training=True)[0]
        return jnp.sum(jnp.tanh(logits)) + jnp.sum(index_loss)

    def searches(jaxpr, found):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in ("while", "scan") and any(
                    v.aval.dtype == jnp.uint32 and v.aval.ndim == 2
                    for v in eqn.outvars):
                found.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                searches(sub, found)
        return found

    assert len(searches(jax.make_jaxpr(f)(values).jaxpr, [])) == 1
    assert len(searches(jax.make_jaxpr(jax.grad(f))(values).jaxpr, [])) == 1


def test_operators_are_registered_for_nd_and_sym():
    for name in ("_contrib_index_select", "_contrib_index_loss",
                 "_contrib_sparse_attention", "_contrib_rotary_embedding",
                 "LayerNorm"):
        assert callable(getattr(mx.nd, name)) and callable(
            getattr(mx.sym, name))
    rng = np.random.RandomState(5)
    q, k, v = (mx.nd.NDArray(t) for t in _qkv(rng, 4, 2, L, dim=16,
                                              batch=BATCH))
    pairs = mx.nd.NDArray(_picked(rng, BATCH, L, TOPK))
    out, lse = mx.nd._contrib_sparse_attention(q, k, v, pairs)
    assert out.shape == (BATCH, 4, L, 16) and lse.shape == (BATCH, 4, L)
    scores = mx.nd.NDArray(jnp.asarray(rng.normal(0, 1, (BATCH, L, L)),
                                       jnp.float32))
    loss, same = mx.nd._contrib_index_loss(scores, pairs, q, k, lse, out)
    assert loss.shape == (1,) and float(loss.asnumpy()[0]) > 0
    assert bool(jnp.all(same._data == out._data))
