"""A tiny ``lfm2_moe`` cell is ``correct`` when sound, and not under the
control and each fault.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/checks -q

The cell under ``cells_lfm2/`` (``tiny_lfm2.sft_b2_s32``, a spec of its own:
``Cell(..., spec=..., base=...)``) is the family of
``lfm2_24b_a2b_ep8.sft_b2_s8192`` at toy widths and the same cut: published
layers 0, 2, 3, 4, 5 of ``layer_types`` (a leading dense layer, then an
attention layer and three convolution layers with experts), 4 of 8 experts
held, a tied head, Adam, generator ``next_token``, entry ``block_step`` over
the program's own ``mxnet_tpu.gluon.model_zoo.short_conv_lm``.  The control
is the reference in bfloat16 put in the program's place; the faults
(faults_lfm2.py, planted in the reference): the bias left out of the
selection, the bias let into the weights, softmax for sigmoid, the
normalisation left out, the convolution looking one row ahead, the output
gate left out, one held expert's output left out, half of the loss's rows
left out; and, in the program, a step that leaves its state unchanged.  The
comparison is the family's own, ``comparisons/lfm2_layers.py``: whatever
stands in the program's place is held against the sound reference given its
picks.
"""
import contextlib
import os

import pytest

from benchmark.checks import test_correct as shared

CELL = "tiny_lfm2.sft_b2_s32"
CELLS = os.path.join(shared.HERE, "cells_lfm2")
METRICS = ("causal_attn_roofline.train", "moe_routed_share.train",
           "short_conv_roofline.train")


@pytest.fixture(scope="module")
def cell():
    from benchmark import harness
    shared.load_cell("tiny_seq.seq_b8")       # the path and the cache
    return harness.Cell(CELL, shared.ROOT,
                        spec=harness.load_json(CELLS, "spec.json"),
                        base=CELLS)


def judged(cell, fault=None, **variant):
    from benchmark import traffic
    from benchmark.checks import faults_lfm2
    from benchmark.comparisons import lfm2_layers as compare
    from benchmark.comparisons.train_norms import judge
    batches = traffic.make_pool(cell.config, cell.traffic, shared.SEED, 3)
    with faults_lfm2.planted(fault) if fault else contextlib.nullcontext():
        other = compare.reference_readings(cell, shared.SEED, batches,
                                           **variant)
    reference = compare.reference_readings(
        cell, shared.SEED, batches, given=other["layers"]["picks"])
    return judge(compare.numbers(other, reference, cell)[0], cell.limits)


def test_sound_run_is_correct(cell):
    result, log = shared.run(cell)
    assert result["correct"], log
    assert set(result["compared"]) == {
        "grad_norm_gap", "update_norm_gap", "bn_stats_gap", "conv_rows_gap",
        "attn_rows_gap", "route_gap", "expert_grad_gap"}
    assert result["compared"]["bn_stats_gap"]["value"] == 0
    assert result["compared"]["route_gap"]["value"] == 0
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["window"]["window_compiles"] == 0


def test_the_probe_s_bias_moves_the_picks(cell):
    """The seed's selection biases are 0; the probe's is of the size of the
    scores' spread, and a router that ignored it would pick other experts
    for a good share of the rows."""
    import jax
    import numpy as np
    from benchmark import traffic
    from benchmark.comparisons import lfm2_layers as compare
    batch = traffic.make_pool(cell.config, cell.traffic, shared.SEED, 1)[0]
    weights, x, _ = compare.probe_inputs(cell, shared.SEED, batch[0])
    bias = np.asarray(weights["layer0_moe_expert_bias"])
    assert bias.shape == (8,) and 0.01 < bias.std() < 1
    scores = jax.nn.sigmoid(np.asarray(x).reshape(-1, 64) @ np.asarray(
        weights["layer0_moe_router_weight"]).T)
    with_bias = jax.lax.top_k(scores + bias, 2)[1]
    without = jax.lax.top_k(scores, 2)[1]
    assert 0.1 < float(np.mean(np.sort(with_bias) != np.sort(without)))


def test_traced_run_reports_the_program_counters(cell):
    import io
    import time
    import jax
    from benchmark import harness
    from mxnet_tpu import profiler
    profiler.reset_spans()      # other tests' networks, in this process
    names = cell.metric_names("per_layer")
    for name in METRICS + ("step_mfu.train",):
        assert name in names
    result = harness.run_cell(cell, shared.SEED, 1.0, True,
                              time.perf_counter(), jax.local_devices()[:1],
                              out=io.StringIO())
    # on the CPU there is no device plane, no table of peaks and no kernel:
    # the two rooflines are silent; the routed share is read
    assert result["correct"]
    assert set(result["metrics"]) == {
        "window_compiles.train", "peak_hbm_gb.train",
        "moe_routed_share.train"}
    # 2 of 8 experts a token, 4 held: 25% where the load is even
    assert 5 < result["metrics"]["moe_routed_share.train"]["value"] < 60


def test_readers_are_silent_where_the_program_has_no_record(cell):
    from benchmark import harness
    from mxnet_tpu import profiler
    profiler.reset_spans()
    run = {"cell": cell, "trace": None, "peaks": None}
    for name in METRICS:
        assert harness.load_reader(name)(run) is None


def test_control_in_bfloat16_fails(cell):
    import jax.numpy as jnp
    found = judged(cell, dtype=jnp.bfloat16, precision=None,
                   state_dtype=jnp.bfloat16)
    assert not all(n["ok"] for n in found.values()), found


@pytest.mark.parametrize("fault,by", [
    ("bias_not_in_selection", "route_gap"),
    ("bias_in_weights", "expert_grad_gap"),
    ("softmax_router", "route_gap"), ("not_normalised", "expert_grad_gap"),
    ("conv_looks_ahead", "conv_rows_gap"),
    ("no_output_gate", "conv_rows_gap"), ("drop_expert", "expert_grad_gap"),
    ("half_rows", "grad_norm_gap")])
def test_reference_fault_is_not_correct(cell, fault, by):
    found = judged(cell, fault=fault)
    assert not found[by]["ok"], found


def test_state_left_unchanged_is_not_correct(cell):
    with shared.broken_step("state_unchanged", summed_loss=False):
        result, log = shared.run(cell)
    assert not result["correct"], log
