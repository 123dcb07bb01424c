"""A tiny ``nemotron_h`` cell is ``correct`` when sound, and not under the
control and each fault.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/checks -q

The cell under ``cells_nemotron/`` (``tiny_nemotron.ssm_b2_s40``, a spec of
its own: ``Cell(..., spec=..., base=...)``) is the family of
``nemotron3_nano_30b_a3b_ep16.ssm_sft_b1_s8192`` at toy widths and the same
cut: published layers 0..8 of ``hybrid_override_pattern`` (4 Mamba-2 layers
of 4 heads of 8, state 16 in 2 groups, chunks of 8 rows over 40; 4 routed
layers, 4 of 16 relu² experts held, 6 picked, a shared expert; 1 attention
layer), an untied head, Adam, generator ``next_token``, entry ``block_step``
over the program's own ``mxnet_tpu.gluon.model_zoo.hybrid_ssm_lm``.  The
control is the reference in bfloat16 put in the program's place; the faults
(faults_nemotron.py, planted in the reference) each put in the program's
place, and, in the program, a step that leaves its state unchanged.  The
comparison is the family's own, ``comparisons/nemotron_layers.py``: whatever
stands in the program's place is held against the sound reference given its
picks.
"""
import contextlib
import os

import pytest

from benchmark.checks import test_correct as shared

CELL = "tiny_nemotron.ssm_b2_s40"
CELLS = os.path.join(shared.HERE, "cells_nemotron")
METRICS = ("ssd_scan_roofline.train", "ssm_layer_ms.train",
           "relu2_experts_roofline.train", "shared_expert_roofline.train")


@pytest.fixture(scope="module")
def cell():
    from benchmark import harness
    shared.load_cell("tiny_seq.seq_b8")       # the path and the cache
    return harness.Cell(CELL, shared.ROOT,
                        spec=harness.load_json(CELLS, "spec.json"),
                        base=CELLS)


def judged(cell, fault=None, **variant):
    from benchmark import traffic
    from benchmark.checks import faults_nemotron
    from benchmark.comparisons import nemotron_layers as compare
    from benchmark.comparisons.train_norms import judge
    batches = traffic.make_pool(cell.config, cell.traffic, shared.SEED, 3)
    with faults_nemotron.planted(fault) if fault \
            else contextlib.nullcontext():
        other = compare.reference_readings(cell, shared.SEED, batches,
                                           **variant)
    reference = compare.reference_readings(
        cell, shared.SEED, batches, given=other["layers"]["picks"])
    return judge(compare.numbers(other, reference, cell)[0], cell.limits)


def test_sound_run_is_correct(cell):
    result, log = shared.run(cell)
    assert result["correct"], log
    assert set(result["compared"]) == {
        "grad_norm_gap", "update_norm_gap", "bn_stats_gap", "ssm_rows_gap",
        "attn_rows_gap", "expert_grad_gap"}
    assert result["compared"]["bn_stats_gap"]["value"] == 0
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["window"]["window_compiles"] == 0


def test_traced_run_reads_no_roofline_off_the_chip(cell):
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
    # on the CPU there is no device plane and no table of peaks: the four
    # readers are silent
    assert result["correct"]
    assert set(result["metrics"]) == {"window_compiles.train",
                                      "peak_hbm_gb.train"}


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
    ("no_carry", "ssm_rows_gap"), ("no_skip", "ssm_rows_gap"),
    ("no_gate", "ssm_rows_gap"), ("one_group", "ssm_rows_gap"),
    ("conv_ahead", "ssm_rows_gap"), ("rotary", "attn_rows_gap"),
    ("qk_norm", "attn_rows_gap"), ("relu", "expert_grad_gap"),
    ("no_scale", "expert_grad_gap"), ("drop_expert", "expert_grad_gap"),
    ("no_shared", "expert_grad_gap"), ("half_rows", "grad_norm_gap")])
def test_reference_fault_is_not_correct(cell, fault, by):
    found = judged(cell, fault=fault)
    assert not found[by]["ok"], found


def test_state_left_unchanged_is_not_correct(cell):
    with shared.broken_step("state_unchanged", summed_loss=False):
        result, log = shared.run(cell)
    assert not result["correct"], log
