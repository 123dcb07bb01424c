"""A tiny ``mellum_moe`` cell is ``correct`` when sound, and not under the
control and each fault.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/checks -q

The cell under ``cells_mellum/`` (``tiny_mellum.sft_b2_s48``, a spec of its
own: ``Cell(..., spec=..., base=...)``) is the family of
``mellum2_12b_a2p5b_ep8.sft_b1_s16384`` at toy widths and the same cut: one
whole period of ``layer_types`` (three sliding layers, a window of 8 keys,
then a full layer whose YaRN rotary's original context of 16 positions the
sequence of 48 passes), 4 of 16 experts held and 4 picked, an untied head,
Adam, generator ``next_token``, entry ``block_step`` over the program's own
``mxnet_tpu.gluon.model_zoo.window_moe_lm``.  The control is the reference in
bfloat16 put in the program's place; the faults (faults_mellum.py, planted in
the reference): the window left out, the window doubled, YaRN left out, its
attention factor left out, the two rotary forms swapped, one held expert's
output left out, half of the loss's rows left out; and, in the program, a
step that leaves its state unchanged.  The comparison is the family's own,
``comparisons/mellum_layers.py``: whatever stands in the program's place is
held against the sound reference given its picks.
"""
import contextlib
import os

import pytest

from benchmark.checks import test_correct as shared

CELL = "tiny_mellum.sft_b2_s48"
CELLS = os.path.join(shared.HERE, "cells_mellum")
METRICS = ("window_attn_roofline.train", "full_attn_roofline.train")


@pytest.fixture(scope="module")
def cell():
    from benchmark import harness
    shared.load_cell("tiny_seq.seq_b8")       # the path and the cache
    return harness.Cell(CELL, shared.ROOT,
                        spec=harness.load_json(CELLS, "spec.json"),
                        base=CELLS)


def judged(cell, fault=None, **variant):
    from benchmark import traffic
    from benchmark.checks import faults_mellum
    from benchmark.comparisons import mellum_layers as compare
    from benchmark.comparisons.train_norms import judge
    batches = traffic.make_pool(cell.config, cell.traffic, shared.SEED, 3)
    with faults_mellum.planted(fault) if fault else contextlib.nullcontext():
        other = compare.reference_readings(cell, shared.SEED, batches,
                                           **variant)
    reference = compare.reference_readings(
        cell, shared.SEED, batches, given=other["layers"]["picks"])
    return judge(compare.numbers(other, reference, cell)[0], cell.limits)


def test_sound_run_is_correct(cell):
    result, log = shared.run(cell)
    assert result["correct"], log
    assert set(result["compared"]) == {
        "grad_norm_gap", "update_norm_gap", "bn_stats_gap", "window_rows_gap",
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
    # on the CPU there is no device plane and no table of peaks: the two
    # rooflines are silent
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
    ("no_window", "window_rows_gap"), ("window_doubled", "window_rows_gap"),
    ("no_yarn", "attn_rows_gap"), ("no_attention_factor", "attn_rows_gap"),
    ("rotary_swapped", "attn_rows_gap"), ("drop_expert", "expert_grad_gap"),
    ("half_rows", "grad_norm_gap")])
def test_reference_fault_is_not_correct(cell, fault, by):
    found = judged(cell, fault=fault)
    assert not found[by]["ok"], found


def test_state_left_unchanged_is_not_correct(cell):
    with shared.broken_step("state_unchanged", summed_loss=False):
        result, log = shared.run(cell)
    assert not result["correct"], log
