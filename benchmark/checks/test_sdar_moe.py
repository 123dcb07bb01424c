"""A tiny ``sdar_moe`` cell is ``correct`` when sound, and not under the
control and each fault.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/checks -q

The cell under ``cells_sdar/`` (``tiny_sdar.bd_b2_s32``, a spec of its own:
``Cell(..., spec=..., base=...)``) is the family of
``sdar_30b_a3b_ep8.bd_b1_s4096`` at toy widths: block-diffusion attention, 4
of 8 experts held, Adam, generator ``block_diffusion``, entry ``block_step``
over the program's own ``mxnet_tpu.gluon.model_zoo.block_diffusion``.  The
control is the reference in bfloat16 put in the program's place; the faults
(faults_sdar.py, planted in the reference): half of the loss's rows left
out, one held expert's output left out, a noised row allowed to see its own
clean block; and, in the program, a step that leaves its state unchanged.
The comparison is the family's own, ``comparisons/sdar_layers.py``.
"""
import contextlib
import os

import pytest

from benchmark.checks import test_correct as shared

CELL = "tiny_sdar.bd_b2_s32"
CELLS = os.path.join(shared.HERE, "cells_sdar")


@pytest.fixture(scope="module")
def cell():
    from benchmark import harness
    shared.load_cell("tiny_seq.seq_b8")       # the path and the cache
    return harness.Cell(CELL, shared.ROOT,
                        spec=harness.load_json(CELLS, "spec.json"),
                        base=CELLS)


def judged(cell, fault=None, **variant):
    from benchmark import traffic
    from benchmark.checks import faults_sdar
    from benchmark.comparisons import sdar_layers as compare
    from benchmark.comparisons.train_norms import judge
    batches = traffic.make_pool(cell.config, cell.traffic, shared.SEED, 3)
    reference = compare.reference_readings(cell, shared.SEED, batches)
    with faults_sdar.planted(fault) if fault else contextlib.nullcontext():
        other = compare.reference_readings(cell, shared.SEED, batches,
                                           **variant)
    return judge(compare.numbers(other, reference, cell)[0], cell.limits)


def test_sound_run_is_correct(cell):
    result, log = shared.run(cell)
    assert result["correct"], log
    assert result["compared"]["bn_stats_gap"]["value"] == 0
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["window"]["window_compiles"] == 0


def test_traced_run_reports_the_program_counters(cell):
    import io
    import time
    import jax
    from benchmark import harness
    from mxnet_tpu import profiler
    profiler.reset_spans()      # other tests' networks, in this process
    names = cell.metric_names("per_layer")
    for name in ("attn_kernel_roofline.train",
                 "attn_tiles_visited_share.train",
                 "moe_load_imbalance.train", "step_mfu.train"):
        assert name in names
    result = harness.run_cell(cell, shared.SEED, 1.0, True,
                              time.perf_counter(), jax.local_devices()[:1],
                              out=io.StringIO())
    # on the CPU there is no device plane, no table of peaks and no kernel:
    # the roofline and the tiles' share are silent, the load is read
    assert result["correct"]
    assert set(result["metrics"]) == {
        "window_compiles.train", "peak_hbm_gb.train",
        "moe_load_imbalance.train"}
    assert 1.0 <= result["metrics"]["moe_load_imbalance.train"]["value"] <= 4.0


def test_readers_are_silent_where_the_program_has_no_record(cell):
    from benchmark import harness
    from mxnet_tpu import profiler
    profiler.reset_spans()
    run = {"cell": cell, "trace": None, "peaks": None}
    for name in ("attn_kernel_roofline.train",
                 "attn_tiles_visited_share.train",
                 "moe_load_imbalance.train"):
        assert harness.load_reader(name)(run) is None


def test_control_in_bfloat16_fails(cell):
    import jax.numpy as jnp
    found = judged(cell, dtype=jnp.bfloat16, precision=None,
                   state_dtype=jnp.bfloat16)
    assert not all(n["ok"] for n in found.values()), found


@pytest.mark.parametrize("fault,by", [
    ("half_rows", "grad_norm_gap"), ("drop_expert", "expert_grad_gap"),
    ("own_clean_block", "attn_rows_gap")])
def test_reference_fault_is_not_correct(cell, fault, by):
    found = judged(cell, fault=fault)
    assert not found[by]["ok"], found


def test_state_left_unchanged_is_not_correct(cell):
    with shared.broken_step("state_unchanged", summed_loss=False):
        result, log = shared.run(cell)
    assert not result["correct"], log


def test_roofline_counts_by_hand():
    import importlib.util
    path = os.path.join(shared.ROOT, "benchmark", "metrics",
                        "attn_kernel_roofline.train.py")
    spec = importlib.util.spec_from_file_location("attn_roofline", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    config = {"block_length": 4, "num_attention_heads": 32, "head_dim": 128,
              "num_hidden_layers": 6}
    # 4096 x 4100 visible pairs x 32 heads x 128 x 2 products: 137.6 GMAC a
    # layer forward, as ISSUE 29 counts it
    flops = module.required_flops(config, {"seq_len": 4096})
    assert flops == 3 * 2 * 6 * 4096 * 4100 * 32 * 128 * 2
    assert abs(flops / 6 / 6 / 1e9 - 137.6) < 0.1
