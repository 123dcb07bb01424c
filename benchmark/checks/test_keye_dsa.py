"""A tiny ``keye_dsa`` cell is ``correct`` when sound, and not under the
control and each fault.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/checks -q

The cell under ``cells_keye/`` (``tiny_keye.sft_b2_s32``, a spec of its own:
``Cell(..., spec=..., base=...)``) is the family of
``keye_vl2_30b_a3b_ep8.sft_b1_s8192`` at toy widths: causal attention over
the 8 keys an indexer picks for each of 32 queries, 4 of 8 experts held, Adam,
generator ``next_token``, entry ``block_step`` over the program's own
``mxnet_tpu.gluon.model_zoo.sparse_causal_lm``.  The control is the reference
in bfloat16 put in the program's place; the faults (faults_keye.py, planted
in the reference): the selection ignored, ``topk`` halved, the indexer's loss
left out, the indexer's input not detached, one held expert's output left
out, half of the loss's rows left out; and, in the program, a step that leaves
its state unchanged.  The comparison is the family's own,
``comparisons/keye_layers.py``: whatever stands in the program's place is
held against the sound reference given its picks.
"""
import contextlib
import os

import pytest

from benchmark.checks import test_correct as shared

CELL = "tiny_keye.sft_b2_s32"
CELLS = os.path.join(shared.HERE, "cells_keye")
METRICS = ("dsa_kernel_roofline.train", "dsa_target_ms.train",
           "dsa_selected_share.train", "dsa_index_roofline.train")


@pytest.fixture(scope="module")
def cell():
    from benchmark import harness
    shared.load_cell("tiny_seq.seq_b8")       # the path and the cache
    return harness.Cell(CELL, shared.ROOT,
                        spec=harness.load_json(CELLS, "spec.json"),
                        base=CELLS)


def judged(cell, fault=None, **variant):
    from benchmark import traffic
    from benchmark.checks import faults_keye
    from benchmark.comparisons import keye_layers as compare
    from benchmark.comparisons.train_norms import judge
    batches = traffic.make_pool(cell.config, cell.traffic, shared.SEED, 3)
    with faults_keye.planted(fault) if fault else contextlib.nullcontext():
        other = compare.reference_readings(cell, shared.SEED, batches,
                                           **variant)
    reference = compare.reference_readings(
        cell, shared.SEED, batches, given=other["layers"]["pairs"])
    return judge(compare.numbers(other, reference, cell)[0], cell.limits)


def test_sound_run_is_correct(cell):
    result, log = shared.run(cell)
    assert result["correct"], log
    assert result["compared"]["bn_stats_gap"]["value"] == 0
    assert result["compared"]["index_select_gap"]["value"] == 0
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
    for name in METRICS + ("moe_load_imbalance.train", "step_mfu.train"):
        assert name in names
    result = harness.run_cell(cell, shared.SEED, 1.0, True,
                              time.perf_counter(), jax.local_devices()[:1],
                              out=io.StringIO())
    # on the CPU there is no device plane, no table of peaks and no kernel:
    # the two rooflines and the target's time are silent; the selection's
    # share and the load are read
    assert result["correct"]
    assert set(result["metrics"]) == {
        "window_compiles.train", "peak_hbm_gb.train",
        "moe_load_imbalance.train", "dsa_selected_share.train"}
    # 8 keys a query of 32: (36 + 24 * 8) of 528 causal pairs
    share = result["metrics"]["dsa_selected_share.train"]["value"]
    assert abs(share - 100.0 * 228 / 528) < 1e-9


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
    ("dense_causal", "attn_rows_gap"), ("half_topk", "index_select_gap"),
    ("no_index_loss", "index_grad_gap"),
    ("attached_indexer", "index_grad_gap"),
    ("drop_expert", "expert_grad_gap"), ("half_rows", "grad_norm_gap")])
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
                        "dsa_kernel_roofline.train.py")
    spec = importlib.util.spec_from_file_location("dsa_roofline", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    config = {"sa_config": {"topk": 2048}, "num_attention_heads": 32,
              "head_dim": 128, "num_hidden_layers": 5}
    # 14,681,088 picked pairs x 32 heads x 128 x 2 products: 120.27 GMAC a
    # layer forward, as ISSUE 33 counts it
    flops = module.required_flops(config, {"seq_len": 8192})
    assert flops == 3 * 2 * 5 * 14681088 * 32 * 128 * 2
    assert abs(flops / 5 / 6 / 1e9 - 120.27) < 0.01
