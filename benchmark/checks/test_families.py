"""The harness takes a family that is not an image classifier in new files
alone.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/checks -q

The tiny sequence cell under ``cells/`` (``tiny_seq.seq_b8``) has integer
inputs, a batch of three arrays, a masked loss written with ``Ops.einsum``,
Adam, a generator of tokens and an entry that drives
``CompiledTrainStep.from_block``; the commit that added it added files and
``cells/spec.json`` entries and edited nothing else.  As in test_correct.py
the look for a chip is skipped and the rest of a run is driven:

* a sound run is correct, and reports what the entry gave it;
* the control, the reference in bfloat16 put in the program's place, fails;
* with the timed path broken underneath, ``correct`` is false: a step that
  returns its state unchanged; half of the batch left out and the mean taken
  over the rest;
* ``step_mfu.train``'s FLOPs equal a count written out by hand;
* an entry whose end-to-end names are not the cell's is refused.
"""
import io
import time

import pytest

from benchmark.checks import test_correct as shared

CELL = "tiny_seq.seq_b8"


@pytest.fixture(scope="module")
def cell():
    return shared.load_cell(CELL)


def test_sound_run_is_correct(cell):
    result, log = shared.run(cell)
    assert result["correct"], log
    assert set(result["compared"]) == {"grad_norm_gap", "update_norm_gap",
                                       "bn_stats_gap"}
    assert result["compared"]["bn_stats_gap"]["value"] == 0   # no such leaf
    assert sorted(result["metrics"]) == sorted(cell.metric_names("end_to_end"))
    assert result["metrics"]["train_images_per_s"]["value"] > 0
    assert result["attempted"] == result["window"]["steps"] > 0
    assert result["failed"] == 0
    assert result["window"]["samples"] == \
        result["attempted"] * cell.traffic["batch"]
    assert result["window"]["window_compiles"] == 0
    assert result["window"]["step_signatures"] == 1


def test_traced_run_reports_the_metrics_that_hold_for_any_family(cell):
    import jax
    from benchmark import harness
    names = cell.metric_names("per_layer")
    assert "step_mfu.train" in names and "conv_roofline.train" not in names
    result = harness.run_cell(cell, shared.SEED, 1.0, True,
                              time.perf_counter(), jax.local_devices()[:1],
                              out=io.StringIO())
    # on the CPU there is no device plane to read and no table of peaks
    assert result["correct"]
    assert set(result["metrics"]) == {"window_compiles.train",
                                      "peak_hbm_gb.train"}


def test_control_in_bfloat16_fails(cell):
    import jax.numpy as jnp
    from benchmark import traffic
    from benchmark.comparisons import train_norms as compare
    batches = traffic.make_pool(cell.config, cell.traffic, shared.SEED, 3)
    assert [a.dtype.name for a in batches[0]] == ["int32", "int32", "float32"]
    reference = compare.reference_readings(cell, shared.SEED, batches)
    control = compare.reference_readings(
        cell, shared.SEED, batches, dtype=jnp.bfloat16, precision=None,
        state_dtype=jnp.bfloat16)
    judged = compare.judge(compare.numbers(control, reference)[0],
                           cell.limits)
    assert not all(n["ok"] for n in judged.values()), judged


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_broken_step_is_not_correct(cell, fault):
    with shared.broken_step(fault, summed_loss=False):
        result, log = shared.run(cell)
    assert not result["correct"], log
    assert "FAILED" in log


def test_flops_equal_a_count_by_hand(cell):
    from benchmark import flops, harness
    vocab, dim, ffn = (cell.config[k] for k in ("vocab", "dim", "ffn"))
    # per token: gate, up and down are ffn x dim each, the head vocab x dim;
    # 2 FLOPs per multiply-accumulate, 3 passes; the embedding is a lookup
    by_hand = 3 * 2 * cell.traffic["seq_len"] * (3 * ffn * dim + vocab * dim)
    assert by_hand == 638976
    assert flops.train_flops_per_sample(cell) == by_hand
    run = {"cell": cell, "peaks": {"flops_per_s": 197e12},
           "device": {"count": 1},
           "end_to_end": {"train_images_per_s": 1000.0}}
    assert harness.load_reader("step_mfu.train")(run) == \
        pytest.approx(100.0 * by_hand * 1000.0 / 197e12)


def test_entry_with_other_end_to_end_names_is_refused(cell, monkeypatch):
    import jax
    from benchmark import harness
    from benchmark.entries import block_step
    sound = block_step.Run.drive

    def renamed(self, window=True):
        found = sound(self, window)
        values = found["end_to_end"]
        values["train_tokens_per_s"] = values.pop("train_images_per_s")
        return found

    monkeypatch.setattr(block_step.Run, "drive", renamed)
    with pytest.raises(harness.BenchmarkError, match="train_tokens_per_s"):
        harness.run_cell(cell, shared.SEED, 0.2, False, time.perf_counter(),
                         jax.local_devices()[:1], out=io.StringIO())
