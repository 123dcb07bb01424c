"""``correct`` comes out false where it must, at a size a test run can hold.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/checks -q

The harness's look for a chip is skipped (``run_cell`` is handed the CPU's
device) and the rest of a run is driven on the tiny cell under ``cells/``:

* a sound run is correct;
* the control, the reference in bfloat16 put in the program's place, fails at
  least one number (PERF.md has the same at the cells' own sizes on the chip);
* with the timed path broken underneath, ``correct`` is false: a step that
  returns its state unchanged; half of the batch left out and the mean taken
  over the rest.

Not part of ``tests/``: the tier-1 count does not include it.
"""
import contextlib
import io
import os
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELLS = os.path.join(HERE, "cells")
CELL = "tiny_resnet.fit_b16"
SEED = 2345678901


def load_cell(name):
    import sys
    sys.path.insert(0, ROOT)
    from benchmark import harness
    harness.configure_jax(os.environ.get("TMPDIR") or
                          os.path.join(ROOT, ".bench_tmp"))
    return harness.Cell(name, ROOT, spec=harness.load_json(CELLS, "spec.json"),
                        base=CELLS)


@pytest.fixture(scope="module")
def cell():
    return load_cell(CELL)


def run(cell):
    import jax
    from benchmark import harness
    log = io.StringIO()
    result = harness.run_cell(cell, SEED, 1.0, False, time.perf_counter(),
                              jax.local_devices()[:1], out=log)
    return result, log.getvalue()


@contextlib.contextmanager
def broken_step(fault, summed_loss=True):
    """Plant ``fault`` under the timed path: in the program's compiled step.
    ``summed_loss``: the step's loss is a sum that the optimizer divides by
    the batch (a Module's), not a mean of its own (a block's)."""
    import mxnet_tpu as mx
    from mxnet_tpu.module.compiled_step import CompiledTrainStep
    sound = CompiledTrainStep.run_window

    def unchanged(self, batches_io):      # a loss comes back, no state moves
        return mx.nd.zeros((len(batches_io),))

    def half_batch(self, batches_io):
        if summed_loss and not getattr(self, "_bench_fault", False):
            self._bench_fault = True
            self._optimizer.rescale_grad *= 2     # the mean over the rest
        cut = [tuple(x[:x.shape[0] // 2] for x in b) for b in batches_io]
        return sound(self, cut)

    CompiledTrainStep.run_window = {"state_unchanged": unchanged,
                                    "half_batch": half_batch}[fault]
    try:
        yield
    finally:
        CompiledTrainStep.run_window = sound


def test_sound_run_is_correct(cell):
    result, log = run(cell)
    assert result["correct"], log
    assert list(result)[-1] == "compared"
    assert set(result["compared"]) == {"grad_norm_gap", "update_norm_gap",
                                       "bn_stats_gap"}
    # the end-to-end values and the counts of operations are the entry's
    assert sorted(result["metrics"]) == sorted(cell.metric_names("end_to_end"))
    assert result["metrics"]["train_images_per_s"]["value"] > 0
    assert result["attempted"] == result["window"]["steps"] > 0
    assert result["failed"] == 0
    assert result["window"]["window_compiles"] == 0


def test_control_in_bfloat16_fails(cell):
    import jax.numpy as jnp
    from benchmark import traffic
    from benchmark.comparisons import train_norms as compare
    batches = traffic.make_pool(cell.config, cell.traffic, SEED, 3)
    reference = compare.reference_readings(cell, SEED, batches)
    control = compare.reference_readings(
        cell, SEED, batches, dtype=jnp.bfloat16, precision=None,
        state_dtype=jnp.bfloat16)
    judged = compare.judge(compare.numbers(control, reference)[0],
                           cell.limits)
    assert not all(n["ok"] for n in judged.values()), judged


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_broken_step_is_not_correct(cell, fault):
    with broken_step(fault):
        result, log = run(cell)
    assert not result["correct"], log
    assert "FAILED" in log
