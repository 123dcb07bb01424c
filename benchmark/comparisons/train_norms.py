"""Comparison ``train_norms``: what decides ``correct`` for a training cell,
and the one a traffic file gets that names no other under ``compare``.

The program's readings come from the object the window then drives: the loss
of each of its first steps, the first gradient as the optimizer got it (from
the optimizer's state after one step), and how far every parameter and
BatchNorm statistic moved over those steps.  The reference (reference/)
follows the same steps from the same seed in float32 at precision
``highest``, once the window has closed and the program's state is freed:
the family's network and loss under the configuration's optimizer, on the
batches the entry's ``first_batches()`` hands over, each a tuple passed whole.

Norms are compared leaf by leaf, as the gap between the program's norm and the
reference's over the reference's norm of that leaf or of the median leaf,
whichever is larger.  Leaves whose reference gradient is under a thousandth of
the median leaf's (a bias that feeds a BatchNorm) are left out of the change:
they move by round-off alone.

Held to a limit (``limits/<cell>.json``; PERF.md has the readings):

* ``grad_norm_gap``: the median leaf's gap of the first gradient, and
  ``update_norm_gap``: the gap of the change at the leaf nine tenths of the
  way up the sorted gaps.  Not the worst leaf's: at the matmul precision the
  configurations state (XLA's default on the TPU, one bfloat16 pass) the
  worst leaf is always a BatchNorm scale or shift of the first blocks, whose
  gradient is a sum with heavy cancellation over some million positions and
  comes out 20 to 60% off in norm, in the program and in the reference run
  at that precision alike (PERF.md, PR 24).  The median gradient gap is what
  half a batch left out moves most (40 times a sound run's); the nine-tenths
  leaf of the change is what bfloat16 weights move most, since a tenth of
  the leaves and more lose their small updates to rounding (8 to 13 times a
  sound run's, where the median leaf's change reads 3 to 8 times).
* ``bn_stats_gap``: the worst BatchNorm statistic's gap of its change; 0 for
  a family that has none.

Reported beside them and held to nothing (``observed``): the worst step's
loss gap, the worst leaf's two gaps and the median leaf's gap of the change,
which no control or fault separates from a sound run as well.
"""
from __future__ import annotations

import math
import statistics

AUX_LEAVES = ("_running_mean", "_running_var")
DEAD_GRADIENT = 1e-3     # of the median leaf's gradient norm


def leaf_gaps(program, reference, leaves):
    """(gap, leaf) for every leaf, worst first: |program - reference| over
    max(reference, median); 1.0 for a leaf the program lacks, infinite for
    one it reads as not finite."""
    if not leaves:
        return [(0.0, None)]
    floor = statistics.median(reference[k] for k in leaves)
    found = []
    for k in leaves:
        got = program.get(k)
        if got is None:
            gap = 1.0
        elif not math.isfinite(got):
            gap = math.inf
        else:
            scale = max(reference[k], floor)
            gap = abs(got - reference[k]) / scale if scale > 0 else 0.0
        found.append((gap, k))
    return sorted(found, reverse=True)


def moved_leaves(reference):
    """The parameters whose reference gradient is not dead."""
    grads = reference["grad_norms"]
    floor = DEAD_GRADIENT * statistics.median(grads.values())
    return sorted(k for k in grads if grads[k] >= floor)


def _median_gap(program, reference, leaves):
    found = leaf_gaps(program, reference, leaves)
    return statistics.median(gap for gap, _ in found), "median leaf"


def _upper_gap(program, reference, leaves, share=0.9):
    """The gap ``share`` of the way up the sorted gaps, with its leaf."""
    found = sorted(leaf_gaps(program, reference, leaves))
    return found[int(share * len(found))]


def numbers(program, reference):
    """(held, observed): the numbers compared and those only reported, each
    ``(value, where)``, from two sets of readings (see reference/common.py
    train_readings for their form)."""
    ref_grad, ref_change = reference["grad_norms"], reference["change_norms"]
    params, moved = sorted(ref_grad), moved_leaves(reference)
    stats = sorted(k for k in ref_change if k.endswith(AUX_LEAVES))
    held = {
        "grad_norm_gap": _median_gap(program["grad_norms"], ref_grad, params),
        "update_norm_gap": _upper_gap(program["change_norms"], ref_change,
                                      moved),
        "bn_stats_gap": leaf_gaps(program["change_norms"], ref_change,
                                  stats)[0],
    }
    gaps = [abs(p - r) / abs(r) if math.isfinite(p) else math.inf
            for p, r in zip(program["losses"], reference["losses"])]
    if len(program["losses"]) != len(reference["losses"]):
        gaps.append(math.inf)
    observed = {
        "loss_gap": (max(gaps), "step %d" % (gaps.index(max(gaps)) + 1)),
        "grad_norm_gap_worst_leaf": leaf_gaps(program["grad_norms"],
                                              ref_grad, params)[0],
        "update_norm_gap_worst_leaf": leaf_gaps(program["change_norms"],
                                                ref_change, moved)[0],
        "update_norm_gap_median_leaf": _median_gap(program["change_norms"],
                                                   ref_change, moved),
    }
    return held, observed


def judge(held, limits):
    """Each number compared beside its limit.  A number that is not finite
    (a loss of a step that did not run) fails."""
    judged = {}
    for name, (value, where) in held.items():
        finite = math.isfinite(value)
        judged[name] = {"value": value if finite else 1e30,
                        "limit": limits[name], "where": where,
                        "ok": finite and value <= limits[name]}
    return judged


def reference_readings(cell, seed, batches, **variant):
    from benchmark.reference import common
    return common.train_readings(cell.config, seed, batches, cell.traffic,
                                 **variant)


def compare(cell, seed, program, run):
    """(judged numbers, observed numbers) of one run: ``program`` is what the
    entry's ``readings()`` gave before its state was freed."""
    held, observed = numbers(
        program, reference_readings(cell, seed, run.first_batches()))
    return judge(held, cell.limits), {
        k: v if math.isfinite(v) else 1e30 for k, (v, _) in observed.items()}
