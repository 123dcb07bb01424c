"""``attn_fwd_calls.train`` over hand-made devices: runs of a step program
that hold 12, then 6, operations named ``attention_fwd`` each, one with none,
and one whose first run the trace's start cut short.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/checks -q
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, trace  # noqa: E402

STEP_NS, KERNEL_NS = 500_000_000, 5_500_000


def device(calls_a_step, cut=0):
    """Three runs of ``jit_train_step`` with a small program after each and a
    probe's forward kernel after them, outside any run of the step; the first
    run lacks its first ``cut`` forward kernels."""
    starts = [i * (STEP_NS + 2000) for i in range(3)]
    modules = [("jit_probe", 4 * STEP_NS, 2 * KERNEL_NS)]
    for start in starts:
        modules += [("jit_train_step", start, STEP_NS),
                    ("jit_split", start + STEP_NS, 1000)]
    ops = [("attention_fwd.99 custom-call f32[32,8192,1]", 4 * STEP_NS,
            KERNEL_NS)]
    for start in starts:
        for i in range(cut if start == 0 else 0, calls_a_step):
            at = start + 3 * i * KERNEL_NS
            ops += [("attention_fwd.%d custom-call f32[32,8192,1]" % i, at,
                     KERNEL_NS),
                    ("attention_bwd_dq.%d custom-call f32[32,8192,128]" % i,
                     at + KERNEL_NS, KERNEL_NS),
                    ("fusion.%d fusion kLoop f32[8192,2048]" % i,
                     at + 2 * KERNEL_NS, KERNEL_NS)]
        ops.append(("fusion.900 fusion kOutput f32[8192,18992]",
                    start + STEP_NS - KERNEL_NS, KERNEL_NS))
    return trace.Summary(0, 5 * STEP_NS, [trace.Device(
        "/device:TPU:0", sorted(ops, key=lambda e: e[1]),
        sorted(modules, key=lambda e: e[1]))], [])


@pytest.mark.parametrize("calls_a_step,cut,reads", [
    (12, 0, 12), (6, 0, 6), (0, 0, None), (12, 3, 12), (6, 3, 6)])
def test_forward_kernels_a_step(calls_a_step, cut, reads):
    read = harness.load_reader("attn_fwd_calls.train")
    assert read({"trace": device(calls_a_step, cut)}) == reads


def test_no_trace_is_silent():
    assert harness.load_reader("attn_fwd_calls.train")({"trace": None}) is None
