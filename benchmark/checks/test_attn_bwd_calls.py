"""``attn_bwd_calls.train`` over hand-made devices: runs of a step program
that hold 12 backward attention operations each (``attention_bwd_dq`` and
``attention_bwd_dkv``, six of each), then 6 (``attention_bwd``), one with
none, and one whose first run the trace's start cut short.

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
TWO_WALKS = ("attention_bwd_dq", "attention_bwd_dkv")
ONE_WALK = ("attention_bwd",)


def device(kernels, cut=0):
    """Three runs of ``jit_train_step`` over six layers, each layer a forward
    kernel, its backward ``kernels`` and a fusion, with a small program after
    each run and a probe's backward kernel after them all, outside any run of
    the step; the first run lacks its first ``cut`` layers' kernels."""
    starts = [i * (STEP_NS + 2000) for i in range(3)]
    modules = [("jit_probe", 4 * STEP_NS, 2 * KERNEL_NS)]
    for start in starts:
        modules += [("jit_train_step", start, STEP_NS),
                    ("jit_split", start + STEP_NS, 1000)]
    ops = [("attention_bwd.99 custom-call f32[32,8192,128]", 4 * STEP_NS,
            KERNEL_NS)]
    for start in starts:
        for layer in range(cut if start == 0 else 0, 6):
            names = ("attention_fwd",) + tuple(kernels) + ("fusion",)
            for slot, name in enumerate(names):
                ops.append(("%s.%d custom-call f32[32,8192,128]"
                            % (name, layer),
                            start + (4 * layer + slot) * KERNEL_NS, KERNEL_NS))
        ops.append(("fusion.900 fusion kOutput f32[8192,18992]",
                    start + STEP_NS - KERNEL_NS, KERNEL_NS))
    return trace.Summary(0, 5 * STEP_NS, [trace.Device(
        "/device:TPU:0", sorted(ops, key=lambda e: e[1]),
        sorted(modules, key=lambda e: e[1]))], [])


@pytest.mark.parametrize("kernels,cut,reads", [
    (TWO_WALKS, 0, 12), (ONE_WALK, 0, 6), ((), 0, None), (TWO_WALKS, 2, 12),
    (ONE_WALK, 2, 6)])
def test_backward_kernels_a_step(kernels, cut, reads):
    read = harness.load_reader("attn_bwd_calls.train")
    assert read({"trace": device(kernels, cut)}) == reads


def test_no_trace_is_silent():
    assert harness.load_reader("attn_bwd_calls.train")({"trace": None}) is None
