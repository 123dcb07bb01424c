"""``moe_slot_fill_share.train`` over the program's recorder and
``moe_experts_roofline.train`` over a trace's operations: a program with a
slot table, one without (the readers' parent), and a trace with no expert
kernel in it.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/checks -q
"""
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, trace  # noqa: E402
from mxnet_tpu import profiler  # noqa: E402


@pytest.mark.parametrize("slots,loads,reads", [
    (69632, [8192, 8192], 100.0 * 8192 / 69632),
    (67584, [9966, 6236, 9310, 9173], 100.0 * 34685 / (4 * 67584)),
    (65536 + 8 * 256, [65536], 100.0 * 65536 / 67584),    # a collapsed router
    (69632, [8192, 0], 100.0 * 8192 / 69632),   # a block that never stepped
    (None, [8192], None),                       # no slot table: the parent
    (69632, [], None)])
def test_slot_fill_share(slots, loads, reads):
    profiler.reset_spans()
    if slots:
        profiler.count("moe.rows", 8192)
        profiler.count("moe.slots", slots)
    for i, pairs in enumerate(loads):
        profiler.gauge("moe.load.check%d_" % i,
                       lambda pairs=pairs: (float(pairs), 1.0))
    try:
        value = harness.load_reader("moe_slot_fill_share.train")(
            {"trace": None})
        assert value == (None if reads is None else pytest.approx(reads))
    finally:
        for i in range(len(loads)):
            profiler.gauge("moe.load.check%d_" % i, lambda: (0.0, 0.0))
        profiler.reset_spans()


def _run(cell_name, ops):
    cell = harness.Cell(cell_name, ROOT)
    step = [("jit_train_step(1)", t, 100_000_000) for t in (0, 100_000_000)]
    device = trace.Device("/device:TPU:0", sorted(
        ops, key=lambda op: op[1]), step)
    return {"trace": types.SimpleNamespace(devices=[device]), "cell": cell,
            "peaks": {"flops_per_s": 197e12}}


def test_experts_roofline_counts_the_even_load_over_the_kernels_time():
    read = harness.load_reader("moe_experts_roofline.train")
    routed_layers, required_flops = (read.__globals__[name] for name in (
        "routed_layers", "required_flops"))
    # pairs a layer at an even load: rows x k x held / E
    for name, pairs, layers, f in (
            ("sdar_30b_a3b_ep8.bd_b1_s4096", 8192, 6, 768),
            ("keye_vl2_30b_a3b_ep8.sft_b1_s8192", 8192, 5, 768),
            ("lfm2_24b_a2b_ep8.sft_b2_s8192", 4096, 4, 1536)):
        cell = harness.Cell(name, ROOT)
        assert routed_layers(cell.config) == layers
        assert required_flops(cell.config, cell.traffic) \
            == pairs * 3 * f * 2048 * 2 * 3 * layers
    # two whole steps, 10 ms of the kernels in each, one operation outside
    ops = [("moe_experts_hidden.1 custom-call", 1_000_000, 4_000_000),
           ("moe_experts_bwd.1 custom-call", 6_000_000, 6_000_000),
           ("fusion.3 fusion kCustom", 20_000_000, 9_000_000),
           ("moe_experts_wgrad.1 custom-call", 101_000_000, 10_000_000)]
    run = _run("lfm2_24b_a2b_ep8.sft_b2_s8192", ops)
    need = 2 * 2 * 4096 * 3 * 1536 * 2048 * 2 * 3 * 4
    assert read(run) == pytest.approx(100.0 * need / 197e12 / 0.020)
    # no such kernel (the parent's XLA fusions), or no trace: silent, never 0
    assert read(_run("lfm2_24b_a2b_ep8.sft_b2_s8192", ops[2:3])) is None
    assert read(dict(run, trace=None)) is None
    assert read(_run("resnet50_v1.fit_b128", ops)) is None
