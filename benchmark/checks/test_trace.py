"""The trace reduction against a small recorded trace.

``recorded_trace.json.gz`` is three consecutive steps of
``resnet50_v1.fit_b128`` on one TPU v5e (my chip run, PR 24), as
``trace.load`` summarised them: operation names in ``trace.compact`` form,
times in nanoseconds on the trace's clock.  The sums are checked against a
count made another way (a sweep over sorted edges in numpy).
"""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import harness, trace  # noqa: E402

RAW = [
    ("%fusion.1392 = (f32[256]{0:T(256)}, f32[128,256,56,56]{1,0,3,2:T(8,128)}) "
     "fusion(f32[256]{0:T(256)S(1)} %copy-done.600, bf16[128,64,56,56]{0,1,3,2} "
     "%get-tuple-element.1972), kind=kOutput, calls=%fused_computation.2318",
     "fusion.1392 fusion kOutput f32[128,256,56,56]", True),
    ("%maximum_add_fusion.1 = f32[128,256,56,56]{1,0,3,2:T(8,128)} fusion("
     "f32[128,256,56,56]{1,0,3,2:T(8,128)} %add_add_fusion.5), kind=kLoop, "
     "calls=%fused_computation.12",
     "maximum_add_fusion.1 fusion kLoop f32[128,256,56,56]", False),
    ("%convolution.7 = f32[128,64,112,112]{0,1,3,2} convolution(bf16[128,3,224,224]"
     "{0,1,3,2} %x, bf16[64,3,7,7]{0,1,3,2} %w), window={size=7x7 stride=2x2}",
     "convolution.7 convolution f32[128,64,112,112]", True),
    ("%copy-done.865 = f32[1024]{0:T(1024)} copy-done((f32[1024]{0:T(1024)}, "
     "u32[]{:S(2)}) %copy-start.865)", "copy-done.865 copy-done f32[1024]", False),
    ("%select_and_scatter.9 = f32[128,64,112,112]{0,1,3,2:T(8,128)} "
     "select-and-scatter(f32[128,64,112,112]{0,1,3,2:T(8,128)} %fusion.29)",
     "select_and_scatter.9 select-and-scatter f32[128,64,112,112]", False),
]


@pytest.fixture(scope="module")
def summary():
    return trace.read_recorded(os.path.join(HERE, "recorded_trace.json.gz"))


def sweep_busy(ops, lo, hi):
    """Busy nanoseconds in [lo, hi]: depth of open intervals over sorted
    edges."""
    edges = []
    for _, s, d in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            edges += [(a, 1), (b, -1)]
    edges.sort()
    times = np.array([t for t, _ in edges])
    depth = np.cumsum([k for _, k in edges])
    return int(np.sum(np.diff(times)[depth[:-1] > 0]))


@pytest.mark.parametrize("raw,compact,is_conv", RAW)
def test_compact_and_conv_class(raw, compact, is_conv):
    assert trace.compact(raw) == compact
    assert trace.conv_class(compact) is is_conv


def test_steps_and_periods(summary):
    device = summary.devices[0]
    assert device.step_module().startswith("jit_traced(")
    steps = device.steps()
    assert len(steps) == 3 and len(device.periods()) == 2
    for (a, b), (c, _) in zip(steps, steps[1:]):
        assert 90e6 < b - a < 105e6 and c >= b      # ~97 ms, in order


def test_busy_and_idle(summary):
    device = summary.devices[0]
    busy = summary.busy_ns(device)
    assert busy == sweep_busy(device.ops, summary.lo_ns, summary.hi_ns)
    idle = sum(b - a for a, b in summary.idle_gaps(device))
    assert busy + idle == summary.hi_ns - summary.lo_ns
    per = device.busy_in(device.periods())
    assert per == [sweep_busy(device.ops, a, b) for a, b in device.periods()]


def test_conv_time_is_the_kOutput_fusions(summary):
    ops = summary.devices[0].ops
    convs = [e for e in ops if trace.conv_class(e[0])]
    assert len(convs) == 3 * 162          # 53 convolutions + dense, x 3 passes
    share = sum(e[2] for e in convs) / sum(e[2] for e in ops)
    assert 0.5 < share < 0.6
    kinds = summary.by_class()
    assert list(kinds)[:2] == ["fusion kOutput", "fusion kLoop"]
    assert abs(kinds["fusion kOutput"] - sum(e[2] for e in convs) / 1e9) < 1e-9
    assert abs(sum(kinds.values()) - sum(e[2] for e in ops) / 1e9) < 1e-9


def test_readers(summary):
    cell = harness.Cell("resnet50_v1.fit_b128", ROOT)
    run = {"cell": cell, "trace": summary,
           "peaks": harness.load_json(os.path.dirname(HERE),
                                      "peaks.json")["TPU v5 lite"],
           "device": {"count": 1, "memory_peak_bytes": 10e9},
           "window": {"window_compiles": 0},
           "end_to_end": {"train_images_per_s": 1300.0}}
    got = {name: harness.load_reader(name)(run)
           for name in cell.metric_names("per_layer")}
    assert 96 < got["step_device_ms.train"] < 98
    assert 0 <= got["dispatch_gap_ms.train"] < 0.1
    assert 0 < got["device_idle_share.train"] < 2
    assert 20 < got["conv_roofline.train"] < 40
    assert 15 < got["step_mfu.train"] < 16
    assert got["peak_hbm_gb.train"] == 10.0
    assert got["window_compiles.train"] == 0
    untraced = dict(run, trace=None)
    assert harness.load_reader("conv_roofline.train")(untraced) is None
    line = summary.breakdown()
    assert len(line["device_ops"]) == 10 and line["idle_gaps"]
