"""benchmark/spans.py on a hand-made Summary and a fake recorder: the clock
mapping, its check, the attribution of idle to the spans that cover it, and
the seven readers under metrics/ that PR 25 added.

The trace here has three runs of the step program with a small program after
each of the first two, so five stretches of between-program idle (200, 20,
100, 250 and 500 ns).  The fake program's clock runs 5 s ahead of the
trace's.  Times below are on the trace's clock; ``host`` shifts them.
"""
import collections
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import harness, spans, trace  # noqa: E402

Span = collections.namedtuple(
    "Span", "name start_ns end_ns cpu_ns thread parent seq attrs")

AHEAD = 5_000_000_000
LO, HI = 1_000, 300_500
STEP = "jit_train_step(1)"
MODULES = [(STEP, 1_000, 99_000), ("jit__unstack(2)", 100_200, 100),
           (STEP, 100_320, 99_680), ("jit__unstack(2)", 200_100, 50),
           (STEP, 200_400, 99_600)]
BENCH = [("bench:callback", 150_000, 10), ("bench:next", 150_250, 20),
         ("bench:callback", 250_000, 10), ("bench:callback", 300_100, 400)]
CALLER, FEED = 1, 2
# (name, start, end, cpu, thread, parent, seq): the program reads the CPU
# clock in fit.step and the feed's stages only
RECORDED = [
    ("fit.step", 50_000, 150_100, 4_000, CALLER, None, 0),
    ("fit.next", 50_010, 100_250, None, CALLER, "fit.step", 1),
    ("feed.wait", 100_050, 100_240, None, CALLER, "fit.next", 1),
    ("step.dispatch", 100_260, 149_900, None, CALLER, "fit.step", 0),
    ("cachedop.call", 100_280, 149_800, None, CALLER, "step.dispatch", 0),
    ("fit.callback", 149_990, 150_050, None, CALLER, "fit.step", 0),
    ("fit.step", 150_200, 200_300, 6_000, CALLER, None, 1),
    ("fit.next", 150_210, 150_300, None, CALLER, "fit.step", 2),
    ("step.dispatch", 150_310, 200_050, None, CALLER, "fit.step", 1),
    ("fit.step", 200_350, 300_600, 9_000, CALLER, None, 2),
    ("fit.next", 200_360, 200_390, None, CALLER, "fit.step", 3),
    ("fit.callback", 249_990, 250_050, None, CALLER, "fit.step", 2),
    ("fit.callback", 300_050, 300_600, None, CALLER, "fit.step", 2),
    ("feed.h2d", 500, 900, 9_999, FEED, None, 0),        # before the window
    ("feed.source", 2_000, 2_200, 100, FEED, None, 1),
    ("feed.h2d", 2_300, 40_000, 2_000, FEED, None, 1),
    ("feed.put_wait", 40_100, 100_000, 50, FEED, None, 1),
    ("feed.source", 100_100, 100_300, 100, FEED, None, 2),
    ("feed.h2d", 100_400, 140_000, 2_000, FEED, None, 2),
    ("feed.put_wait", 140_100, 200_000, 50, FEED, None, 2),
]
TOTALS = {"fit.step": {"count": 3, "wall_ns": 1, "cpu_ns": 1, "max": 1},
          "fit.bind": {"count": 1, "wall_ns": 1_000_000_000},
          "fit.init_params": {"count": 1, "wall_ns": 2_000_000_000},
          "fit.init_optimizer": {"count": 1, "wall_ns": 500_000_000},
          "fit.build_step": {"count": 1, "wall_ns": 250_000_000}}
T_OPEN_NS = -1_000_000          # the window opened 1 ms before the trace
METRICS = ["host_cpu_ms.train", "feed_cpu_ms.train", "feed_starved_ms.train",
           "dispatch_exposed_ms.train", "idle_unattributed_share.train",
           "setup_prologue_s.train", "setup_first_call_s.train"]
NEED_THE_MAPPING = METRICS[:5]


def host(ns, skew=0):
    return ns + AHEAD + skew


def recorded(skew=0):
    out = [Span(n, host(a, skew), host(b, skew), cpu, thread, parent, seq,
                None)
           for n, a, b, cpu, thread, parent, seq in RECORDED]
    first = lambda a, b, op: Span(
        "cachedop.first_call", host(a), host(b), None, CALLER, "step.dispatch",
        0, {"op": op})
    return out + [first(T_OPEN_NS - 7_000_000_000, T_OPEN_NS - 1_000_000_000,
                        "train_step"),           # 6 s, in set-up
                  first(T_OPEN_NS - 900_000_000, T_OPEN_NS - 800_000_000,
                        "traced"),               # another CachedOp
                  first(60_000, 70_000, "train_step")]   # inside the window


def make_run(traced=True):
    device = trace.Device("/device:TPU:0", list(MODULES), list(MODULES))
    summary = trace.Summary(LO, HI, [device], list(BENCH))
    return {"trace": summary if traced else None,
            "window": {"t_open": host(T_OPEN_NS) / 1e9,
                       "t_close": host(HI) / 1e9}}


@pytest.fixture
def fake(monkeypatch):
    def install(skew=0, totals=TOTALS):
        monkeypatch.setattr(spans, "record", lambda: (recorded(skew), totals))
    return install


def test_clock_mapping_and_check(fake, capsys):
    fake()
    found = spans.program_spans(make_run())
    assert found.offset == -AHEAD
    assert found.aligned and found.miss_ns <= 0
    steps = [s for s in found.spans if s.name == "fit.step"]
    assert [(s.start, s.end) for s in steps] == [
        (50_000, 150_100), (150_200, 200_300), (200_350, 300_600)]
    assert found.periods() == 2
    assert "clock check missed by" in capsys.readouterr().err


def test_skew_of_a_millisecond_silences_the_mapped_readers(fake, capsys):
    fake(skew=1_000_000)
    run = make_run()
    found = spans.program_spans(run)
    assert not found.aligned and found.miss_ns >= 999_000
    err = capsys.readouterr().err
    assert err.count("the clock mapping missed by") == 1
    for name in NEED_THE_MAPPING:
        assert harness.load_reader(name)(run) is None
    assert harness.load_reader("setup_prologue_s.train")(run) == 3.75
    assert capsys.readouterr().err == ""          # said once per run


def test_no_callback_span_means_no_check_and_no_metric(fake):
    fake()
    run = make_run()
    run["trace"].spans = [s for s in BENCH if s[0] != "bench:callback"]
    assert spans.program_spans(run).miss_ns is None
    assert spans.feed_starved_ms(run) is None


def test_idle_goes_to_the_innermost_span(fake):
    fake()
    found = spans.program_spans(make_run())
    pieces = found.idle()
    assert sum(ns for ns, _ in pieces) == 200 + 20 + 100 + 250 + 500
    innermost = {}
    for ns, chain in pieces:
        key = chain[-1].name if chain else None
        innermost[key] = innermost.get(key, 0) + ns
    assert innermost == {"fit.next": 50 + 30, "feed.wait": 150,
                         "cachedop.call": 20, "step.dispatch": 50,
                         "fit.step": 50 + 150 + 10 + 10 + 50, None: 50,
                         "fit.callback": 450}
    assert found.idle_under("feed.wait") == 150
    assert found.idle_under("step.dispatch") == 70     # with cachedop.call's
    assert found.idle_unattributed() == 320


def test_caller_wall_is_each_spans_own(fake):
    fake()
    steps, cpu_ns, wall = spans.program_spans(make_run()).caller_wall()
    assert steps == 2                   # the third fit.step ends after hi
    assert cpu_ns == 4_000 + 6_000
    # step 0: fit.next takes 50,240 of which feed.wait holds 190
    assert wall["feed.wait"] == 190
    assert wall["fit.next"] == 50_240 - 190 + 90
    assert wall["cachedop.call"] == 49_520
    # less the first call that the fake puts inside the window
    assert wall["step.dispatch"] == (49_640 - 49_520) + 49_740 - 10_000
    assert wall["fit.callback"] == 60
    assert sum(wall.values()) == 100_100 + 50_100


def test_the_seven_readers(fake):
    fake()
    run = make_run()
    read = {name: harness.load_reader(name)(run) for name in METRICS}
    assert read["host_cpu_ms.train"] == pytest.approx(5_000 / 1e6)
    assert read["feed_cpu_ms.train"] == pytest.approx(2_150 / 1e6)
    assert read["feed_starved_ms.train"] == pytest.approx(150 / 2 / 1e6)
    assert read["dispatch_exposed_ms.train"] == pytest.approx(70 / 2 / 1e6)
    assert read["idle_unattributed_share.train"] == \
        pytest.approx(100 * 320 / 1070)
    assert read["setup_prologue_s.train"] == 3.75
    assert read["setup_first_call_s.train"] == 6.0


@pytest.mark.parametrize("name", METRICS)
def test_silent_when_untraced_or_without_a_recorder(name, fake, monkeypatch):
    fake()
    assert harness.load_reader(name)(make_run(traced=False)) is None
    monkeypatch.setattr(spans, "record", lambda: None)   # PR 25's parent
    assert harness.load_reader(name)(make_run()) is None
    fake(totals={})                                      # no fit() ran
    assert harness.load_reader(name)(make_run()) is None
    fake()                          # a window that gives no anchor
    run = dict(make_run(), window={"window_compiles": 0})
    assert harness.load_reader(name)(run) is None


def test_every_new_metric_is_in_the_manifest():
    spec = harness.load_json(ROOT, "BENCHMARK.json")
    listed = {m["name"]: m for m in spec["per_layer"]}
    fit_cells = [w["name"] for w in spec["workloads"] if harness.load_json(
        ROOT, "benchmark", "traffic", w["traffic"] + ".json")["entry"]
        == "module_fit"]
    for name in METRICS:
        # they read fit()'s spans: for the cells whose entry runs fit()
        assert listed[name]["workloads"] == fit_cells
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                           name + ".py"))
