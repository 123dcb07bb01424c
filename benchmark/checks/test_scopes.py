"""benchmark/scopes.py and the nine readers of PR 37 on a small hand-made pair
(``recorded_scopes.json.gz``): a ``Summary`` and the optimized HLO text that
the program's table is parsed from.

The trace has four runs of ``jit_train_step`` of 21 operations each, 945 us of
them outside the one container: forward 400 us, backward 490 (120 of it
recomputed, four operations inside ``while.10``'s 200 us; 10 a copy that the
compiler made, without a name, scheduled before a backward kernel), optimizer
45, metric 5, a last copy without a name 5.  The first run lacks its first three
operations (a trace starts inside a run), and a small program between two
runs holds an operation called ``fusion.1`` like one of the step's.  The text
has the entry, the loop's body and condition, a reduction's region, and a
fused computation for every fusion.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/checks -q
"""
import collections
import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import harness, scopes, spans, trace  # noqa: E402
from mxnet_tpu import profiler  # noqa: E402

Span = collections.namedtuple(
    "Span", "name start_ns end_ns cpu_ns thread parent seq attrs")

STEP_US = 945.0
READS = {"fwd_device_ms.train": 0.400, "bwd_device_ms.train": 0.490,
         "opt_device_ms.train": 0.045, "remat_device_ms.train": 0.120,
         "moe_layer_ms.train": 0.400, "moe_move_ms.train": 0.120,
         "scope_unnamed_share.train": 100.0 * 110 / 890,
         "setup_step_lower_s.train": 3.0, "setup_step_compile_s.train": 2.5}
T_OPEN = 100.0          # seconds on the recorder's clock


class Executable:
    """What ``profiler.program_ops`` asks of a kept ``jax.stages.Compiled``."""

    def __init__(self, text):
        self.text = text

    def runtime_executable(self):
        return self

    def hlo_modules(self):
        return [self]

    def to_string(self, options):
        return self.text


def recorded():
    with gzip.open(os.path.join(HERE, "recorded_scopes.json.gz"), "rt") as f:
        pair = json.load(f)
    return trace.Summary.from_json(pair["summary"]), pair["hlo"]


def first_calls(parts=True):
    """The recorder's spans of a step whose first call took 6 s in set-up
    (3 s to lower, 2.5 s to compile) and of a second signature's first call
    inside the window, which no set-up metric counts; without ``parts``, the
    parent's: the one span around all of it."""
    s = lambda t: int(t * 1e9)
    step, other = {"op": "train_step"}, {"op": "traced"}
    out = [("cachedop.first_call", s(10), s(16), "step.dispatch", step),
           ("cachedop.first_call", s(17), s(18), None, other),
           ("cachedop.first_call", s(T_OPEN + 1), s(T_OPEN + 3), None, step)]
    if parts:
        out += [("cachedop.lower", s(10), s(13), "cachedop.first_call", step),
                ("cachedop.compile", s(13), s(15.5), "cachedop.first_call",
                 step),
                ("cachedop.lower", s(17), s(17.5), "cachedop.first_call",
                 other),
                ("cachedop.lower", s(T_OPEN + 1), s(T_OPEN + 2),
                 "cachedop.first_call", step),
                ("cachedop.compile", s(T_OPEN + 2), s(T_OPEN + 3),
                 "cachedop.first_call", step)]
    return [Span(name, a, b, None, 1, parent, None, attrs)
            for name, a, b, parent, attrs in out]


@pytest.fixture
def run(monkeypatch):
    """The reader's context of a traced run of a program that kept the
    step's executable."""
    summary, text = recorded()
    profiler.reset_spans()
    profiler.program("train_step", "train|float32[1,64]", Executable(text))
    monkeypatch.setattr(spans, "record", lambda: (
        first_calls(), {"cachedop.compile": {"compile.cache_hits": 1,
                                             "compile.cache_misses": 0,
                                             "compile.count": 1}}))
    yield {"trace": summary, "window": {"t_open": T_OPEN}}
    profiler.reset_spans()


def read_all(run):
    return {name: harness.load_reader(name)(run) for name in READS}


def with_unmatched(summary, share):
    """The summary with one more operation in every run, of an instruction
    that the table lacks, ``share`` of the run's device time."""
    device = summary.devices[0]
    extra = STEP_US * 1e3 * share / (1 - share)
    device.ops = sorted(
        device.ops + [("fusion.999 fusion kLoop f32[8]", hi - 40_000, extra)
                      for _, hi in device.steps()], key=lambda e: e[1])
    return summary


def test_the_table_holds_every_listed_operation_and_no_fused_one():
    _, text = recorded()
    table = profiler._parse_ops(text)
    assert table["while.10"] == ("bwd", None, False, "while")
    assert table["fusion.11"] == ("bwd", "moe.experts", True, "fusion")
    assert table["compare.1"][3] == "compare"       # the loop's condition
    assert table["add.1"][:2] == ("fwd", "loss")    # a reduction's region
    # the compiler's copies: the phase of the named instruction that runs
    # next, and after the last one none
    assert table["copy-done.21"] == ("bwd", None, False, "copy-done")
    assert table["copy.20"] == (None, None, False, "copy")
    assert table["p.0"][0] == "fwd" and table["p.2"][0] == "bwd"
    assert not any(name.startswith(("multiply.", "param_0."))
                   for name in table)
    # the listed ones, the entry's and the body's parameter, the condition's
    # two, the region's three
    assert len(table) == 21 + 2 + 2 + 3


@pytest.mark.parametrize("metric", list(READS))
def test_the_reader_gives(run, metric):
    assert harness.load_reader(metric)(run) == pytest.approx(READS[metric])


def test_the_phases_add_up_to_the_step_and_the_container_is_left_out(
        run, capsys):
    found = scopes.step_scopes(run)
    assert len(found.runs) == 4 and found.matched == 1.0
    total = found.ms(lambda p, s, r: True)
    assert total * 1e3 == pytest.approx(STEP_US)      # not 1,145: no while.10
    by_phase = [found.ms(lambda p, s, r, q=q: p == q)
                for q in ("fwd", "bwd", "opt", "metric", None)]
    assert sum(by_phase) == pytest.approx(total)
    line = [l for l in capsys.readouterr().err.splitlines()
            if l.startswith("step scopes: ")]
    assert len(line) == 1
    said = json.loads(line[0][len("step scopes: "):])
    assert said["phase_ms"] == {"fwd": 0.4, "bwd": 0.49, "opt": 0.045,
                                "metric": 0.005, "None": 0.005}
    assert said["scope_ms_fwd_bwd"]["moe.experts"] == [0.08, 0.19]
    assert said["scope_ms_fwd_bwd"][scopes.UNNAMED] == [0.04, 0.07]
    assert said["copy_ms"] == {"bwd": 0.01, "None": 0.005}
    assert said["largest_unnamed_ms"][0][0].startswith("fusion.17 ")
    scopes.step_scopes(run)                     # made once a run
    assert "step scopes" not in capsys.readouterr().err


@pytest.mark.parametrize("share,silent", [(0.01, False), (0.03, True)])
def test_unmatched_device_time_silences_the_scope_readers(run, share,
                                                          silent):
    with_unmatched(run["trace"], share)
    read = read_all(run)
    for name in READS:
        if name.startswith("setup_"):           # they read no trace
            assert read[name] == READS[name]
        elif silent:
            assert read[name] is None, name
        else:
            assert read[name] == pytest.approx(READS[name]), name


def test_a_program_without_the_table_or_the_spans_is_silent(run,
                                                            monkeypatch):
    """The parent of PR 37 with this PR's benchmark laid over it."""
    monkeypatch.delattr(profiler, "program_ops")
    monkeypatch.setattr(spans, "record",
                        lambda: (first_calls(parts=False), {}))
    assert read_all(run) == dict.fromkeys(READS)


@pytest.mark.parametrize("why", ["no trace", "no table kept",
                                 "an executable that gives no text"])
def test_the_scope_readers_are_silent_with(run, why):
    if why == "no trace":
        run["trace"] = None
    else:
        profiler.reset_spans()
        if why != "no table kept":
            profiler.program("train_step", "sig", object())
    read = read_all(run)
    assert [name for name, value in read.items() if value is not None] \
        == ["setup_step_lower_s.train", "setup_step_compile_s.train"]


def test_every_new_metric_is_declared_for_the_cells_it_reads():
    spec = harness.load_json(ROOT, "BENCHMARK.json")
    cells = [w["name"] for w in spec["workloads"]]
    declared = {m["name"]: m for m in spec["per_layer"]}
    for name in READS:
        entry = declared[name]
        decoder_only = name in ("remat_device_ms.train", "moe_layer_ms.train",
                                "moe_move_ms.train",
                                "scope_unnamed_share.train")
        assert entry["workloads"] == (cells[2:] if decoder_only else cells)
        assert entry["moves"] == ("setup_s" if name.startswith("setup_")
                                  else "train_images_per_s")
