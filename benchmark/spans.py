"""The program's own spans on the trace's clock: the one reader of
``mxnet_tpu.profiler``'s recorder (PR 25).

The harness deletes the ``.xplane.pb`` before the per-layer readers run, and
``trace.load`` keeps neither the program's ``mx:`` annotations nor an
operation's scope.  What a reader can still see is the ``Summary``, the
``window`` dict and, because it runs in the program's process, the recorder
itself: ``profiler.spans()`` (``time.perf_counter_ns()``) and
``profiler.totals()``.  The trace's clock is not the host's, but
``summary.hi_ns`` is the start of ``bench:window_close``, emitted on the line
after ``t_close = time.perf_counter()``: that pair is the anchor, and
``program_spans`` checks it in every run against the ``bench:callback`` spans,
each of which ``fit()`` wraps in a ``fit.callback`` span.

Between-program idle is the complement, in the window, of the union of the
first chip's programs (XLA modules).  Each stretch of it is attributed to the
spans of the thread that runs ``fit()`` that cover it.

A program without the recorder (the parent of PR 25) gives ``None`` from every
function here, and the result line leaves the metric out.
"""
from __future__ import annotations

import bisect
import collections
import sys

from benchmark.trace import gaps

ALIGN_NS = 50_000          # a bench:callback may stick out of fit.callback so far
CALLBACK = "bench:callback"
PROLOGUE = ("fit.bind", "fit.init_params", "fit.init_optimizer",
            "fit.build_step")
FEED_STAGES = ("feed.source", "feed.transform", "feed.h2d", "feed.put_wait")
STEP_PROGRAM = "train_step"
_CACHED = "program_spans"

# one span of the recorder, its times on the trace's clock and not clipped
Mapped = collections.namedtuple(
    "Mapped", "name start end cpu_ns thread parent attrs")


def record():
    """``(spans, totals)`` of the program's recorder, or None where the
    program has none."""
    try:
        from mxnet_tpu import profiler
    except ImportError:
        return None
    if not hasattr(profiler, "spans") or not hasattr(profiler, "totals"):
        return None
    return profiler.spans(), profiler.totals()


class ProgramSpans:
    """The recorder's spans as ``Mapped`` tuples on the trace's clock;
    ``aligned`` says whether the mapping passed its check, and the functions
    that need it return None if not."""

    def __init__(self, spans, totals, summary, window):
        self.totals = totals
        self.summary = summary
        self.offset = summary.hi_ns - window["t_close"] * 1e9
        self.t_open = window["t_open"] * 1e9 + self.offset
        self.spans = [Mapped(s.name, s.start_ns + self.offset,
                             s.end_ns + self.offset, s.cpu_ns, s.thread,
                             s.parent, s.attrs) for s in spans]
        threads = {s.thread for s in self.spans if s.name == "fit.step"}
        self.caller = sorted((s for s in self.spans if s.thread in threads),
                             key=lambda s: (s.start, -s.end))
        self._idle = None
        self.miss_ns = self._miss_ns()
        self.aligned = self.miss_ns is not None and self.miss_ns <= ALIGN_NS

    def _miss_ns(self):
        """By how much the worst ``bench:callback`` span sticks out of the
        ``fit.callback`` span that fits it best; None with none to check."""
        ours = [s for s in self.caller if s.name == "fit.callback"]
        theirs = [(s, s + d) for n, s, d in self.summary.spans
                  if n == CALLBACK]
        if not ours or not theirs:
            return None
        return max(min(max(o.start - s, e - o.end) for o in ours)
                   for s, e in theirs)

    def in_window(self, span):
        return span.start >= self.summary.lo_ns and \
            span.end <= self.summary.hi_ns

    def inside(self, names):
        """The spans of ``names`` that lie wholly in the window."""
        return [s for s in self.spans
                if s.name in names and self.in_window(s)]

    def periods(self):
        """Whole steps in the window, as dispatch_gap_ms.train counts them."""
        return len(self.summary.devices[0].steps()) - 1

    def idle(self):
        """``[(ns, chain)]`` for every stretch of between-program idle on
        the first chip, cut where a span of the caller's thread starts or
        ends; ``chain`` is the spans that cover it, outermost first."""
        if self._idle is not None:
            return self._idle
        lo, hi = self.summary.lo_ns, self.summary.hi_ns
        programs = [(s, s + d) for _, s, d in self.summary.devices[0].modules]
        out = []
        for a, b in gaps(programs, lo, hi):
            over = [s for s in self.caller if s.start < b and s.end > a]
            cuts = sorted({a, b} | {t for s in over for t in (s.start, s.end)
                                    if a < t < b})
            for p, q in zip(cuts, cuts[1:]):
                mid = (p + q) / 2
                out.append((q - p, [s for s in over
                                    if s.start <= mid < s.end]))
        self._idle = out
        return out

    def idle_under(self, name):
        return sum(ns for ns, chain in self.idle()
                   if any(s.name == name for s in chain))

    def idle_unattributed(self):
        """Idle that no child of ``fit.step`` covers."""
        return sum(ns for ns, chain in self.idle()
                   if not any(s.parent == "fit.step" for s in chain))

    def caller_wall(self):
        """``(steps, cpu_ns, {name: ns})``: over the ``fit.step`` spans that
        lie wholly in the window, the CPU time of the caller's thread in them
        and the wall time it spent in each span itself, its children's taken
        out.  A step's wall less its CPU is what the thread spent blocked,
        and a span whose own wall time exceeds the step's CPU is where."""
        steps = [s for s in self.caller
                 if s.name == "fit.step" and self.in_window(s)]
        starts = [s.start for s in steps]
        out = {}
        for s in self.caller:
            at = bisect.bisect_right(starts, s.start) - 1
            if at < 0 or s.end > steps[at].end:
                continue                        # in no whole step
            wall = s.end - s.start
            out[s.name] = out.get(s.name, 0) + wall
            if s.name != "fit.step":
                out[s.parent] = out.get(s.parent, 0) - wall
        return len(steps), sum(s.cpu_ns for s in steps), out

    def describe(self):
        """One line for the run's log: idle by innermost span per whole
        step, the caller's wall time per ``fit.step`` by span against the
        step's CPU time, and the feed thread's stages per batch (wall/CPU)."""
        periods = max(1, self.periods())
        idle = {}
        for ns, chain in self.idle():
            key = chain[-1].name if chain else "(none)"
            idle[key] = idle.get(key, 0) + ns
        steps, cpu_ns, wall = self.caller_wall()
        stages = self.inside(FEED_STAGES)
        batches = max(1, sum(s.name == "feed.put_wait" for s in stages))
        feed = {name: "%.3f/%.3f" % (
            sum(s.end - s.start for s in stages if s.name == name)
            / batches / 1e6,
            sum(s.cpu_ns for s in stages if s.name == name) / batches / 1e6)
            for name in FEED_STAGES if any(s.name == name for s in stages)}
        per = lambda table, n, unit: {
            k: round(v / n / unit, 3)
            for k, v in sorted(table.items(), key=lambda kv: -kv[1])}
        return ("program spans: clock check missed by %.1f us; idle us per "
                "step by innermost span %s; caller CPU %.3f ms per step, its "
                "own wall ms per step by span %s; feed wall/cpu ms per batch "
                "%s" % (self.miss_ns / 1e3, per(idle, periods, 1e3),
                        cpu_ns / max(1, steps) / 1e6,
                        per(wall, max(1, steps), 1e6), feed))


def program_spans(run):
    """The ``ProgramSpans`` of a traced run of an entry that ran ``fit()``,
    made once per run and kept in ``run``; else None."""
    if _CACHED in run:
        return run[_CACHED]
    run[_CACHED] = found = None
    # a traced run whose window gives the anchor's host side
    recorded = record() if run.get("trace") is not None \
        and "t_close" in run.get("window", ()) else None
    if recorded is not None and "fit.step" in recorded[1]:
        found = ProgramSpans(recorded[0], recorded[1], run["trace"],
                             run["window"])
        if found.miss_ns is None:
            print("program spans: no %s span in the trace to check the "
                  "clock mapping against" % CALLBACK, file=sys.stderr)
        else:
            print(found.describe(), file=sys.stderr)
            if not found.aligned:
                print("program spans: the clock mapping missed by %.1f us "
                      "(limit %.0f us): no metric is read from it"
                      % (found.miss_ns / 1e3, ALIGN_NS / 1e3),
                      file=sys.stderr)
        run[_CACHED] = found
    return found


def _aligned(run):
    found = program_spans(run)
    return found if found is not None and found.aligned \
        and found.periods() >= 1 else None


# -- the readers: one function per metric under metrics/ --------------------

def host_cpu_ms(run):
    found = _aligned(run)
    steps = found.inside(("fit.step",)) if found else []
    if not steps:
        return None
    return sum(s.cpu_ns for s in steps) / len(steps) / 1e6


def feed_cpu_ms(run):
    found = _aligned(run)
    stages = found.inside(FEED_STAGES) if found else []
    batches = sum(s.name == "feed.put_wait" for s in stages)
    if not batches:
        return None
    return sum(s.cpu_ns for s in stages) / batches / 1e6


def feed_starved_ms(run):
    found = _aligned(run)
    if found is None:
        return None
    return found.idle_under("feed.wait") / found.periods() / 1e6


def dispatch_exposed_ms(run):
    found = _aligned(run)
    if found is None:
        return None
    return found.idle_under("step.dispatch") / found.periods() / 1e6


def idle_unattributed_share(run):
    found = _aligned(run)
    total = sum(ns for ns, _ in found.idle()) if found else 0
    if not total:
        return None
    return 100.0 * found.idle_unattributed() / total


def setup_prologue_s(run):
    found = program_spans(run)
    if found is None or not all(n in found.totals for n in PROLOGUE):
        return None
    return sum(found.totals[n]["wall_ns"] for n in PROLOGUE) / 1e9


def setup_first_call_s(run):
    found = program_spans(run)
    first = [s for s in found.spans
             if s.name == "cachedop.first_call" and s.attrs
             and s.attrs.get("op") == STEP_PROGRAM
             and s.end <= found.t_open] if found else []
    if not first:
        return None
    return sum(s.end - s.start for s in first) / 1e9
