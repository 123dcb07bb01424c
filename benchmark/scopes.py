"""Whose device time it is: the one reader of ``mxnet_tpu.profiler``'s table
from the step program's instructions to the program's own phases and scopes
(PR 37), and of the two spans that split a first call.

``trace.load`` keeps each device operation's instruction name (``compact()``'s
first token, ``fusion.1426``) and drops its ``op_name``; the xplane's device
events carry none anyway.  The program does: it keeps the executable of the
step's first call, and ``profiler.program_ops("train_step")`` reads from that
executable's optimized HLO, when asked, ``{instruction: (phase, scope,
recomputed, opcode)}``.  A reader runs in the program's process, so the join
needs no trace file and no second compile: for every whole run of the step
program on the first chip (``Device.steps()``), each operation's duration goes
to its instruction's phase and scope; ``while``, ``conditional`` and ``call``
are left out, since the device lists their bodies' operations beside them; a
number is the median over the runs (the trace starts inside a run, which then
lacks its first operations).

Every function gives None, and the result line leaves the metric out, where
the program has no ``program_ops`` (the parent of PR 37), where it keeps no
table of ``train_step``, where the run has no trace, or where less than
``MATCHED_SHARE`` of a step's device time finds its instruction in the table:
a table of another program must not pass for this one's.

The names are as fresh as the executable: one loaded from the persistent
cache carries the ``op_name``s of the tree that compiled it (jax keys that
cache without metadata), so two trees whose steps differ in scope names alone
must not share a cache directory.

The set-up readers need no trace: ``window["t_open"]`` and the recorder's
``perf_counter_ns`` are one clock.
"""
from __future__ import annotations

import json
import statistics
import sys
import time

from benchmark import spans

STEP_PROGRAM = spans.STEP_PROGRAM
CONTAINERS = ("while", "conditional", "call")
MOVES = ("copy", "async-")      # mostly the compiler's own: memory to memory
MATCHED_SHARE = 0.98
PHASES = ("fwd", "bwd", "opt", "metric")
MOE_LAYER = ("moe.route", "moe.sort", "moe.experts", "moe.combine")
MOE_MOVE = ("moe.sort", "moe.combine")
UNNAMED, UNMATCHED = "(no scope)", "(no instruction)"
TOP_UNNAMED = 8
FIRST_CALL = ("cachedop.first_call", "cachedop.lower", "cachedop.compile")
_CACHED, _SETUP = "step_scopes", "step_setup_spans"


def program_table():
    """``(table, seconds it took to get, what is kept)`` from the program's
    recorder, or None where the program has none to give."""
    try:
        from mxnet_tpu import profiler
        t0 = time.perf_counter()
        table = profiler.program_ops(STEP_PROGRAM)
        seconds = time.perf_counter() - t0
    except Exception:           # no recorder, or one without the registry
        return None
    if not table:
        return None
    return table, seconds, _kept_bytes(profiler, table)


def _kept_bytes(profiler, table):
    """Host bytes of what the program keeps for the table: the table itself,
    and of the newest kept executable of the step its optimized HLO
    (serialized) and its generated code."""
    out = {"rows": len(table),
           "table_bytes": sys.getsizeof(table) + sum(
               sys.getsizeof(k) + sys.getsizeof(v) for k, v in table.items())}
    try:
        compiled = [c for (name, _), c in profiler.programs().items()
                    if name == STEP_PROGRAM][-1]
        executable = compiled.runtime_executable()
        module, = executable.hlo_modules()
        out["hlo_bytes"] = len(module.as_serialized_hlo_module_proto())
        out["code_bytes"] = executable.size_of_generated_code_in_bytes()
    except Exception:
        pass
    return out


class StepScopes:
    """The join of one device's operations with the table: for every run of
    the step program ``{(phase, scope, recomputed): ns}`` with the unmatched
    operations under ``UNMATCHED`` (and, for the log, the copies' ns by
    phase), and the medians over the runs."""

    def __init__(self, device, table):
        ops = sorted((start, name, dur) for name, start, dur in device.ops)
        self.runs, self.copies, self.unnamed_ops = [], [], {}
        i = 0
        for lo, hi in device.steps():
            while i < len(ops) and ops[i][0] < lo:
                i += 1
            sums, copied = {}, {}
            while i < len(ops) and ops[i][0] < hi:
                _, name, dur = ops[i]
                i += 1
                parts = name.split(" ")
                row = table.get(parts[0])
                opcode = row[3] if row else "".join(parts[1:2])
                if opcode in CONTAINERS:
                    continue
                key = row[:3] if row else UNMATCHED
                sums[key] = sums.get(key, 0) + dur
                if row and opcode.startswith(MOVES):
                    copied[row[0]] = copied.get(row[0], 0) + dur
                if row and row[1] is None and row[0] in ("fwd", "bwd"):
                    self.unnamed_ops[name] = \
                        self.unnamed_ops.get(name, 0) + dur
            if sums:
                self.runs.append(sums)
                self.copies.append(copied)
        shares = [1 - sums.get(UNMATCHED, 0) / sum(sums.values())
                  for sums in self.runs]
        self.matched = statistics.median(shares) if shares else 0.0

    def ms(self, keep):
        """Median over the runs of the device ms of the rows ``keep(phase,
        scope, recomputed)`` keeps."""
        return statistics.median(
            sum(ns for key, ns in sums.items()
                if key != UNMATCHED and keep(*key))
            for sums in self.runs) / 1e6

    def describe(self, seconds, kept):
        """The run's table as JSON: ms a step by phase, by scope in each
        pass, recomputed, and the largest operations that carry no scope."""
        keys = {k for sums in self.runs for k in sums if k != UNMATCHED}
        phase_ms = {str(phase): round(self.ms(
            lambda p, s, r, phase=phase: p == phase), 3)
            for phase in PHASES + (None,) if any(k[0] == phase for k in keys)}
        by_scope = {}
        for scope in {k[1] for k in keys if k[0] in ("fwd", "bwd")}:
            by_scope[scope or UNNAMED] = [round(self.ms(
                lambda p, s, r, phase=phase: p == phase and s == scope), 3)
                for phase in ("fwd", "bwd")]
        top = sorted(self.unnamed_ops.items(), key=lambda kv: -kv[1])
        return json.dumps({
            "runs": len(self.runs),
            "matched_share": round(self.matched, 6),
            "step_ms": round(self.ms(lambda p, s, r: True), 3),
            "phase_ms": phase_ms,
            "recomputed_ms": round(self.ms(lambda p, s, r: r), 3),
            "copy_ms": {str(phase): round(statistics.median(
                copied.get(phase, 0) for copied in self.copies) / 1e6, 3)
                for phase in {p for copied in self.copies for p in copied}},
            "scope_ms_fwd_bwd": dict(sorted(
                by_scope.items(), key=lambda kv: -sum(kv[1]))),
            "largest_unnamed_ms": [
                [name, round(ns / len(self.runs) / 1e6, 3)]
                for name, ns in top[:TOP_UNNAMED]],
            "table_build_s": round(seconds, 3), "kept": kept})


def step_scopes(run):
    """The ``StepScopes`` of a traced run, made once per run and kept in
    ``run``; None where the table or the trace is missing or the two do not
    belong together."""
    if _CACHED in run:
        return run[_CACHED]
    run[_CACHED] = found = None
    trace = run.get("trace")
    got = program_table() if trace is not None and trace.devices else None
    if got is not None:
        table, seconds, kept = got
        joined = StepScopes(trace.devices[0], table)
        if joined.runs:
            print("step scopes: %s" % joined.describe(seconds, kept),
                  file=sys.stderr)
            if joined.matched >= MATCHED_SHARE:
                found = joined
            else:
                print("step scopes: only %.2f%% of a step's device time "
                      "found its instruction in the program's table (limit "
                      "%.0f%%): no metric is read from it"
                      % (100 * joined.matched, 100 * MATCHED_SHARE),
                      file=sys.stderr)
    run[_CACHED] = found
    return found


# -- the readers: one function per metric under metrics/ --------------------

def phase_ms(run, phase):
    found = step_scopes(run)
    if found is None:
        return None
    return found.ms(lambda p, s, r: p == phase)


def remat_ms(run):
    """Device ms a step of the recomputed operations; silent where nothing
    is recomputed."""
    found = step_scopes(run)
    return (found.ms(lambda p, s, r: r) or None) if found else None


def scopes_ms(run, scopes):
    """Device ms a step of the named scopes, both passes; silent where the
    step has none of them."""
    found = step_scopes(run)
    return (found.ms(lambda p, s, r: s in scopes) or None) if found else None


def unnamed_share(run):
    """The share (%) of the step's ``fwd`` + ``bwd`` device time whose
    instructions carry no scope."""
    found = step_scopes(run)
    if found is None:
        return None
    passes = found.ms(lambda p, s, r: p in ("fwd", "bwd"))
    if not passes:
        return None
    return 100.0 * found.ms(
        lambda p, s, r: p in ("fwd", "bwd") and s is None) / passes


def setup_spans(run):
    """``{span name: wall seconds}`` of the step program's first call and its
    parts, over the spans that ended before the window opened; made once per
    run, with one line for the run's log."""
    if _SETUP in run:
        return run[_SETUP]
    run[_SETUP] = found = {}
    recorded = spans.record()
    t_open = run.get("window", {}).get("t_open")
    if recorded is None or t_open is None:
        return found
    for s in recorded[0]:
        if s.name in FIRST_CALL and s.attrs \
                and s.attrs.get("op") == STEP_PROGRAM \
                and s.end_ns <= t_open * 1e9:
            found[s.name] = found.get(s.name, 0) \
                + (s.end_ns - s.start_ns) / 1e9
    charged = recorded[1].get("cachedop.compile", {})
    if found:
        print("step set-up: %s; charged to cachedop.compile: persistent-"
              "cache hits %s (a load), misses %s (a compile), backend "
              "compile events %s"
              % (json.dumps({k: round(v, 3) for k, v in found.items()}),
                 charged.get("compile.cache_hits"),
                 charged.get("compile.cache_misses"),
                 charged.get("compile.count")), file=sys.stderr)
    return found
