"""Profiler: the MXNet surface, and the one recorder of the program's own spans.

Reference: src/profiler/ + python/mxnet/profiler.py — engine-integrated op
profiling into chrome://tracing JSON (profiler.h:85-477, DumpProfile), aggregate
per-op stats table (aggregate_stats.cc), user Domain/Task/Counter/Marker objects
(profiler.py:198-283), env autostart MXNET_PROFILER_AUTOSTART.

TPU-native: wraps ``jax.profiler`` (XPlane/TensorBoard traces capture every XLA
op on-device — richer than the reference's per-engine-op events) and keeps the
reference's python surface: set_config/set_state/dump/dumps + Domain/Task/
Counter/Marker.  That surface records only while a session runs
(``set_state("run")``).

Under it sits the recorder that the training hot path uses all the time
(``fit()``, ``DeviceFeed``, ``CompiledTrainStep``, ``CachedOp``):

* ``with span(name, seq=None, cpu=False, **attrs):`` records one
  ``Span(name, start_ns, end_ns, cpu_ns, thread, parent, seq, attrs)``:
  ``time.perf_counter_ns()`` at both ends, the enclosing span's name on that
  thread, and the step or batch number ``seq`` that it or an enclosing span
  was given.  With ``cpu=True`` it also reads the thread's CPU clock at both
  ends (``time.thread_time_ns()``: wall less CPU is what the thread spent
  blocked); that is a system call of 6 us on the chip's host, so only the
  spans whose CPU time something reads ask for it (``fit.step``, the feed's
  stages), and ``cpu_ns`` is None in the others.  The record goes to a
  bounded ring (the newest ``RING_SIZE`` spans of the process) and to
  running totals per name; ``count(name, delta)`` adds to the same totals.
* The span also enters ``jax.profiler.TraceAnnotation("mx:" + name)``, so in
  any ``jax.profiler`` trace it lies as ``mx:<name>`` on the host thread's
  line beside the device's operations, on the trace's clock.
* A ``jax.monitoring`` listener charges every backend compile and every
  persistent-cache hit or miss to the innermost span open on the thread it
  happens on: ``totals()[name]["compile.count"]`` says which step compiled.
* ``program(name, signature, compiled)`` keeps the executable of a
  signature's first call (``CachedOp`` hands it over), and
  ``program_ops(name)`` reads from its optimized HLO, when asked, which
  ``fwd`` / ``bwd`` / ``opt`` / ``metric`` phase and which scope each of the
  program's instructions belongs to: a device trace names an operation by
  its instruction, so this table is what turns a trace's operations into the
  program's own parts (see "the programs" below).

How to read them: ``totals()`` in a live process (it survives the feed that
``fit()`` drops at each epoch's end), ``spans()`` for the newest records, the
``mx:`` spans in XProf or Perfetto, and there the step's device operations
under ``jit_train_step`` with ``fwd`` / ``bwd`` / ``opt`` / ``metric`` and the
symbol's node names in their ``op_name`` (module/compiled_step.py).  While an
MXNet session runs, every span is also a B/E pair of ``dump()`` and a row of
``dumps()``.  The recording path takes no lock: the ring is a ``deque``, the
totals are per thread and merged on reading.
"""
from __future__ import annotations

import collections
import os
import re
import time
import json
import threading

import jax

__all__ = ["set_config", "set_state", "state", "dump", "dumps", "merge_dumps",
           "pause", "resume", "memory_summary",
           "Domain", "Task", "Frame", "Event", "Counter", "Marker",
           "span", "count", "totals", "spans", "reset_spans", "Span",
           "RING_SIZE", "gauge", "program", "programs", "program_ops",
           "scope_of", "PHASES", "PROGRAMS_KEPT"]

_config = {"profile_all": False, "profile_symbolic": True, "profile_imperative": True,
           "profile_memory": False, "profile_api": False,
           "filename": "profile.json", "aggregate_stats": False}
_state = {"running": False, "trace_dir": None}
_events = []
_lock = threading.Lock()
_agg = collections.defaultdict(lambda: [0, 0.0])  # name -> [count, total_ms]

# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------

Span = collections.namedtuple(
    "Span", "name start_ns end_ns cpu_ns thread parent seq attrs")

# fit() records about 16 spans per step on two threads: some 4,000 steps
RING_SIZE = 1 << 16
_ring = collections.deque(maxlen=RING_SIZE)
_clock_ns = time.perf_counter_ns
_cpu_ns = time.thread_time_ns
_annotate = jax.profiler.TraceAnnotation
# dump()'s events are microseconds since the epoch, taken from the monotonic
# clock through one offset read at import: they cannot step with the wall clock
_EPOCH_NS = time.time_ns() - _clock_ns()
_NO_SPAN = "(no span)"
_PEAK = 3        # a totals record is [count, wall ns, CPU ns, max]
# guarded by _lock: [thread, totals, compiles] of every thread that recorded,
# and the sums of the threads that have ended
_tables = []
_retired = ({}, {})


def _fold(into, table, peak=None):
    """Add ``table``'s records (name -> list of sums) to ``into``'s; the
    entry at index ``peak`` is a maximum, not a sum."""
    for name, rec in list(table.items()):
        have = into.get(name)
        if have is None:
            into[name] = list(rec)
            continue
        for i, v in enumerate(rec):
            have[i] = max(have[i], v) if i == peak else have[i] + v


class _ThreadTables(threading.local):
    """What one thread records into without a lock: its stack of open
    spans, its current ``seq``, its totals (name -> [count, wall ns, CPU ns,
    max]) and compile charges (span name -> [count, ns, hits, misses])."""

    def __init__(self):
        self.stack = []
        self.seq = None
        self.tid = threading.get_ident()
        self.totals = {}
        self.compiles = {}
        with _lock:
            # a thread that has ended writes no more: its sums move to
            # _retired, so the list is as long as the live threads are many
            for entry in [e for e in _tables if not e[0].is_alive()]:
                _fold(_retired[0], entry[1], peak=_PEAK)
                _fold(_retired[1], entry[2])
                _tables.remove(entry)
            _tables.append([threading.current_thread(), self.totals,
                            self.compiles])


_tls = _ThreadTables()


def _now_us():
    return (_EPOCH_NS + _clock_ns()) / 1e3


class span:
    """Context manager: one recorded span (see the module docstring).
    ``seq`` numbers this span and, while it is open, the spans inside it;
    ``cpu`` asks for the thread's CPU time as well; ``cat`` is the category
    of its B/E pair in ``dump()``; the other keywords are its attributes, in
    the record and on the trace annotation.  Once closed it has its wall
    time as ``wall_ns``."""

    __slots__ = ("name", "attrs", "wall_ns", "_seq", "_cpu", "_cat",
                 "_annotation", "_t0", "_c0", "_parent", "_outer_seq",
                 "_stack")

    def __init__(self, name, seq=None, cpu=False, cat="span", **attrs):
        self.name = name
        self.attrs = attrs
        self._seq = seq
        self._cpu = cpu
        self._cat = cat

    def __enter__(self):
        tls = _tls
        stack = self._stack = tls.stack
        self._parent = stack[-1].name if stack else None
        stack.append(self)
        if self._seq is not None:
            self._outer_seq = tls.seq
            tls.seq = self._seq
        self._annotation = _annotate("mx:" + self.name, **self.attrs)
        self._annotation.__enter__()
        if self._cpu:
            self._c0 = _cpu_ns()
        self._t0 = t0 = _clock_ns()
        if _state["running"]:
            # B now and not with E: a span still open when the session stops
            # keeps its begin in dump()
            _record(self.name, self._cat, "B", (_EPOCH_NS + t0) / 1e3,
                    self.attrs)
        return self

    def __exit__(self, exc_type=None, exc=None, tb=None):
        t1 = _clock_ns()
        cpu = _cpu_ns() - self._c0 if self._cpu else None
        self._annotation.__exit__(exc_type, exc, tb)
        tls = _tls
        stack = self._stack          # the opening thread's, whoever closes
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:          # a Task stopped out of order
            stack.remove(self)
        seq = tls.seq
        if self._seq is not None:
            tls.seq = self._outer_seq
        name, t0 = self.name, self._t0
        self.wall_ns = wall = t1 - t0
        _ring.append((name, t0, t1, cpu, tls.tid, self._parent, seq,  # mxlint: disable=CON102
                      self.attrs or None))   # deque.append is atomic, and bounded
        rec = tls.totals.get(name)
        if rec is None:
            tls.totals[name] = [1, wall, cpu or 0, wall]
        else:
            rec[0] += 1
            rec[1] += wall
            if cpu:
                rec[2] += cpu
            if wall > rec[3]:
                rec[3] = wall
        if _state["running"]:
            _record(name, self._cat, "E", (_EPOCH_NS + t1) / 1e3, self.attrs)
            with _lock:
                a = _agg[name]
                a[0] += 1
                a[1] += wall / 1e6
        return False


def count(name, delta=1):
    """Add ``delta`` to the counter ``name`` in the totals: its ``count`` is
    the sum of the deltas, its ``max`` the largest of them."""
    totals_ = _tls.totals
    rec = totals_.get(name)
    if rec is None:
        totals_[name] = [delta, 0, 0, delta]
    else:
        rec[0] += delta
        if delta > rec[3]:
            rec[3] = delta


_gauges = {}


def gauge(name, read):
    """Register ``read() -> (count, max)`` under ``name``: a value that lives
    elsewhere (state on the device, written by the compiled step) and is
    fetched only when ``totals()`` is asked, never on the timed path.  A
    later registration under the same name replaces the earlier one; a
    ``read`` that raises leaves its name out."""
    with _lock:
        _gauges[name] = read


def totals():
    """``{name: {"count", "wall_ns", "cpu_ns", "max"}}`` of every span,
    counter and gauge since the process started (or ``reset_spans()``), over all
    threads; ``max`` is a span's longest wall time in ns, and ``cpu_ns`` is
    0 for a name whose spans read no CPU clock.  A span name under
    which something compiled also has ``compile.count``, ``compile.ns``,
    ``compile.cache_hits`` and ``compile.cache_misses``.  Nothing is
    cleared; a span that ends during the call may be counted in part."""
    merged, compiled = {}, {}
    with _lock:
        _fold(merged, _retired[0], peak=_PEAK)
        _fold(compiled, _retired[1])
        for _, thread_totals, thread_compiles in _tables:
            _fold(merged, thread_totals, peak=_PEAK)
            _fold(compiled, thread_compiles)
    out = {name: {"count": r[0], "wall_ns": r[1], "cpu_ns": r[2],
                  "max": r[3]} for name, r in merged.items()}
    for name, r in compiled.items():
        out.setdefault(name, {"count": 0, "wall_ns": 0, "cpu_ns": 0,
                              "max": 0}).update(
            {"compile.count": r[0], "compile.ns": r[1],
             "compile.cache_hits": r[2], "compile.cache_misses": r[3]})
    with _lock:
        gauges = list(_gauges.items())
    for name, read in gauges:
        try:
            count_, max_ = read()
        except Exception:       # its state is gone: nothing to report
            continue
        out[name] = {"count": count_, "wall_ns": 0, "cpu_ns": 0, "max": max_}
    return out


def spans(since_ns=None):
    """The ring's records as ``Span`` tuples, oldest first: all of them, or
    those that ended at or after ``since_ns`` (``time.perf_counter_ns()``)."""
    records = list(_ring)
    return [Span(*r) for r in records
            if since_ns is None or r[2] >= since_ns]


def reset_spans():
    """Forget every span, total, gauge, compile charge and kept program (for
    tests)."""
    with _lock:
        _ring.clear()
        _gauges.clear()
        _programs.clear()
        for table in _retired:
            table.clear()
        for _, thread_totals, thread_compiles in _tables:
            thread_totals.clear()
            thread_compiles.clear()


# ---------------------------------------------------------------------------
# the programs: whose device time an instruction is
# ---------------------------------------------------------------------------
# The names are the program's own, so the rule that reads them lives here.
# A compiled step is traced under ``jax.named_scope``s, which XLA keeps as the
# ``op_name`` of every instruction of the optimized program (a fusion carries
# its root's): ``jit(train_step)/bwd/transpose(jvp(fwd))/jvp()/checkpoint/
# rematted_computation/moe.experts/dot_general``.  On that path
#
# * the phase is the first component that is one of ``PHASES``: the step opens
#   ``fwd``, ``bwd``, ``opt`` and ``metric`` (module/compiled_step.py), and a
#   backward operation also names, wrapped, the forward one it transposes;
# * the scope is the innermost component, ``jvp(...)`` and ``transpose(...)``
#   unwrapped, that is a scope's name: lower-case words joined by dots,
#   ``<layer>.<part>`` (``moe.experts``, ``attn.block_mask``, ``dsa.index``,
#   ``conv.gated``, ``mlp.dense``, ``attn.proj``, ``lm.head``), or one of the
#   step's own ``SCOPE_WORDS`` (``loss``).  A symbol's node names and a
#   block's prefixes carry no dot and are no scope;
# * ``rematted_computation`` says the operation recomputes, in the backward
#   pass, a forward value that ``hybridize(remat=True)`` did not keep;
# * an instruction that the compiler made and that has no ``op_name`` (a copy
#   between memory spaces with its start and its done, 6% of a ResNet-50
#   step's device time) belongs to no scope and is counted with the phase
#   in which the schedule runs it: the text lists a computation's
#   instructions in the order they run, and the phase is that of the next
#   one that has a name, which is the one that waits for the copy.

PHASES = ("fwd", "bwd", "opt", "metric")
SCOPE_WORDS = ("loss",)
PROGRAMS_KEPT = 8
_REMAT = "rematted_computation"
_WRAPPED = re.compile(r"(?:jvp|transpose)\((.*)\)\Z")
_SCOPE = re.compile(r"[a-z][a-z0-9_]*(?:\.[a-z][a-z0-9_]*)+\Z")
_HLO_COMPUTATION = re.compile(r"(?:ENTRY )?%?([\w.\-]+) (?:\(.*)?\{\Z")
_HLO_INSTRUCTION = re.compile(
    r"\s+(?:ROOT )?%?([\w.\-]+) = (?:.*? )??([a-z][a-z\-]*)\(")
_HLO_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
# guarded by _lock: (name, signature) -> [compiled, table or None], oldest
# first
_programs = collections.OrderedDict()


def scope_of(op_name):
    """``(phase, scope, recomputed)`` of an instruction's ``op_name`` path,
    by the rule above; ``(None, None, False)`` for a path that names none."""
    phase = scope = None
    parts = op_name.split("/")
    for part in parts:
        if phase is None and part in PHASES:
            phase = part
        inner = _WRAPPED.match(part)
        while inner:
            part = inner.group(1)
            inner = _WRAPPED.match(part)
        if part in SCOPE_WORDS or _SCOPE.match(part):
            scope = part
    return phase, scope, _REMAT in parts


def program(name, signature, compiled):
    """Keep ``compiled``, the ``jax.stages.Compiled`` of the first call of
    ``signature`` of the program ``name``: one dictionary insert, nothing is
    read from it until ``program_ops`` asks.  It holds the executable and the
    arguments' shapes, not the function, the block or a buffer, so it
    outlives the step that made it; the newest ``PROGRAMS_KEPT`` of the
    process are kept."""
    with _lock:
        _programs.pop((name, signature), None)
        _programs[(name, signature)] = [compiled, None]
        while len(_programs) > PROGRAMS_KEPT:
            _programs.popitem(last=False)


def programs():
    """``{(name, signature): compiled}`` of the kept programs, oldest first."""
    with _lock:
        return {key: entry[0] for key, entry in _programs.items()}


def _hlo_text(compiled):
    """The optimized program's text without what a reader of names does not
    need: a custom call's backend configuration (a Pallas kernel's serialized
    body, most of a 100 MB executable's text), large constants, shapes."""
    from jax._src.lib import xla_client
    options = xla_client._xla.HloPrintOptions.short_parsable()
    options.print_metadata = True
    options.print_backend_config = False
    options.print_large_constants = False
    options.print_operand_shape = False
    options.print_result_shape = False
    module, = compiled.runtime_executable().hlo_modules()
    return module.to_string(options)


def _parse_ops(text):
    """``{instruction: (phase, scope, recomputed, opcode)}`` of HLO text, for
    every instruction of every computation that no ``fusion`` calls: the
    device lists the operations of the entry, of a loop's body and condition
    and of a branch under their own names, and a fusion as one.  An
    instruction without ``op_name`` takes its phase from the schedule (see
    the rule above)."""
    computations, fused, current = {}, set(), None
    for line in text.split("\n"):
        if current is None:
            opened = _HLO_COMPUTATION.match(line)
            if opened:
                current = computations.setdefault(opened.group(1), {})
                made = set()         # by the compiler: no op_name
            continue
        if line.startswith("}"):
            phase = None             # of the named instruction that runs next
            for name in reversed(current):
                if name in made:
                    current[name] = (phase,) + current[name][1:]
                else:
                    phase = current[name][0]
            current = None
            continue
        found = _HLO_INSTRUCTION.match(line)
        if not found:
            continue
        name, opcode = found.groups()
        if opcode == "fusion":
            called = _HLO_CALLS.search(line)
            if called:
                fused.add(called.group(1))
        op_name = _HLO_OP_NAME.search(line)
        if op_name is None:
            made.add(name)
        current[name] = scope_of(op_name.group(1) if op_name else "") \
            + (opcode,)
    table = {}
    for computation, rows in computations.items():
        if computation not in fused:
            table.update(rows)
    return table


def program_ops(name):
    """``{instruction name: (phase, scope, recomputed, opcode)}`` of the
    newest kept program called ``name``, or None where none is kept or its
    executable gives no text.  Built from the executable's optimized HLO at
    the first asking and kept; nothing is parsed before."""
    with _lock:
        entry = next((e for (n, _), e in reversed(_programs.items())
                      if n == name), None)
    if entry is None:
        return None
    if entry[1] is None:
        try:
            entry[1] = _parse_ops(_hlo_text(entry[0]))
        except Exception:       # an executable that cannot print itself
            return None
    return entry[1]


def _event(name, cat, ph, ts_us, args):
    return {"name": name, "cat": cat, "ph": ph, "ts": ts_us,
            "pid": os.getpid(), "tid": threading.get_ident(),
            "args": dict(args or {})}


_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": 2,
                 "/jax/compilation_cache/cache_misses": 3}


def _compile_record():
    tls = _tls
    name = tls.stack[-1].name if tls.stack else _NO_SPAN
    rec = tls.compiles.get(name)
    if rec is None:
        rec = tls.compiles[name] = [0, 0, 0, 0]
    return rec


def _on_compile_duration(event, duration, **_):
    if event == _BACKEND_COMPILE:
        rec = _compile_record()
        rec[0] += 1
        rec[1] += int(duration * 1e9)


def _on_compile_event(event, **_):
    slot = _CACHE_EVENTS.get(event)
    if slot is not None:
        _compile_record()[slot] += 1


jax.monitoring.register_event_duration_secs_listener(_on_compile_duration)
jax.monitoring.register_event_listener(_on_compile_event)


# ---------------------------------------------------------------------------
# the MXNet surface
# ---------------------------------------------------------------------------

def set_config(**kwargs):
    with _lock:
        _config.update(kwargs)


def set_state(state_="stop", profile_process="worker"):
    return _set_state(state_, fresh=True)


def _set_state(state_, fresh):
    if state_ == "run":
        with _lock:
            if _state["running"]:
                return       # atomic check-and-claim: one starter wins
            _state["running"] = True
            if fresh:
                # each session is a fresh trace: without this, a long-lived
                # process that profiles periodically re-emits every prior
                # session's spans on dump() and grows the buffer unboundedly.
                # resume() passes fresh=False so a pause/resume cycle keeps
                # the pre-pause spans.  The per-op aggregate table resets
                # with the trace — otherwise dumps() mixes op stats across
                # sessions unless the caller remembered dumps(reset=True).
                _events.clear()
                _agg.clear()
            trace_dir = os.path.splitext(_config["filename"])[0] + "_xplane"
        # the jax call runs unlocked (it can block on backend init); the
        # claim above excludes a second start_trace, but a concurrent
        # stop() may land in this window — detected and honored below
        try:
            # spans and device operations, not every Python call: with the
            # Python tracer on, 20 s of training took 170 s to write out
            # (PERF.md, PR 24)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        except Exception:
            trace_dir = None
        with _lock:
            if _state["running"]:
                _state["trace_dir"] = trace_dir
                trace_dir = None
        if trace_dir is not None:
            # a stop() interleaved before our trace existed and could not
            # stop it; honor the stop rather than leak an active trace
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
    elif state_ == "stop":
        with _lock:
            if not _state["running"]:
                return
            _state["running"] = False
            trace_dir = _state["trace_dir"]
            _state["trace_dir"] = None
        if trace_dir is not None:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass


def state():
    return "run" if _state["running"] else "stop"


def pause(profile_process="worker"):
    _set_state("stop", fresh=False)


def resume(profile_process="worker"):
    _set_state("run", fresh=False)


def profiling_imperative():
    """True when imperative op dispatch should be recorded — the gate the
    dispatch hot path checks (ProfileOperator's `IsProfiling` analog)."""
    return _state["running"] and _config.get("profile_imperative", True)


def profiling_active():
    """True while a profiling session is running.

    High-rate counter writers (the serving queue-depth / batch-latency
    gauges update on every request) must gate on this: Counter.set_value
    appends a trace event unconditionally, so an ungated per-request update
    in a long-lived server grows the event buffer without bound between
    dumps."""
    return _state["running"]


def _record(name, cat, ph, ts=None, args=None):
    """One event of ``dump()``; nothing while no session runs, so a Counter
    in a long-lived process cannot grow the buffer."""
    if not _state["running"]:
        return
    with _lock:
        _events.append(_event(name, cat, ph,
                              ts if ts is not None else _now_us(), args))


def dump(finished=True, profile_process="worker"):
    """Write accumulated host events as chrome://tracing JSON; device-side
    XPlane traces (if any) are in <filename>_xplane for TensorBoard.
    ``finished=True`` (the reference default) also retires the event
    buffer, so a later session starts clean."""
    with _lock:
        payload = {"traceEvents": list(_events)}
        if finished:
            _events.clear()
    with open(_config["filename"], "w") as f:
        json.dump(payload, f)


def dumps(reset=False):
    """Return the aggregate per-op stats table (aggregate_stats.cc analog)."""
    lines = ["%-40s %10s %14s %14s" % ("Name", "Calls", "Total(ms)", "Avg(ms)")]
    with _lock:
        for name, (cnt, total) in sorted(_agg.items(), key=lambda kv: -kv[1][1]):
            lines.append("%-40s %10d %14.3f %14.3f"
                         % (name, cnt, total, total / max(cnt, 1)))
        if reset:
            _agg.clear()
    return "\n".join(lines)


def memory_summary(device=None):
    """Live-allocation table: one row per (dtype, shape) bucket of the
    arrays currently alive on ``device`` (all devices if None), sorted by
    resident bytes — the storage-profiler analog (reference
    src/profiler/storage_profiler.h tags every Storage::Alloc with the
    requesting scope; here XLA owns allocation, so the observable unit is
    the live ``jax.Array`` population).

    Returns the formatted table; the last line totals bytes and count.
    Device-side internals (XLA scratch, donated aliasing) are invisible by
    design — for whole-HBM accounting use TensorBoard's memory_viewer on
    an XPlane trace from ``set_state('run')``/``dump()``."""
    buckets = collections.defaultdict(lambda: [0, 0])   # (dtype, shape) -> [count, bytes]
    total = n = 0
    for arr in jax.live_arrays():
        try:
            devs = getattr(arr, "devices", lambda: set())()
        except Exception:
            devs = set()
        if device is not None and devs and device not in devs:
            continue
        nbytes = arr.size * arr.dtype.itemsize
        key = (str(arr.dtype), tuple(arr.shape))
        buckets[key][0] += 1
        buckets[key][1] += nbytes
        total += nbytes
        n += 1
    lines = ["%-12s %-28s %8s %14s" % ("Dtype", "Shape", "Count", "Bytes")]
    for (dt, shp), (cnt, b) in sorted(buckets.items(),
                                      key=lambda kv: -kv[1][1]):
        lines.append("%-12s %-28s %8d %14d" % (dt, str(shp), cnt, b))
    lines.append("%-12s %-28s %8d %14d" % ("TOTAL", "", n, total))
    return "\n".join(lines)


def merge_dumps(filenames, out=None):
    """Aggregate per-op stats across several workers' trace dumps
    (the distributed analog of ``dumps()``; reference server-side profiling,
    include/mxnet/kvstore.h:49 SetServerProfilerCommand +
    tests/nightly/test_server_profiling.py).

    ``filenames``: per-rank chrome-trace JSON files written by ``dump()``.
    ``out``: optional path for the combined trace (events from all ranks in
    one timeline; pids distinguish the workers).  Returns the merged table.
    """
    events = []
    for fn in filenames:
        with open(fn) as f:
            events.extend(json.load(f).get("traceEvents", []))
    if out is not None:
        with open(out, "w") as f:
            json.dump({"traceEvents": events}, f)
    # pair B/E spans per (worker pid, thread, name) to recover durations
    open_spans = collections.defaultdict(list)
    agg = collections.defaultdict(lambda: [0, 0.0])
    for ev in sorted(events, key=lambda e: e.get("ts", 0)):
        name = ev.get("name")
        if name is None or ev.get("ph") not in ("B", "E"):
            # external tools emit name-less metadata ('M') events; skip
            # anything that isn't a named duration span
            continue
        key = (ev.get("pid"), ev.get("tid"), name)
        if ev.get("ph") == "B":
            open_spans[key].append(ev["ts"])
        elif open_spans[key]:
            begin = open_spans[key].pop()
            entry = agg[name]
            entry[0] += 1
            entry[1] += (ev["ts"] - begin) / 1e3
    lines = ["%-40s %10s %14s %14s" % ("Name", "Calls", "Total(ms)",
                                       "Avg(ms)")]
    for name, (cnt, total) in sorted(agg.items(), key=lambda kv: -kv[1][1]):
        lines.append("%-40s %10d %14.3f %14.3f"
                     % (name, cnt, total, total / max(cnt, 1)))
    return "\n".join(lines)


class Domain:
    def __init__(self, name):
        self.name = name

    def __str__(self):
        return self.name

    def new_task(self, name):
        return Task(self, name)

    def new_counter(self, name, value=None):
        return Counter(self, name, value)

    def new_marker(self, name):
        return Marker(self, name)


class _Span:
    """A named ``span`` of the recorder that is started and stopped by hand;
    its domain is the category of its B/E pair."""

    def __init__(self, domain, name):
        self.domain = domain
        self.name = name
        self._open = None

    def start(self):
        self._open = span(self.name, cat=str(self.domain))
        self._open.__enter__()
        return self

    def stop(self):
        if self._open is not None:
            self._open.__exit__()
            self._open = None
        return self

    def __enter__(self):
        return self.start()

    def __exit__(self, *a):
        self.stop()


class Task(_Span):
    pass


class Frame(_Span):
    pass


class Event(_Span):
    def __init__(self, name):
        super().__init__(Domain("event"), name)


class Counter:
    def __init__(self, domain, name, value=None):
        self.domain = domain
        self.name = name
        self._value = 0
        if value is not None:
            self.set_value(value)

    def set_value(self, value):
        self._value = value
        _record(self.name, str(self.domain), "C", args={"value": value})

    def increment(self, delta=1):
        self.set_value(self._value + delta)

    def decrement(self, delta=1):
        self.set_value(self._value - delta)

    def __iadd__(self, v):
        self.increment(v)
        return self

    def __isub__(self, v):
        self.decrement(v)
        return self


class Marker:
    def __init__(self, domain, name):
        self.domain = domain
        self.name = name

    def mark(self, scope="process"):
        _record(self.name, str(self.domain), "i", args={"s": scope[0]})


# env autostart (reference: MXNET_PROFILER_AUTOSTART, docs/faq/env_var.md:152
# — begin profiling at import so short scripts profile without code changes;
# registered in env.py).  jax.profiler.start_trace is deferred to the first
# set_state call's path, so a missing backend cannot break import.
if os.environ.get("MXNET_PROFILER_AUTOSTART", "0").lower() in ("1", "true"):
    try:
        set_state("run")
    except Exception:
        pass
