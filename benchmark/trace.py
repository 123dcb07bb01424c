"""From the profiler's trace to numbers: the only reader of ``.xplane.pb``.

``load`` turns the trace of one window into a ``Summary``: for each of the
cell's chips the device's operations and its programs (XLA modules) as
``(name, start_ns, duration_ns)`` on the trace's clock, clipped to the window,
and the harness's own host spans (``bench:*`` TraceAnnotations) on the same
clock.  The per-layer readers under ``metrics/`` work on a ``Summary`` alone,
and ``checks/test_trace.py`` holds them to a small recorded one.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
import shutil

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"


def union_ns(intervals):
    """Total length of the union of (start, end) intervals."""
    total, reach = 0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def gaps(intervals, lo, hi):
    """The idle (start, end) stretches of [lo, hi] that no interval covers."""
    out, reach = [], lo
    for start, end in sorted(intervals):
        if start > reach:
            out.append((reach, min(start, hi)))
        reach = max(reach, end)
        if reach >= hi:
            break
    if reach < hi:
        out.append((reach, hi))
    return [(a, b) for a, b in out if b > a]


_INSTRUCTION = re.compile(r"^%?([\w.\-]+) = (.*?) ([a-z][a-z\-]*)\(")
_SHAPE = re.compile(r"[a-z]+\d*\[[\d,]*\]")
_KIND = re.compile(r"kind=(k\w+)")


def compact(hlo):
    """A device operation's name as the summary keeps it.  The TPU profiler
    names an operation by its whole HLO instruction, some hundreds of
    characters; kept are the instruction's name, its opcode, a fusion's kind
    and the shape of its (last) result:
    ``fusion.1392 fusion kOutput f32[128,256,56,56]``."""
    match = _INSTRUCTION.match(hlo)
    if not match:
        return hlo[:80]
    name, result, opcode = match.groups()
    kind = _KIND.search(hlo)
    shapes = _SHAPE.findall(result)
    return " ".join(x for x in (name, opcode, kind.group(1) if kind else "",
                                shapes[-1] if shapes else "") if x)


def conv_class(op):
    """Whether a device operation (by its compact name) is a convolution or
    a fusion around one.  Written after reading one trace of each cell by
    hand (PERF.md, PR 24): the TPU compiler leaves a bare ``convolution``
    or wraps it, with the BatchNorm sums or the bias that follow, in a
    fusion of kind ``kOutput`` (its name for a fusion whose root is a
    convolution or dot; elementwise and reduction fusions are ``kLoop`` and
    ``kInput``).  ResNet-50's step has 162 of them: 53 convolutions and the
    dense layer, forward, input gradient and weight gradient."""
    parts = op.split(" ")
    opcode = parts[1] if len(parts) > 1 else ""
    return opcode == "convolution" or (opcode == "fusion" and
                                       "kOutput" in parts[2:3])


class Device:
    def __init__(self, name, ops, modules):
        self.name, self.ops, self.modules = name, ops, modules

    def step_module(self):
        """The name of the step program: the module with most device time."""
        totals = {}
        for name, _, dur in self.modules:
            totals[name] = totals.get(name, 0) + dur
        return max(totals, key=totals.get) if totals else None

    def steps(self):
        """(start, end) of each run of the step program, in order."""
        which = self.step_module()
        return sorted((s, s + d) for n, s, d in self.modules if n == which)

    def periods(self):
        """(start, next start) of each run of the step program but the
        last: a whole step with whatever small programs the loop puts
        between two runs (stacking the batch, splitting the key)."""
        steps = self.steps()
        return [(a[0], b[0]) for a, b in zip(steps, steps[1:])]

    def busy_in(self, spans):
        """Device-busy nanoseconds (union of op intervals) inside each of
        the sorted, disjoint ``spans``."""
        ops = sorted((s, s + d) for _, s, d in self.ops)
        out, i = [], 0
        for lo, hi in spans:
            while i < len(ops) and ops[i][1] <= lo:
                i += 1
            j, inside = i, []
            while j < len(ops) and ops[j][0] < hi:
                inside.append((max(ops[j][0], lo), min(ops[j][1], hi)))
                j += 1
            out.append(union_ns(inside))
        return out


class Summary:
    def __init__(self, lo_ns, hi_ns, devices, spans):
        self.lo_ns, self.hi_ns = lo_ns, hi_ns
        self.window_s = (hi_ns - lo_ns) / 1e9
        self.devices = devices       # [Device]
        self.spans = spans           # [(name, start_ns, duration_ns)]

    def busy_ns(self, device):
        return union_ns((s, s + d) for _, s, d in device.ops)

    def busy_s(self):
        """Seconds in which an operation ran, averaged over the chips."""
        return sum(self.busy_ns(d) for d in self.devices) / 1e9 \
            / max(1, len(self.devices))

    def idle_gaps(self, device):
        return gaps([(s, s + d) for _, s, d in device.ops],
                    self.lo_ns, self.hi_ns)

    def label(self, start, end):
        """What the host was doing in [start, end]: the harness's span that
        covers most of it, else the fit loop itself."""
        best, cover = "fit loop", 0
        for name, s, d in self.spans:
            overlap = min(end, s + d) - max(start, s)
            if overlap > cover:
                best, cover = name[len(SPAN_PREFIX):], overlap
        return best if cover * 2 >= end - start else "fit loop"

    def breakdown(self):
        device = self.devices[0]
        totals = {}
        for name, _, dur in device.ops:
            totals[name] = totals.get(name, 0) + dur
        top = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
        by_label = {}
        for a, b in self.idle_gaps(device):
            key = self.label(a, b)
            by_label[key] = by_label.get(key, 0) + (b - a)
        idle = sorted(by_label.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, t / 1e9] for n, t in top],
                "idle_gaps": [[n, t / 1e9] for n, t in idle]}

    def by_class(self):
        """Device seconds on the first chip by kind of operation: the
        opcode, and a fusion's kind with it (``fusion kOutput`` are the
        convolutions, see conv_class)."""
        totals = {}
        for name, _, dur in self.devices[0].ops:
            parts = name.split(" ")
            key = " ".join(parts[1:3] if parts[1:2] == ["fusion"]
                           else parts[1:2]) or name
            totals[key] = totals.get(key, 0) + dur
        return {k: t / 1e9 for k, t in
                sorted(totals.items(), key=lambda kv: -kv[1])}

    # -- a recorded trace is this, as JSON -------------------------------
    def to_json(self):
        return {"lo_ns": self.lo_ns, "hi_ns": self.hi_ns,
                "devices": [{"name": d.name, "ops": d.ops,
                             "modules": d.modules} for d in self.devices],
                "spans": self.spans}

    @classmethod
    def from_json(cls, obj):
        devices = [Device(d["name"], [tuple(e) for e in d["ops"]],
                          [tuple(e) for e in d["modules"]])
                   for d in obj["devices"]]
        return cls(obj["lo_ns"], obj["hi_ns"], devices,
                   [tuple(e) for e in obj["spans"]])


def read_recorded(path):
    with gzip.open(path, "rt") as f:
        return Summary.from_json(json.load(f))


def clip(events, lo, hi):
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def start(trace_dir):
    """Start the profiler on ``trace_dir`` as an entry does inside its
    window: the harness's and the program's spans, not every Python call."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def xplane_path(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(trace_dir):
    """The Summary of the trace under ``trace_dir``, or None where the trace
    has no device plane (a CPU rehearsal)."""
    import jax
    path = xplane_path(trace_dir)
    if path is None:
        return None
    data = jax.profiler.ProfileData.from_file(path)
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE) and " " not in plane.name:
            ops, modules, short = [], [], {}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:     # a step's few thousand names
                        name = e.name         # recur in every step
                        if name not in short:
                            short[name] = compact(name)
                        ops.append((short[name], int(e.start_ns),
                                    int(e.duration_ns)))
                elif line.name == MODULES_LINE:
                    modules = [(e.name, int(e.start_ns), int(e.duration_ns))
                               for e in line.events]
            devices.append(Device(plane.name, ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, int(e.start_ns),
                                      int(e.duration_ns)))
    devices = [d for d in devices if d.ops]
    if not devices:
        return None
    devices.sort(key=lambda d: d.name)
    opens = [s for n, s, _ in spans if n == SPAN_PREFIX + "window_open"]
    closes = [s for n, s, _ in spans if n == SPAN_PREFIX + "window_close"]
    lo = opens[0] if opens else min(s for d in devices for _, s, _ in d.ops)
    hi = closes[-1] if closes else max(s + t for d in devices
                                       for _, s, t in d.ops)
    for d in devices:
        d.ops = clip(d.ops, lo, hi)
        d.modules = clip(d.modules, lo, hi)
    spans = clip(sorted(spans, key=lambda e: e[1]), lo, hi)
    return Summary(lo, hi, devices, spans)


def discard(trace_dir):
    shutil.rmtree(trace_dir, ignore_errors=True)
