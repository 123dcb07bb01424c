"""What every cell's run has in common: finding the cell's files by the names
in BENCHMARK.json, the chip check, the compile cache, the compile meter, the
trace capture, the per-layer readers and the result line.

A cell's window is driven by its entry (``entries/<entry>.py``), named in the
cell's traffic file; the entry also reports the cell's end-to-end values and
how many operations the window attempted and how many failed.  Whether its
output is correct is decided by the comparison the traffic file names
(``comparisons/<compare>.py``, default ``train_norms``) against the plain
reference of the configuration's family.
"""
from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))


class BenchmarkError(Exception):
    """The run cannot give a result (no chip, eager fallback, bad files)."""


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Cell:
    """One entry of BENCHMARK.json's ``workloads`` with the files it names."""

    def __init__(self, name, root, spec=None, base=HERE):
        self.spec = spec or load_json(root, "BENCHMARK.json")
        found = [w for w in self.spec["workloads"] if w["name"] == name]
        if not found:
            raise BenchmarkError("no workload %r in BENCHMARK.json" % name)
        self.name = name
        self.entry = found[0]
        self.chips = int(self.entry["chips"])
        config = [c for c in self.spec["configs"]
                  if c["name"] == self.entry["config"]][0]
        self.config = load_json(root, config["file"])
        self.traffic = load_json(base, "traffic",
                                 self.entry["traffic"] + ".json")
        self.limits = load_json(base, "limits", name + ".json")["limits"]

    def metric_names(self, group, reported=None):
        """The metrics of ``group`` that this cell reports: those without a
        ``workloads`` key whose end-to-end metric the cell reports, and
        those that list the cell."""
        if reported is None:
            reported = set(self.metric_names("end_to_end", ()))
        names = []
        for m in self.spec[group]:
            if "workloads" in m:
                if self.name in m["workloads"]:
                    names.append(m["name"])
            elif group == "end_to_end" or m["moves"] in reported:
                names.append(m["name"])
        return names

    def unit(self, metric):
        for group in ("end_to_end", "per_layer"):
            for m in self.spec[group]:
                if m["name"] == metric:
                    return m["unit"]
        raise KeyError(metric)


class CompileMeter:
    """Sums XLA compile time and persistent-cache traffic from JAX's own
    monitoring events, on any thread (a copy of chip_smoke.py's)."""

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.compiles = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += duration
                self.compiles += 1

    def _event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def read(self):
        with self._lock:
            return {"compile_s": self.seconds, "compiles": self.compiles,
                    "cache_hits": self.hits, "cache_misses": self.misses}


def configure_jax(root):
    """The persistent compilation cache at a fixed path inside the checkout
    (or where JAX_COMPILATION_CACHE_DIR says), holding every program however
    quickly it compiled, so that a warm run compiles nothing."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


def find_chips(jax, chips):
    """The cell's devices, or BenchmarkError: never the CPU."""
    devices = jax.local_devices()
    if not devices or devices[0].platform == "cpu":
        raise BenchmarkError("JAX found no accelerator (platform %r)"
                             % (devices[0].platform if devices else None))
    if len(devices) < chips:
        raise BenchmarkError("the cell asks for %d chips, JAX found %d"
                             % (chips, len(devices)))
    return devices[:chips]


def peaks_of(device):
    table = load_json(HERE, "peaks.json")
    if device.device_kind not in table:
        raise BenchmarkError("no peaks for device kind %r in peaks.json"
                             % device.device_kind)
    return table[device.device_kind]


def load_reader(metric):
    path = os.path.join(HERE, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def memory_peak(devices):
    """Peak bytes on the fullest of the cell's chips.  The TPU allocator's
    ``peak_bytes_in_use`` leaves out what the runtime reserves for a
    program's temporaries; that is ``peak_bytes_reserved`` (8.34 GB where
    the step's ``memory_analysis()`` has 8.38 GB of temporaries: PERF.md, PR
    24), and the footprint is the two together."""
    stats = [d.memory_stats() or {} for d in devices]
    return max(s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
               for s in stats)


def run_cell(cell, seed, seconds, trace, t_start, devices, out=sys.stderr):
    """Drive one run of ``cell`` on ``devices`` and return the result
    object.  ``devices`` come from find_chips in a real run; a check under
    checks/ hands the CPU's."""
    import jax
    from benchmark import trace as trace_mod

    meter = CompileMeter()
    entry = importlib.import_module("benchmark.entries."
                                    + cell.traffic["entry"])
    comparison = importlib.import_module(
        "benchmark.comparisons." + cell.traffic.get("compare", "train_norms"))
    trace_dir = os.path.join(os.environ.get("TMPDIR") or
                             os.path.join(HERE, os.pardir, ".bench_tmp"),
                             "bench_trace_%d" % os.getpid()) if trace else None
    run = entry.Run(cell, seed, seconds, devices, meter, t_start, trace_dir)
    window = run.drive()                     # set-up, then the timed window
    end_to_end, names = window.pop("end_to_end"), \
        cell.metric_names("end_to_end")
    if sorted(end_to_end) != sorted(names):
        raise BenchmarkError(
            "entry %s reports the end-to-end metrics %s, the cell has %s"
            % (cell.traffic["entry"], sorted(end_to_end), sorted(names)))
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(memory_peak(devices))}
    program = run.readings()
    run.free()
    gc.collect()

    t_ref = time.perf_counter()
    numbers, observed = comparison.compare(cell, seed, program, run)
    window["reference_s"] = time.perf_counter() - t_ref
    correct = all(n["ok"] for n in numbers.values())

    context = {"cell": cell, "window": window, "end_to_end": end_to_end,
               "peaks": peaks_of(devices[0]) if devices[0].platform != "cpu"
               else None, "device": device, "trace": None}
    result = {"correct": bool(correct), "attempted": int(window["attempted"]),
              "failed": int(window["failed"])}
    if trace:
        t_read = time.perf_counter()
        summary = trace_mod.load(trace_dir)
        window["trace_read_s"] = time.perf_counter() - t_read
        context["trace"] = summary
        if summary is not None:
            device["busy_s"] = summary.busy_s()
            device["window_s"] = summary.window_s
            result["breakdown"] = summary.breakdown()
            print("device seconds by kind of operation: %s"
                  % json.dumps(summary.by_class()), file=out)
        trace_mod.discard(trace_dir)
        metrics = {}
        for name in cell.metric_names("per_layer"):
            value = load_reader(name)(context)
            if value is not None:
                metrics[name] = {"value": float(value),
                                 "unit": cell.unit(name)}
    else:
        metrics = {name: {"value": float(end_to_end[name]),
                          "unit": cell.unit(name)} for name in names}
    result["metrics"] = metrics
    result["device"] = device
    result["window"] = {k: v for k, v in window.items()
                        if isinstance(v, (int, float))}
    result["observed"] = observed
    result["compared"] = {k: {"value": n["value"], "limit": n["limit"]}
                          for k, n in numbers.items()}
    for name, n in numbers.items():
        print("compared %s value %.6g limit %.6g %s"
              % (name, n["value"], n["limit"], "ok" if n["ok"] else "FAILED"),
              file=out)
    return result


def main(workload, seed, seconds, trace, t_start, root):
    try:
        cell = Cell(workload, root)
        jax = configure_jax(root)
        devices = find_chips(jax, cell.chips)
        result = run_cell(cell, seed, seconds, trace, t_start, devices)
    except BenchmarkError as exc:
        print("benchmark: %s" % exc, file=sys.stderr)
        return 3
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
