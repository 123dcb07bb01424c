"""The readings that a cell of family ``mellum_moe`` has its limits set from.

    python3 benchmark/checks/readings_mellum.py <cell> <first seed> <seeds> [<control seeds> [layers] [<variant> ...]]

As readings_lfm2.py, on the chip, at the cell's own size, in one process:
for each seed the program against the float32 ``highest`` reference, by the
numbers of ``comparisons/mellum_layers.py``.  For the first ``control seeds``
seeds also, each put in the program's place: the control (the reference in
bfloat16: weights, moments and activations), a witness (the reference in
float32 at XLA's default precision) and the faults of faults_mellum.py.  A
state left unchanged reads 1 by construction.  With ``layers`` the compiled
step is left out and only the blocks' numbers are read; ``variant``s
(``control_bf16``, ``witness_default_precision``, ``fault_<name>``) name the
ones to read in the program's place, where not all are wanted.  Beside them
what the program's expert blocks recorded of the newest step (each layer's
pairs routed here and largest load) and the peak of device memory.  One JSON
line per seed, also appended to ``chiprun_out/readings_<cell>.jsonl``.
"""
import time

T_START = time.perf_counter()

import contextlib  # noqa: E402
import gc          # noqa: E402
import importlib   # noqa: E402
import json        # noqa: E402
import os          # noqa: E402
import sys         # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv):
    sys.path.insert(0, ROOT)
    from benchmark import harness, traffic
    from benchmark.checks import faults_mellum
    from benchmark.comparisons import mellum_layers as compare
    from benchmark.comparisons import train_norms
    import jax.numpy as jnp

    name, first, count = argv[0], int(argv[1]), int(argv[2])
    controls = int(argv[3]) if len(argv) > 3 else 3
    layers_only = "layers" in argv[4:]
    wanted = [a for a in argv[4:] if a != "layers"]
    cell = harness.Cell(name, ROOT)
    jax = harness.configure_jax(ROOT)
    devices = harness.find_chips(jax, cell.chips)
    meter = harness.CompileMeter()
    entry = importlib.import_module("benchmark.entries."
                                    + cell.traffic["entry"])
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    for i in range(count):
        seed = first + i * 1000003
        t0 = time.perf_counter()
        line = {"cell": name, "seed": seed}
        if layers_only:
            batches = traffic.make_pool(cell.config, cell.traffic, seed, 1)
        else:
            run = entry.Run(cell, seed, 0.0, devices, meter, T_START, None)
            run.drive(window=False)
            program, batches = run.readings(), run.first_batches()
            from mxnet_tpu import profiler
            line["gauges"] = {k: [v["count"], v["max"]] for k, v in
                              profiler.totals().items()
                              if k.startswith("moe.load.")}
            run.free()
            gc.collect()
        inputs = compare.probe_inputs(cell, seed, batches[0][0])
        ops_of = lambda variant: {k: v for k, v in variant.items()  # noqa: E731
                                  if k in ("dtype", "precision")}

        def read(fault=None, **variant):
            """The reference, as it is or changed, with its own picks, as
            the comparison reads the program."""
            with faults_mellum.planted(fault) if fault \
                    else contextlib.nullcontext():
                if layers_only:
                    return {"layers": compare.reference_probe(
                        cell, *inputs, **ops_of(variant))}
                return compare.reference_readings(cell, seed, batches, inputs,
                                                  **variant)

        steps = {} if layers_only else train_norms.reference_readings(
            cell, seed, batches)

        def both(readings):
            """``readings`` in the program's place against the sound
            reference given its picks."""
            reference = dict(steps, layers=compare.reference_probe(
                cell, *inputs, given=readings["layers"]["picks"]))
            if layers_only:
                found = compare.layer_numbers(
                    readings["layers"], reference["layers"],
                    cell.config["num_experts"])
                return {k: list(v) for k, v in found.items()}
            held, observed = compare.numbers(readings, reference, cell)
            return {k: v[0] for k, v in {**held, **observed}.items()}

        layers = compare.program_probe(cell, *inputs)
        line["program"] = both({"layers": layers} if layers_only
                               else dict(program, layers=layers))
        line["memory_peak_bytes"] = harness.memory_peak(devices)
        if i < controls:
            variants = {
                "control_bf16": dict(dtype=jnp.bfloat16, precision=None,
                                     state_dtype=jnp.bfloat16),
                "witness_default_precision": dict(precision=None)}
            variants.update(("fault_" + fault, dict(fault=fault))
                            for fault in faults_mellum.FAULTS
                            if not (layers_only and fault == "half_rows"))
            for variant, how in variants.items():
                if not wanted or variant in wanted:
                    line[variant] = both(read(**how))
        line["seconds"] = time.perf_counter() - t0
        text = json.dumps(line)
        print(text, flush=True)
        with open(os.path.join(out_dir, "readings_%s.jsonl" % name), "a") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
