"""The readings that the limits in ``limits/<cell>.json`` are set from.

    python3 benchmark/checks/readings.py <cell> <first seed> <seeds> [<control seeds>]

On the chip, at the cell's own size, in one process: for each seed the
program's set-up steps (the very entry and compiled step a run's window then
drives) against the float32 ``highest`` reference: the lower readings.  For
the first ``control seeds`` seeds also, each put in the program's place: the
control (the reference in bfloat16: weights, momenta and activations), the
same with float32 master weights, the half-batch fault (the reference fed
half of each batch, the mean taken over the rest) and a witness (the
reference in float32 at XLA's default precision, which is what the
configuration states).  A state left unchanged reads 1 by construction.  One
JSON line per seed, also appended to ``chiprun_out/readings_<cell>.jsonl``;
every leaf's norms go to ``chiprun_out/leaves_<cell>.jsonl``.
"""
import time

T_START = time.perf_counter()

import gc          # noqa: E402
import importlib   # noqa: E402
import json        # noqa: E402
import os          # noqa: E402
import sys         # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv):
    sys.path.insert(0, ROOT)
    from benchmark import harness
    from benchmark.comparisons import train_norms as compare
    import jax.numpy as jnp

    name, first, count = argv[0], int(argv[1]), int(argv[2])
    controls = int(argv[3]) if len(argv) > 3 else 3
    cell = harness.Cell(name, ROOT)
    jax = harness.configure_jax(ROOT)
    devices = harness.find_chips(jax, cell.chips)
    meter = harness.CompileMeter()
    entry = importlib.import_module("benchmark.entries."
                                    + cell.traffic["entry"])
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    half = slice(0, cell.traffic["batch"] // 2)
    for i in range(count):
        seed = first + i * 1000003
        t0 = time.perf_counter()
        run = entry.Run(cell, seed, 0.0, devices, meter, T_START, None)
        run.drive(window=False)
        program, batches = run.readings(), run.first_batches()
        run.free()
        gc.collect()
        reference = compare.reference_readings(cell, seed, batches)

        def both(readings):
            held, observed = compare.numbers(readings, reference)
            return {**held, **observed}

        line = {"cell": name, "seed": seed,
                "program": both(program),
                "losses": program["losses"]}
        full = {"seed": seed, "program": program, "reference": reference}
        if i < controls:
            variants = {
                "control_bf16": dict(dtype=jnp.bfloat16, precision=None,
                                     state_dtype=jnp.bfloat16),
                "control_bf16_master_f32": dict(dtype=jnp.bfloat16,
                                                precision=None),
                "fault_half_batch": dict(keep=half),
                "witness_default_precision": dict(precision=None),
            }
            for key, variant in variants.items():
                full[key] = compare.reference_readings(cell, seed, batches,
                                                       **variant)
                line[key] = both(full[key])
        with open(os.path.join(out_dir, "leaves_%s.jsonl" % name), "a") as f:
            f.write(json.dumps(full) + "\n")
        line["seconds"] = time.perf_counter() - t0
        text = json.dumps(line)
        print(text, flush=True)
        with open(os.path.join(out_dir, "readings_%s.jsonl" % name), "a") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
