#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process that finds the cell's chips (none or too few: exit non-zero,
no CPU fallback), builds the model on the device from the seed, warms up the
cell's own shapes, measures for ``--seconds`` and prints one JSON object as
the last line of standard output.  See README.md beside this file.
"""
import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import os        # noqa: E402
import sys       # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "mxnet_tpu")):
        print("benchmark: no program beside the benchmark (mxnet_tpu/ is "
              "missing under %s)" % ROOT, file=sys.stderr)
        return 4
    sys.path.insert(0, ROOT)
    from benchmark import harness
    return harness.main(args.workload, args.seed, args.seconds,
                        bool(args.trace), T_START, ROOT)


if __name__ == "__main__":
    sys.exit(main())
