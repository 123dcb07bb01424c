"""Quantify eager per-op dispatch overhead vs the hybridized path.

Reference context: the reference amortizes per-op engine overhead with
bulking (src/engine/threaded_engine.h:411 BulkStatus, docs/faq/env_var.md:
83-92 MXNET_ENGINE_* knobs).  This repo's ``engine.bulk()`` is a no-op (XLA
fusion bulk-compiles any jitted region), and this benchmark is the
justification artifact: it measures a small-op RNN workload — the worst
case SURVEY §7(b) flags — both ways.

Workload: a gluon LSTMCell unrolled T steps over batch B. Eager mode
dispatches each step's ops through the imperative runtime (per-op jit
cache); hybridized mode traces the whole unroll into one cached XLA module
(the bulking analog).

Prints one JSON line with eager/hybrid steps/sec and the per-op dispatch
overhead estimate.

Usage: JAX_PLATFORMS=cpu python tools/eager_overhead.py [--steps 100]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100, help="unroll length")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--iters", type=int, default=5)
    args = ap.parse_args()

    import mxnet_tpu as mx
    from mxnet_tpu import nd, gluon
    from mxnet_tpu.gluon import rnn

    rng = np.random.RandomState(0)
    x = nd.array(rng.uniform(-1, 1, (args.batch, args.steps, args.hidden))
                 .astype(np.float32))
    steps = args.steps

    class Unrolled(gluon.HybridBlock):
        """The whole T-step unroll as one block: hybridized it traces into
        ONE cached XLA module (the engine-bulking analog); eager it
        dispatches every step's ops through the imperative runtime."""

        def __init__(self, hidden, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.cell = rnn.LSTMCell(hidden)

        def hybrid_forward(self, F, seq):
            outs, _ = self.cell.unroll(steps, seq, layout="NTC",
                                       merge_outputs=True)
            return outs

    def bench(hybridize):
        net = Unrolled(args.hidden)
        net.initialize(mx.init.Xavier())
        if hybridize:
            net.hybridize()
        # warmup: the CachedOp traces on the first call and jit-compiles on
        # the second; time only steady-state calls
        net(x).wait_to_read()
        net(x).wait_to_read()
        t0 = time.perf_counter()
        for _ in range(args.iters):
            net(x).wait_to_read()
        dt = time.perf_counter() - t0
        return args.iters * args.steps / dt      # cell-steps per second

    eager_sps = bench(False)
    hybrid_sps = bench(True)
    # an LSTM step is ~10 primitive ops; overhead per op is the per-step
    # time difference spread over them
    ops_per_step = 10
    overhead_us = (1e6 / eager_sps - 1e6 / hybrid_sps) / ops_per_step
    print(json.dumps({
        "metric": "eager_vs_hybrid_lstm_steps_per_sec",
        "eager_steps_per_sec": round(eager_sps, 1),
        "hybrid_steps_per_sec": round(hybrid_sps, 1),
        "hybrid_speedup": round(hybrid_sps / eager_sps, 2),
        "per_op_dispatch_overhead_us": round(overhead_us, 1),
        "config": {"steps": args.steps, "batch": args.batch,
                   "hidden": args.hidden},
    }))


if __name__ == "__main__":
    main()
