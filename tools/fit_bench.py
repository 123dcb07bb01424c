#!/usr/bin/env python
"""fit_bench — compiled vs eager ``fit()`` end-to-end (BENCH_MODE=fused_fit).

PR 4's pipeline bench measured a hand-built fused step; this one measures
what users actually call: ``Module.fit``.  Same module, same synthetic
data, same optimizer, two runs:

* ``eager``    — ``fit(compiled=False)``: forward / backward / per-param
  update dispatched separately, metric fetch (host sync) every batch — the
  historical loop;
* ``compiled`` — ``fit()`` default: the whole iteration as ONE CachedOp via
  CompiledTrainStep, metrics accumulating on-device, host fetch only at
  epoch end.

Both runs train ``1 + timed_epochs`` epochs; the first epoch absorbs
compilation (and is also when the compiled path's single signature is
built), and the timed window is the steady-state remainder.  Reported:
img/s for both paths, ``speedup_vs_eager``, and the compiled path's
**recompile delta across the timed epochs** (must be 0 — the zero
steady-state-recompile contract of docs/PERF.md).

Writes ``BENCH_FUSED_FIT.json`` and prints the record as one JSON line
(the bench.py watchdog contract).  ``--smoke`` shrinks everything for the
tier-1 wiring in tests/test_compiled_fit.py.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np


def _make_symbol(channels, classes):
    from mxnet_tpu import sym
    data = sym.Variable("data")
    net = sym.Convolution(data, kernel=(3, 3), pad=(1, 1),
                          num_filter=channels, name="conv1")
    net = sym.Activation(net, act_type="relu")
    net = sym.Convolution(net, kernel=(3, 3), pad=(1, 1),
                          num_filter=channels * 2, name="conv2")
    net = sym.Activation(net, act_type="relu")
    net = sym.Pooling(net, global_pool=True, pool_type="avg", kernel=(1, 1))
    net = sym.Flatten(net)
    net = sym.FullyConnected(net, num_hidden=classes, name="fc")
    return sym.SoftmaxOutput(net, name="softmax")


def _run_fit(compiled, data, labels, batch, channels, classes, epochs,
             steps_per_call=1):
    """One fit() run; -> (imgs_per_sec over epochs >= 1, cache delta info)."""
    import mxnet_tpu as mx
    from mxnet_tpu import io

    mx.random.seed(42)
    it = io.NDArrayIter(data, labels, batch_size=batch, shuffle=False)
    mod = mx.mod.Module(_make_symbol(channels, classes))
    marks = []
    stats = []

    def mark(*_args):
        marks.append(time.perf_counter())
        cstep = getattr(mod, "_compiled_step", None)
        stats.append(cstep.cache_stats()["recompiles"] if cstep else None)

    mod.fit(it, num_epoch=epochs, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            eval_metric="acc", initializer=mx.init.Xavier(),
            epoch_end_callback=mark, compiled=compiled,
            steps_per_call=steps_per_call)
    n_batches = len(data) // batch
    timed_epochs = epochs - 1
    wall = marks[-1] - marks[0]   # epoch 0 (compile) excluded
    rate = n_batches * batch * timed_epochs / wall
    recompile_delta = (stats[-1] - stats[0]
                       if stats[0] is not None else None)
    return rate, recompile_delta, mod


def run(smoke=False, out_path=None, emit=True):
    import jax

    devs = jax.devices()
    device_kind = getattr(devs[0], "device_kind", devs[0].platform)

    batch = int(os.environ.get("BENCH_FIT_BATCH", "8" if smoke else "32"))
    img = int(os.environ.get("BENCH_FIT_IMG", "12" if smoke else "24"))
    channels = int(os.environ.get("BENCH_FIT_CHANNELS",
                                  "4" if smoke else "16"))
    n_batches = int(os.environ.get("BENCH_FIT_BATCHES",
                                   "6" if smoke else "20"))
    epochs = 1 + int(os.environ.get("BENCH_FIT_EPOCHS",
                                    "2" if smoke else "3"))
    steps_per_call = int(os.environ.get("BENCH_FIT_STEPS_PER_CALL", "1"))
    classes = 10

    rng = np.random.RandomState(3)
    data = rng.uniform(-1, 1,
                       (batch * n_batches, 3, img, img)).astype(np.float32)
    labels = rng.randint(0, classes, batch * n_batches).astype(np.float32)

    compiled_rate, recompile_delta, mod = _run_fit(
        True, data, labels, batch, channels, classes, epochs,
        steps_per_call=steps_per_call)
    if getattr(mod, "_compiled_step", None) is None:
        raise RuntimeError("compiled fit fell back to the eager loop — "
                           "the fused_fit bench would measure nothing")
    eager_rate, _, _ = _run_fit(
        False, data, labels, batch, channels, classes, epochs)

    record = {
        "metric": "fused_fit_imgs_per_sec_bs%d" % batch,
        "value": round(compiled_rate, 2),
        "unit": "images/sec",
        "vs_baseline": None,
        "mode": "fused_fit",
        "compiled_imgs_per_sec": round(compiled_rate, 2),
        "eager_imgs_per_sec": round(eager_rate, 2),
        "speedup_vs_eager": round(compiled_rate / eager_rate, 4),
        "recompile_delta_timed_epochs": recompile_delta,
        "timed_epochs": epochs - 1,
        "batches_per_epoch": n_batches,
        "steps_per_call": steps_per_call,
        "device": device_kind,
        "config": {"batch": batch, "img": img, "channels": channels,
                   "smoke": bool(smoke)},
        "data": "synthetic pre-staged host arrays (NDArrayIter); measures "
                "the fit() dispatch/sync path, not the input pipeline",
        "sync": "eager: metric asnumpy per batch; compiled: device metric "
                "accumulators fetched at epoch end only",
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
    if emit:
        print(json.dumps(record), flush=True)
    return record


def main(argv=None):
    ap = argparse.ArgumentParser(prog="fit_bench", description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small config for tier-1 (a few seconds)")
    ap.add_argument("--out", default=os.path.join(REPO,
                                                  "BENCH_FUSED_FIT.json"),
                    help="artifact path (default: repo BENCH_FUSED_FIT.json)")
    ap.add_argument("--no-artifact", action="store_true",
                    help="print the JSON line only")
    args = ap.parse_args(argv)
    record = run(smoke=args.smoke,
                 out_path=None if args.no_artifact else args.out)
    # acceptance gates (ISSUE 6): compiled >= 1.3x eager end-to-end on the
    # full config, zero steady-state recompiles; smoke keeps a loose floor
    ok = (record["recompile_delta_timed_epochs"] == 0
          and record["speedup_vs_eager"] >= (1.0 if args.smoke else 1.3))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
