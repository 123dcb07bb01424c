#!/usr/bin/env python
"""input_bench — end-to-end input-pipeline benchmark (BENCH_MODE=pipeline).

Every prior bench number (BENCH_LIVE.json) times a step that consumes a
pre-staged on-device batch — kernel/step throughput, disclosed as such.
This bench closes the loop the ROADMAP north-star actually cares about:
**end-to-end** img/s when every batch must be decoded, batchified, and
moved to the device, and whether the async feed hides that work behind
compute.

Workload: a synthetic-decode dataset — per-sample host work simulated as a
sleep (the blocking-I/O/libjpeg profile of real decode threads, which
release the GIL) plus a numpy normalize, feeding a small hybridized conv
net whose whole train step runs through one CachedOp.  Three measurements
over identical shapes:

* ``compute``  — the step over one pre-staged batch (the BENCH_LIVE
  discipline: upper bound, no input pipeline at all);
* ``sync``     — the historical synchronous path: decode + batchify inline
  in the consumer loop (``DataLoader`` default path);
* ``e2e``      — the async feed path: ``DataLoader(prefetch_to_device=
  ctx)`` — decode/batchify/h2d on the ``DeviceFeed`` thread, one-to-two
  batches ahead.

Reported: all three rates, **overlap efficiency = e2e / compute** (1.0
means the input pipeline is fully hidden), **speedup = e2e / sync**, the
feed's own stats (h2d time, consumer starvation, peak queue depth), and
the CachedOp recompile delta across the timed region (must be 0 — a
recompiling pipeline benchmark measures XLA, not the feed).

Decode cost defaults to ``BENCH_DECODE_RATIO`` (0.7) of the measured
compute step, i.e. a workload that is decode-heavy enough to punish a
synchronous pipeline (~1.7x) but inside the feed's ability to hide it;
``BENCH_DECODE_MS`` pins an absolute per-batch cost instead.  Timing sync
discipline matches bench.py: steps are data-dependent through the
parameters, and each timed window ends with a host fetch of the last loss.

Writes ``BENCH_PIPELINE.json`` and prints the same record as one JSON
line (the bench.py watchdog contract).  ``--smoke`` shrinks the model and
batch count for the tier-1 wiring in tests/test_input_pipeline.py.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# NO JAX_PLATFORMS setdefault here: this is a bench, not an analysis tool —
# on a TPU host it must measure the TPU exactly like every other BENCH_MODE
# (bench.py's watchdog owns the hang risk; pass JAX_PLATFORMS=cpu manually
# for container runs)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np


class SyntheticDecodeDataset:
    """n samples of (CHW float32 image, int label); __getitem__ costs
    ``decode_ms`` of sleep (GIL-releasing, like real decode threads) plus a
    numpy normalize, so sample loading has somewhere to hide."""

    def __init__(self, n, img, decode_ms_per_sample, classes=10, seed=0):
        self._rng = np.random.RandomState(seed)
        self._raw = self._rng.randint(
            0, 256, (n, 3, img, img)).astype(np.uint8)
        self._labels = self._rng.randint(0, classes, n).astype(np.float32)
        self._decode_s = decode_ms_per_sample / 1e3
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        if self._decode_s > 0:
            time.sleep(self._decode_s)
        img = self._raw[i].astype(np.float32) * (1.0 / 255.0)
        return img, self._labels[i]


def _build_trainer(batch, img, channels, classes=10, lr=None, momentum=None):
    """-> (fused-step CachedOp, step fn): the whole training iteration —
    forward + backward + SGD-momentum update — as ONE CachedOp, via the
    SAME ``CompiledTrainStep`` machinery that powers the default
    ``fit(compiled=True)`` path (module/compiled_step.py), so this bench
    and the fit loop exercise one code path.

    All state (params + momenta) rides as CachedOp aux, so each call
    writes the updated values back in place; the per-step host fetch of
    the loss therefore waits for the ENTIRE step — one clean barrier per
    batch, which is exactly the regime where a synchronous input pipeline
    costs its full decode+transfer time and an async feed hides it.
    ``lr``/``momentum`` come from BENCH_PIPE_LR / BENCH_PIPE_MOMENTUM
    (defaults 0.05 / 0.9) unless given explicitly.
    """
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.module.compiled_step import CompiledTrainStep

    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Conv2D(channels, 3, padding=1, activation="relu"))
        net.add(nn.Conv2D(channels * 2, 3, padding=1, activation="relu"))
        net.add(nn.GlobalAvgPool2D())
        net.add(nn.Flatten())
        net.add(nn.Dense(classes))
    net.initialize(mx.init.Xavier())
    net(nd.zeros((1, 3, img, img)))   # materialize deferred shapes

    if lr is None:
        lr = float(os.environ.get("BENCH_PIPE_LR", "0.05"))
    if momentum is None:
        momentum = float(os.environ.get("BENCH_PIPE_MOMENTUM", "0.9"))
    optimizer = opt_mod.SGD(learning_rate=lr, momentum=momentum,
                            rescale_grad=1.0)

    def ce_loss(outs, y):
        logp = nd.log_softmax(outs[0])
        picked = nd.pick(logp, y.astype("int32"), axis=1)
        return -nd.mean(picked)

    trainer = CompiledTrainStep.from_block(net, ce_loss, optimizer)

    def step(xb, yb):
        loss = trainer.step(xb, yb)   # [1]-shaped: one loss per microstep
        # the loss is one output of the single fused XLA module, so this
        # host fetch is a full-step barrier — the honest per-batch sync
        return float(np.asarray(loss.asnumpy())[0])

    # absorb the compile before anything is timed
    x = nd.array(np.zeros((batch, 3, img, img), np.float32))
    y = nd.array(np.zeros((batch,), np.float32))
    for _ in range(3):
        step(x, y)
    return trainer.cached_op, step


def _timed_epoch(batch_iter, step, batch, n_batches, warm=1):
    """Train over ``warm + n_batches`` batches; time the last n_batches.

    The ``warm`` batches fill the pipeline (feed path) / fault in the
    source (sync path) so every variant is measured at steady state — the
    regime a long epoch runs in.  Each step already ends with a host fetch
    (see ``_build_trainer``), so the window needs no extra barrier."""
    n = 0
    t0 = None
    for xb, yb in batch_iter:
        if n == warm:
            t0 = time.perf_counter()
        step(xb, yb)
        n += 1
        if n == warm + n_batches:
            break
    if n != warm + n_batches:
        raise RuntimeError("source ran dry: %d of %d batches"
                           % (n, warm + n_batches))
    wall = time.perf_counter() - t0
    return batch * n_batches / wall, wall


def run(smoke=False, out_path=None, emit=True):
    """Run the three measurements; -> the result record (also printed and
    written to ``out_path`` / BENCH_PIPELINE.json)."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd

    devs = jax.devices()
    device_kind = getattr(devs[0], "device_kind", devs[0].platform)

    batch = int(os.environ.get("BENCH_PIPE_BATCH", "8" if smoke else "32"))
    img = int(os.environ.get("BENCH_PIPE_IMG", "32"))
    channels = int(os.environ.get("BENCH_PIPE_CHANNELS", "32"))
    n_batches = int(os.environ.get("BENCH_PIPE_BATCHES",
                                   "8" if smoke else "20"))
    ratio = float(os.environ.get("BENCH_DECODE_RATIO", "0.7"))
    ctx = mx.current_context()

    cached_op, step = _build_trainer(batch, img, channels)
    cache_before = cached_op.cache_stats()

    # -- compute-only: pre-staged batch, no input pipeline ---------------
    rng = np.random.RandomState(1)
    x0 = nd.array(rng.uniform(-1, 1, (batch, 3, img, img)
                              ).astype(np.float32), ctx=ctx)
    y0 = nd.array(rng.randint(0, 10, batch).astype(np.float32), ctx=ctx)
    compute_rate, compute_wall = _timed_epoch(
        iter(lambda: (x0, y0), None), step, batch, n_batches)
    step_ms = compute_wall / n_batches * 1e3

    # -- decode cost: pinned by env, else a fixed ratio of the step ------
    decode_ms_env = os.environ.get("BENCH_DECODE_MS")
    decode_ms = (float(decode_ms_env) if decode_ms_env
                 else ratio * step_ms)
    n_samples = batch * (n_batches + 4)   # +warm batch +slack per epoch
    dataset = SyntheticDecodeDataset(n_samples, img, decode_ms / batch)

    def loader(**kw):
        return gluon.data.DataLoader(dataset, batch_size=batch,
                                     last_batch="discard", **kw)

    # -- synchronous path: decode + batchify inline in the consumer ------
    with loader() as sync_loader:
        sync_rate, _ = _timed_epoch(iter(sync_loader), step, batch,
                                    n_batches)

    # -- async feed path: DataLoader(prefetch_to_device=ctx) -------------
    with loader(prefetch_to_device=ctx) as feed_loader:
        feed_iter = iter(feed_loader)    # the DeviceFeed itself
        e2e_rate, _ = _timed_epoch(feed_iter, step, batch, n_batches)
        feed_stats = feed_iter.stats()
        feed_iter.close()

    cache_after = cached_op.cache_stats()
    recompiles = cache_after["recompiles"] - cache_before["recompiles"]

    record = {
        "metric": "pipeline_train_imgs_per_sec_bs%d" % batch,
        "value": round(e2e_rate, 2),
        "unit": "images/sec",
        "vs_baseline": None,
        "mode": "pipeline",
        "e2e_imgs_per_sec": round(e2e_rate, 2),
        "sync_imgs_per_sec": round(sync_rate, 2),
        "compute_imgs_per_sec": round(compute_rate, 2),
        "overlap_efficiency": round(e2e_rate / compute_rate, 4),
        "speedup_vs_sync": round(e2e_rate / sync_rate, 4),
        "step_ms": round(step_ms, 3),
        "decode_ms_per_batch": round(decode_ms, 3),
        "decode_ratio": round(decode_ms / step_ms, 4),
        "timed_batches": n_batches,
        "feed_stats": {k: round(v, 3) if isinstance(v, float) else v
                       for k, v in feed_stats.items()},
        "cache": {"recompiles_delta": recompiles,
                  "hits": cache_after["hits"],
                  "recompiles": cache_after["recompiles"]},
        "device": device_kind,
        "config": {"batch": batch, "img": img, "channels": channels,
                   "smoke": bool(smoke)},
        "data": "synthetic-decode (sleep-simulated per-sample decode, "
                "GIL-releasing) -> DataLoader -> DeviceFeed",
        "sync": "per-step host fetch of the loss (the fit-loop metric-"
                "update shape); 1 warm batch before each timed window",
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
    if emit:
        print(json.dumps(record), flush=True)
    return record


def main(argv=None):
    ap = argparse.ArgumentParser(prog="input_bench", description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small config for tier-1 (a few seconds)")
    ap.add_argument("--out", default=os.path.join(REPO, "BENCH_PIPELINE.json"),
                    help="artifact path (default: repo BENCH_PIPELINE.json)")
    ap.add_argument("--no-artifact", action="store_true",
                    help="print the JSON line only")
    args = ap.parse_args(argv)
    record = run(smoke=args.smoke,
                 out_path=None if args.no_artifact else args.out)
    # exit status encodes the acceptance gates so CI can fail loudly
    ok = (record["cache"]["recompiles_delta"] == 0
          and record["speedup_vs_sync"] >= (1.2 if args.smoke else 1.5)
          and record["overlap_efficiency"] >= (0.7 if args.smoke else 0.85))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
