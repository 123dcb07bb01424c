"""Measure kvstore allreduce bandwidth (reference: tools/bandwidth/
measure.py — the GB/s of gradient aggregation).

Single process: measures the tpu_sync jitted add-tree over N simulated
device buffers (one chip: HBM-bound adds).  Under a multi-device mesh
(virtual CPU or a pod slice) the same reduce compiles to XLA collectives —
run with XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu
to exercise the collective path without hardware.

Multi-process (the reference's distributed kvstore measurement — the
DCN-analog number): launch N workers, each timing the full cross-host
push/pull allreduce; rank 0 prints the JSON line:

    python tools/launch.py -n 4 --launcher local \\
        python tools/bandwidth.py --kv dist_sync --size-mb 16

Mesh collectives (the ZeRO sharded-update wire, docs/PERF.md): time one
collective over the dp mesh instead of the kvstore round trip:

    python tools/bandwidth.py --collective reduce_scatter --size-mb 16
    python tools/bandwidth.py --collective allgather
    python tools/bandwidth.py --wire 2bit     # EF-quantized gradient reduce

``--wire 2bit`` benches the quantized gradient reduce-scatter against the
fp32 baseline on the same gradient stream and reports the wire-byte
reduction (int8 codes vs fp32: 4x) plus the measured error-feedback
accuracy delta.  ``--smoke`` shrinks sizes/iters for CI schema checks.

Usage: python tools/bandwidth.py [--size-mb 64] [--copies 4] [--iters 20]
Prints one JSON line {"metric", "value", "unit"}.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _run_collective(args):
    """Time one mesh collective (jitted shard_map) and print its row."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax import shard_map
    import functools
    from mxnet_tpu.parallel import (make_mesh, allreduce, allgather,
                                    reduce_scatter)

    mesh = make_mesh()
    dp = int(mesh.shape["dp"])
    n = max(dp, int(args.size_mb * (1 << 20) / 4) // dp * dp)
    rng = np.random.RandomState(0)

    if args.collective == "reduce_scatter":
        # every replica contributes a FULL gradient row; each keeps 1/N
        x = rng.uniform(-1, 1, (dp, n)).astype(np.float32)
        fn, in_spec, out_spec = (
            lambda s: reduce_scatter(s[0], "dp")[None],
            P("dp"), P("dp"))
    elif args.collective == "allgather":
        x = rng.uniform(-1, 1, n).astype(np.float32)
        fn, in_spec, out_spec = (
            lambda s: allgather(s, "dp")[None],
            P("dp"), P("dp", None))
    elif args.collective == "allreduce":
        x = rng.uniform(-1, 1, n).astype(np.float32)
        fn, in_spec, out_spec = (
            lambda s: allreduce(s, "dp"), P("dp"), P("dp"))
    else:
        raise SystemExit("unknown --collective %r" % args.collective)

    run = jax.jit(shard_map(fn, mesh=mesh, in_specs=in_spec,
                            out_specs=out_spec, check_vma=False))
    x = jax.device_put(x, NamedSharding(
        mesh, P("dp", *([None] * (x.ndim - 1)))))
    run(x).block_until_ready()              # compile
    t0 = time.perf_counter()
    for _ in range(args.iters):
        out = run(x)
    out.block_until_ready()
    dt = time.perf_counter() - t0
    gbytes = n * 4 * args.iters / dt / 1e9
    print(json.dumps({
        "metric": "mesh_%s" % args.collective,
        "value": round(gbytes, 2),
        "unit": "GB/s",
        "size_mb": round(n * 4 / (1 << 20), 3),
        "devices": dp,
    }))


def _run_wire(args):
    """Bench the ZeRO gradient reduce at both wire formats on the SAME
    gradient stream: fp32 psum_scatter vs the EF-quantized int8-code
    reduce (parallel/zero.py quantized_reduce_scatter), reporting the
    wire-byte reduction and the measured error-feedback accuracy delta
    (max |delivered - fp32| of the per-step mean gradient)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax import shard_map
    from mxnet_tpu.parallel import (make_mesh, reduce_scatter,
                                    quantized_reduce_scatter)

    mesh = make_mesh()
    dp = int(mesh.shape["dp"])
    n = max(dp, int(args.size_mb * (1 << 20) / 4) // dp * dp)
    thr = args.wire_threshold
    rng = np.random.RandomState(0)
    g = rng.uniform(-0.4, 0.4, (dp, n)).astype(np.float32)
    row = NamedSharding(mesh, P("dp", None))

    def fp32_fn(gs):
        return (reduce_scatter(gs[0], "dp") / dp)[None]

    def q_fn(gs, rs):
        shard, new_r = quantized_reduce_scatter(gs[0], rs[0], thr, "dp", dp)
        return shard[None], new_r[None]

    fp32 = jax.jit(shard_map(fp32_fn, mesh=mesh, in_specs=P("dp"),
                             out_specs=P("dp", None), check_vma=False))
    quant = jax.jit(shard_map(q_fn, mesh=mesh,
                              in_specs=(P("dp"), P("dp", None)),
                              out_specs=(P("dp", None), P("dp", None)),
                              check_vma=False))
    g_dev = jax.device_put(g, row)
    res = jax.device_put(jnp.zeros((dp, n), jnp.float32), row)

    fp32(g_dev).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(args.iters):
        out_f = fp32(g_dev)
    out_f.block_until_ready()
    dt_f = time.perf_counter() - t0

    quant(g_dev, res)[0].block_until_ready()
    res = jax.device_put(jnp.zeros((dp, n), jnp.float32), row)
    sum_q = np.zeros(n, np.float64)
    t0 = time.perf_counter()
    for _ in range(args.iters):
        out_q, res = quant(g_dev, res)
        sum_q += np.asarray(out_q).ravel()
    dt_q = time.perf_counter() - t0

    mean_f = np.asarray(out_f).ravel()          # constant across iters
    # per-step delivered error of the quantized stream (EF bounds this by
    # ~threshold/iters per element once the residual warms up)
    delta = float(np.abs(sum_q / args.iters - mean_f).max())
    fp32_bytes = dp * n * 4
    wire_bytes = dp * n * 1                     # int8 codes on the wire
    base = {
        "unit": "GB/s",
        "size_mb": round(n * 4 / (1 << 20), 3),
        "devices": dp,
    }
    if args.wire == "fp32":
        print(json.dumps(dict(base, metric="gradient_reduce_wire_fp32",
                              value=round(n * 4 * args.iters / dt_f / 1e9, 2),
                              wire_bytes_per_step=fp32_bytes)))
        return
    print(json.dumps(dict(
        base, metric="gradient_reduce_wire_2bit",
        value=round(n * 4 * args.iters / dt_q / 1e9, 2),
        wire_bytes_per_step=wire_bytes,
        fp32_bytes_per_step=fp32_bytes,
        wire_reduction_x=round(fp32_bytes / wire_bytes, 1),
        wire_threshold=thr,
        accuracy_delta=round(delta, 6))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size-mb", type=float, default=64.0,
                    help="per-buffer size in MiB (fp32)")
    ap.add_argument("--copies", type=int, default=4,
                    help="number of per-device gradients to reduce")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--kv", default="tpu_sync")
    ap.add_argument("--collective", default=None,
                    choices=["allreduce", "reduce_scatter", "allgather"],
                    help="time one mesh collective instead of the kvstore")
    ap.add_argument("--wire", default=None, choices=["fp32", "2bit"],
                    help="bench the ZeRO gradient reduce at this wire format")
    ap.add_argument("--wire-threshold", type=float, default=0.5)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes/iters: schema check, not a measurement")
    args = ap.parse_args()
    if args.smoke:
        args.size_mb = min(args.size_mb, 0.25)
        args.iters = min(args.iters, 3)

    if args.collective is not None:
        _run_collective(args)
        return
    if args.wire is not None:
        _run_wire(args)
        return

    import mxnet_tpu as mx
    from mxnet_tpu import nd

    dist = args.kv.startswith("dist")
    n = int(args.size_mb * (1 << 20) / 4)
    kv = mx.kvstore.create(args.kv)
    # in dist mode each worker contributes ONE buffer; the interesting
    # reduce is the cross-process one, not the local add-tree
    copies = 1 if dist else args.copies
    rng = np.random.RandomState(0)
    bufs = [nd.array(rng.uniform(-1, 1, n).astype(np.float32))
            for _ in range(copies)]
    kv.init("0", bufs[0])
    if dist:
        kv.barrier()

    out = nd.zeros((n,))
    # warmup (compile)
    kv.push("0", bufs)
    kv.pull("0", out=out)
    out.wait_to_read()

    if dist:
        kv.barrier()
    t0 = time.perf_counter()
    for _ in range(args.iters):
        kv.push("0", bufs)
        kv.pull("0", out=out)
    out.wait_to_read()
    dt = time.perf_counter() - t0

    # bytes reduced per iteration: every participating buffer in + one out
    workers = getattr(kv, "num_workers", 1)
    gbytes = max(copies, workers) * n * 4 * args.iters / dt / 1e9
    if getattr(kv, "rank", 0) == 0:
        print(json.dumps({
            "metric": "kvstore_%s_allreduce" % args.kv,
            "value": round(gbytes, 2),
            "unit": "GB/s",
            "size_mb": args.size_mb,
            "copies": copies,
            "workers": workers,
        }))


if __name__ == "__main__":
    main()
