"""Fused (Pallas) vs dense (XLA) attention on-chip comparison.

Beyond-parity perf evidence: the reference's transformer pieces
(src/operator/contrib/transformer.cc) compute attention as explicit
batched-gemm + softmax + batched-gemm, materializing the (T, T) score
matrix in HBM.  The repo's `mxnet_tpu.ops.pallas_ops.flash_attention`
streams K/V blocks through VMEM with an online softmax, so score traffic
never touches HBM.  This tool measures both paths on the live device and
records the speedup + achieved TFLOP/s per sequence length.

Writes one JSON line per (path, T) to stdout and the aggregate to
ATTN_BENCH.json.  It measures the compiled kernel, so it needs the chip
(one is enough) and exits with an error on any other platform.

Usage: python tools/attn_bench.py [--seqs 1024,2048,4096,8192]
"""
import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

OUT_PATH = os.path.join(REPO, "ATTN_BENCH.json")


def _now():
    return time.strftime("%Y-%m-%dT%H:%M:%S", time.gmtime()) + "Z"


def _time_fn(fn, *args, warmup=2, iters=10):
    """Median wall seconds per call, synchronized on the result buffer."""
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def attn_flops(B, H, T, D, causal):
    """QK^T + PV matmul FLOPs (softmax excluded, like every flash paper)."""
    full = 2 * 2.0 * B * H * T * T * D
    return full / 2 if causal else full


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", default="1024,2048,4096,8192")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_ops

    dev = jax.devices()[0]
    platform = dev.platform
    if platform != "tpu":
        sys.exit("attn_bench measures the compiled TPU kernel; JAX's "
                 "default backend here is %r" % platform)
    kind = dev.device_kind
    B, H, D = args.batch, args.heads, args.head_dim
    rows = []
    for T in [int(s) for s in args.seqs.split(",")]:
        key = jax.random.PRNGKey(T)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (B, H, T, D), jnp.bfloat16)
        k = jax.random.normal(kk, (B, H, T, D), jnp.bfloat16)
        v = jax.random.normal(kv, (B, H, T, D), jnp.bfloat16)

        scale = 1.0 / (D ** 0.5)

        # dense path: exactly what an unfused transformer.cc-style graph
        # lowers to — jit so XLA fuses softmax; the (T,T) matrix still lands
        dense = jax.jit(lambda q_, k_, v_: pallas_ops._attention_reference(
            q_, k_, v_, True, scale))
        fused = jax.jit(lambda q_, k_, v_: pallas_ops._flash_attention_pallas(
            q_, k_, v_, True, scale))

        # fwd+bwd: scalar loss so grad drives the custom_vjp
        dense_fb = jax.jit(jax.grad(
            lambda q_, k_, v_: pallas_ops._attention_reference(
                q_, k_, v_, True, scale).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))
        fused_fb = jax.jit(jax.grad(
            lambda q_, k_, v_: pallas_ops.flash_attention(
                q_, k_, v_, causal=True).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))

        flops = attn_flops(B, H, T, D, causal=True)
        row = {"T": T, "B": B, "H": H, "D": D, "dtype": "bfloat16",
               "causal": True, "platform": platform, "device_kind": kind}
        paths = [("dense_fwd", dense, 1.0), ("fused_fwd", fused, 1.0),
                 ("dense_fwdbwd", dense_fb, 3.5), ("fused_fwdbwd", fused_fb, 3.5)]
        for name, fn, flop_mult in paths:
            try:
                sec = _time_fn(fn, q, k, v, iters=args.iters)
                row[name + "_ms"] = round(sec * 1e3, 3)
                row[name + "_tflops"] = round(flops * flop_mult / sec / 1e12, 2)
            except Exception as e:  # dense OOMs first at long T — that IS the result
                row[name + "_error"] = "%s: %s" % (type(e).__name__, str(e)[:200])
        if "dense_fwd_ms" in row and "fused_fwd_ms" in row:
            row["fwd_speedup"] = round(row["dense_fwd_ms"] / row["fused_fwd_ms"], 2)
        if "dense_fwdbwd_ms" in row and "fused_fwdbwd_ms" in row:
            row["fwdbwd_speedup"] = round(
                row["dense_fwdbwd_ms"] / row["fused_fwdbwd_ms"], 2)
        print(json.dumps(row), flush=True)
        rows.append(row)

    out = {"description": "flash_attention (Pallas, ops/pallas_ops.py) vs "
                          "dense XLA attention, causal bf16, median of %d "
                          "iters, block_until_ready-synced"
                          % args.iters,
           "captured_at": _now(), "platform": platform, "device_kind": kind,
           "rows": rows}
    with open(OUT_PATH, "w") as f:
        json.dump(out, f, indent=1)
    # summary from the largest T that produced a speedup — the dense path
    # is EXPECTED to OOM first at long T, and that must not turn a
    # successful capture into a failed one
    best = next((r for r in reversed(rows) if "fwd_speedup" in r), None)
    print(json.dumps({"metric": "attn_fused_vs_dense_fwd_speedup_T%d"
                                % (best["T"] if best else rows[-1]["T"]),
                      "value": best["fwd_speedup"] if best else None,
                      "unit": "x",
                      "vs_baseline": best["fwd_speedup"] if best else None}),
          flush=True)


if __name__ == "__main__":
    main()
