"""CPU-vs-TPU op consistency sweep on real hardware — the reference's
tests/python/gpu/test_operator_gpu.py strategy (same op run on both
devices via context injection, results compared at dtype-appropriate
tolerances; check_consistency in python/mxnet/test_utils.py) pointed at
the live chip.

Runs a representative op battery (conv/FC/BN/pooling/softmax/reductions/
elementwise/dot in f32+bf16/flash-attention/autograd backward) with the
SAME host inputs placed on cpu(0) and tpu(0), records per-case max
absolute difference, and writes CONSISTENCY_TPU.json.  The Pallas flash
attention case is the kernel-vs-XLA-reference check ON HARDWARE: the TPU
side runs the Pallas kernel, the CPU side the dense XLA reference.

Exits nonzero (and value=null) when no TPU is present.

Usage: python tools/tpu_consistency.py [--out CONSISTENCY_TPU.json]
"""
import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np


def build_cases():
    """[(name, fn(ctx)->np.ndarray, rtol, atol)] — each callable builds
    inputs ON ctx from the shared host arrays and returns host results."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd, autograd

    rng = np.random.RandomState(0)
    x_img = rng.randn(4, 8, 14, 14).astype(np.float32)
    w_conv = rng.randn(16, 8, 3, 3).astype(np.float32) * 0.1
    b_conv = rng.randn(16).astype(np.float32) * 0.1
    x_fc = rng.randn(16, 64).astype(np.float32)
    w_fc = rng.randn(32, 64).astype(np.float32) * 0.1
    b_fc = rng.randn(32).astype(np.float32) * 0.1
    gamma = np.abs(rng.randn(8).astype(np.float32)) + 0.5
    beta = rng.randn(8).astype(np.float32)
    mean = rng.randn(8).astype(np.float32) * 0.1
    var = np.abs(rng.randn(8).astype(np.float32)) + 0.5
    q = rng.randn(2, 4, 128, 64).astype(np.float32)
    k = rng.randn(2, 4, 128, 64).astype(np.float32)
    v = rng.randn(2, 4, 128, 64).astype(np.float32)

    def conv(ctx):
        out = nd.Convolution(nd.array(x_img, ctx=ctx),
                             nd.array(w_conv, ctx=ctx),
                             nd.array(b_conv, ctx=ctx),
                             kernel=(3, 3), num_filter=16)
        return out.asnumpy()

    def fc(ctx):
        return nd.FullyConnected(nd.array(x_fc, ctx=ctx),
                                 nd.array(w_fc, ctx=ctx),
                                 nd.array(b_fc, ctx=ctx),
                                 num_hidden=32).asnumpy()

    def bn_infer(ctx):
        out = nd.BatchNorm(nd.array(x_img, ctx=ctx),
                           nd.array(gamma, ctx=ctx),
                           nd.array(beta, ctx=ctx),
                           nd.array(mean, ctx=ctx),
                           nd.array(var, ctx=ctx))
        if isinstance(out, (list, tuple)):  # [out, running_mean, running_var]
            out = out[0]
        return out.asnumpy()

    def pool(ctx):
        return nd.Pooling(nd.array(x_img, ctx=ctx), kernel=(2, 2),
                          pool_type="max", stride=(2, 2)).asnumpy()

    def softmax(ctx):
        return nd.log_softmax(nd.array(x_fc, ctx=ctx), axis=1).asnumpy()

    def elemwise(ctx):
        a = nd.array(np.abs(x_fc) + 0.1, ctx=ctx)
        return (nd.log(a) + nd.tanh(a) * nd.sqrt(a)).asnumpy()

    def reductions(ctx):
        a = nd.array(x_img, ctx=ctx)
        return np.stack([nd.sum(a, axis=(2, 3)).asnumpy().ravel(),
                         nd.max(a, axis=(2, 3)).asnumpy().ravel(),
                         nd.mean(a, axis=(2, 3)).asnumpy().ravel()])

    def dot_f32(ctx):
        return nd.dot(nd.array(x_fc, ctx=ctx),
                      nd.array(w_fc.T, ctx=ctx)).asnumpy()

    def dot_bf16(ctx):
        a = nd.array(x_fc, ctx=ctx).astype("bfloat16")
        b = nd.array(w_fc.T, ctx=ctx).astype("bfloat16")
        return nd.dot(a, b).astype("float32").asnumpy()

    def flash_attn(ctx):
        # TPU side: the Pallas kernel DIRECTLY (the public entry's
        # try/except would silently substitute the dense reference on a
        # broken kernel, making this case pass vacuously); CPU side: the
        # dense XLA reference the kernel is validated against.
        from mxnet_tpu.ops import pallas_ops
        import jax
        dev = ctx.jax_device()
        scale = 1.0 / np.sqrt(q.shape[-1])
        args = [jax.device_put(t, dev) for t in (q, k, v)]
        with jax.default_device(dev):
            if dev.platform == "cpu":
                out = pallas_ops._attention_reference(*args, True, scale)
            else:
                out = pallas_ops._flash_attention_pallas(*args, True, scale)
        return np.asarray(out)

    def conv_backward(ctx):
        xs = nd.array(x_img, ctx=ctx)
        ws = nd.array(w_conv, ctx=ctx)
        xs.attach_grad()
        ws.attach_grad()
        with autograd.record():
            out = nd.Convolution(xs, ws, nd.array(b_conv, ctx=ctx),
                                 kernel=(3, 3), num_filter=16)
            loss = (out * out).sum()
        loss.backward()
        return np.concatenate([xs.grad.asnumpy().ravel(),
                               ws.grad.asnumpy().ravel()])

    return [("Convolution_fwd", conv, 1e-4, 1e-4),
            ("FullyConnected_fwd", fc, 1e-4, 1e-4),
            ("BatchNorm_infer", bn_infer, 1e-4, 1e-4),
            ("Pooling_max", pool, 1e-5, 1e-5),
            ("log_softmax", softmax, 1e-4, 1e-4),
            ("elemwise_chain", elemwise, 1e-4, 1e-4),
            ("reductions", reductions, 1e-3, 1e-3),
            ("dot_f32", dot_f32, 1e-3, 1e-3),
            ("dot_bf16", dot_bf16, 5e-2, 5e-2),
            ("flash_attention_pallas_vs_dense", flash_attn, 2e-2, 2e-2),
            ("Convolution_backward", conv_backward, 5e-3, 5e-1)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO,
                                                  "CONSISTENCY_TPU.json"))
    ap.add_argument("--self-test", action="store_true",
                    help="compare cpu vs cpu (validates the battery "
                         "plumbing without hardware; diffs must be 0)")
    args = ap.parse_args()

    import jax
    import mxnet_tpu as mx

    devs = jax.devices()
    if devs[0].platform != "tpu" and not args.self_test:
        print(json.dumps({"metric": "tpu_consistency_cases_passed",
                          "value": None,
                          "error": "no TPU backend (platform=%s)"
                                   % devs[0].platform}))
        sys.exit(3)
    kind = devs[0].device_kind

    rows, n_pass = [], 0
    for name, fn, rtol, atol in build_cases():
        try:
            r_cpu = fn(mx.cpu(0))
            r_tpu = fn(mx.cpu(0) if args.self_test else mx.context.tpu(0))
            from mxnet_tpu.test_utils import almost_equal
            diff = np.abs(r_cpu.astype(np.float64) - r_tpu.astype(np.float64))
            denom = np.abs(r_cpu.astype(np.float64)) + atol
            ok = bool(almost_equal(r_cpu, r_tpu, rtol=rtol, atol=atol))
            row = {"case": name, "ok": ok,
                   "max_abs_diff": float(diff.max()),
                   "max_rel_diff": float((diff / denom).max()),
                   "rtol": rtol, "atol": atol}
        except Exception as e:
            row = {"case": name, "ok": False,
                   "error": "%s: %s" % (type(e).__name__, str(e)[:200])}
        rows.append(row)
        n_pass += bool(row["ok"])
        print("%-36s %s" % (name, "OK" if row["ok"]
                            else row.get("error", "DIFF %.3g" %
                                         row.get("max_abs_diff", -1))),
              flush=True)

    out = {"description": "same op, same host inputs, cpu(0) vs tpu(0) "
                          "(reference test_operator_gpu context-injection "
                          "strategy on real hardware)",
           "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
           "device_kind": kind, "cases": rows,
           "passed": n_pass, "total": len(rows)}
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"metric": "tpu_consistency_cases_passed",
                      "value": n_pass, "unit": "cases",
                      "vs_baseline": n_pass / len(rows),
                      "total": len(rows), "device_kind": kind}), flush=True)
    sys.exit(0 if n_pass == len(rows) else 1)


if __name__ == "__main__":
    main()
