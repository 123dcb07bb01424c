"""Benchmark: ResNet-50 training throughput on one TPU chip.

Baseline (BASELINE.md): MXNet 1.2 trains ResNet-50 bs=32 fp32 at 298.51
img/s on 1x V100 (docs/faq/perf.md:208-217).  vs_baseline is images/sec
relative to that number.

The measured step is the full compiled training iteration — forward + backward
+ SGD-momentum update as ONE XLA module with donated buffers (the analog of
train_imagenet.py's per-batch forward_backward+update), bf16 compute with fp32
params (TPU-native dtype policy; the reference's fp16 path is the analog).

Prints one JSON line: {"metric", "value", "unit", "vs_baseline"} plus
supporting keys ("mfu", "platform", "device", "layout", "step_flops").  A line
whose "platform" is not "tpu" is a rehearsal of the code path, not a
measurement; its "mfu" is null.
"""
import json
import os
import sys
import time

import numpy as np

# knob defaults live in mxnet_tpu/env.py (the env_var.md registry)
BATCH = int(os.environ.get("BENCH_BATCH", "32"))
IMG = int(os.environ.get("BENCH_IMG", "224"))
# BENCH_MODE=train (default, the driver metric) | inference
# (docs/faq/perf.md:150-180: 1076.81 img/s fp32 / 2085.51 fp16 on V100)
# | transformer (beyond-parity: GPT-2-small-ish decoder LM with the Pallas
# flash-attention kernel; tokens/sec + MFU, no reference baseline exists)
# | pipeline (END-TO-END input pipeline: synthetic decode -> DataLoader ->
# DeviceFeed -> fused train step; reports e2e vs compute-only img/s and
# overlap efficiency — tools/input_bench.py, artifact BENCH_PIPELINE.json)
# | fused_fit (compiled fit() vs eager fit() end-to-end: the default
# CompiledTrainStep path — tools/fit_bench.py, artifact BENCH_FUSED_FIT.json)
MODE = os.environ.get("BENCH_MODE", "train")
# BENCH_LAYOUT=auto (default: measure NCHW first, then NHWC, report the
# faster — settles SURVEY §7(f) with data in every driver capture) |
# NCHW (reference layout) | NHWC (channels-last only)
LAYOUT = os.environ.get("BENCH_LAYOUT", "auto").upper()
if MODE not in ("train", "inference", "transformer", "int8", "pipeline",
                "fused_fit"):
    # still honor the one-JSON-line-on-stdout contract
    print(json.dumps({"metric": "invalid_bench_mode", "value": None,
                      "unit": None, "vs_baseline": None,
                      "error": "unknown BENCH_MODE=%r (train|inference|"
                               "transformer|int8|pipeline|fused_fit)"
                               % MODE}))
    sys.exit(1)
if LAYOUT not in ("AUTO", "NCHW", "NHWC"):
    print(json.dumps({"metric": "invalid_bench_layout", "value": None,
                      "unit": None, "vs_baseline": None,
                      "error": "unknown BENCH_LAYOUT=%r (auto|NCHW|NHWC)"
                               % LAYOUT}))
    sys.exit(1)
# reference numbers per (mode, batch) at 224x224 (BASELINE.md; train =
# docs/faq/perf.md:208-217 fp32 V100, inference = :164-180 fp16 V100)
_BASELINES = {("train", 32): 298.51, ("train", 128): 363.69,
              ("inference", 32): 2085.51, ("inference", 128): 2355.04}
BASELINE_IMGS_PER_SEC = _BASELINES.get((MODE, BATCH))
# the baseline ratio is only meaningful where the reference published one
IS_HEADLINE = (IMG == 224 and BASELINE_IMGS_PER_SEC is not None)
if MODE == "transformer":
    METRIC = ("transformer_lm_train_tokens_per_sec_d%d_T%d"
              % (int(os.environ.get("BENCH_TFM_DEPTH", "12")),
                 int(os.environ.get("BENCH_TFM_SEQ", "1024"))))
elif MODE == "int8":
    METRIC = "resnet50_int8_infer_imgs_per_sec_bs%d" % BATCH
elif MODE == "pipeline":
    # end-to-end input-pipeline mode: decode -> DataLoader -> DeviceFeed ->
    # fused train step; tools/input_bench.py is the implementation and
    # BENCH_PIPELINE.json the artifact (config via BENCH_PIPE_*)
    METRIC = ("pipeline_train_imgs_per_sec_bs%s"
              % os.environ.get("BENCH_PIPE_BATCH", "32"))
elif MODE == "fused_fit":
    # compiled-vs-eager fit(): tools/fit_bench.py, BENCH_FUSED_FIT.json
    # artifact (config via BENCH_FIT_*)
    METRIC = ("fused_fit_imgs_per_sec_bs%s"
              % os.environ.get("BENCH_FIT_BATCH", "32"))
else:
    _KIND = "train" if MODE == "train" else "infer"
    METRIC = ("resnet50_%s_imgs_per_sec_bs%d" % (_KIND, BATCH) if IS_HEADLINE
              else "resnet50_%s_imgs_per_sec_bs%d_img%d" % (_KIND, BATCH, IMG))

# peak bf16 matmul throughput per chip, by device_kind substring
# (public spec-sheet numbers; used only to report MFU alongside img/s)
_PEAK_FLOPS = [
    ("v6", 918e12),
    ("v5p", 459e12),
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 46e12),
]


def _peak_flops(device_kind):
    """Peak bf16 FLOP/s of a TPU by its device_kind; a kind that is not in
    the table is an error, not a default."""
    kind = device_kind.lower()
    for sub, peak in _PEAK_FLOPS:
        if sub in kind:
            return peak
    raise KeyError("no peak FLOP/s on record for TPU device_kind %r; add it "
                   "to bench._PEAK_FLOPS with its source" % device_kind)


def _device():
    """(platform, device_kind) of JAX's default backend's first device."""
    import jax
    devs = jax.devices()
    print("backend: %s x%d" % (devs[0].platform, len(devs)), file=sys.stderr)
    return devs[0].platform, devs[0].device_kind


def _timed_rate(run_step, block, items_per_step, default_iters=20):
    """Shared measurement harness: 1 compile-absorbing call + block, 2 more
    warmup calls + block, then BENCH_ITERS timed calls + block.  Returns
    items/sec.  ``run_step()`` advances one step; ``block()`` must return a
    device array from the LAST step.

    Sync discipline: the timed region ends with ``np.asarray`` on the array
    ``block()`` returns — a device->host copy of a value cannot complete
    before the computation that produces it.  The steps are data-dependent
    (each consumes the previous step's donated outputs), so the final fetch
    transitively waits for all of them."""
    def _sync():
        out = block()
        if out is not None:
            np.asarray(out)
    run_step()
    _sync()
    for _ in range(2):
        run_step()
    _sync()
    iters = int(os.environ.get("BENCH_ITERS", str(default_iters)))
    t0 = time.perf_counter()
    for _ in range(iters):
        run_step()
    _sync()
    wall = time.perf_counter() - t0
    _timed_rate.last_window = {"iters": iters, "wall_s": round(wall, 4)}
    return items_per_step * iters / wall


def _mfu(flops_per_step, rate, items_per_step, platform, device_kind):
    """Model-flops-utilization of a TPU from XLA's own cost model; None on
    any other platform, where a run is a rehearsal and has no peak."""
    if platform != "tpu":
        return None
    return round(flops_per_step * rate / items_per_step
                 / _peak_flops(device_kind), 4)


def _step_flops(compiled):
    """FLOPs of one compiled step from XLA's own cost model."""
    return float(compiled.cost_analysis()["flops"])


def _measure(layout):
    """Build + AOT-compile + time ResNet-50 in the given layout.

    Returns {"imgs_per_sec", "flops"}; the whole measured step is one XLA
    module (forward+backward+SGD-momentum, donated buffers) in train mode,
    or the bf16 forward in inference mode."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.gluon.block import functional_call, param_values
    from mxnet_tpu import nd

    dtype = jnp.bfloat16
    net = vision.resnet50_v1(classes=1000, layout=layout)
    net.initialize(mx.init.Xavier())
    shape = (1, 3, IMG, IMG) if layout == "NCHW" else (1, IMG, IMG, 3)
    net(nd.zeros(shape))  # materialize deferred shapes
    params = param_values(net)

    aux_names = {n for n, p in net.collect_params().items()
                 if p.grad_req == "null"}
    train_names = sorted(n for n in params if n not in aux_names)

    def loss_fn(train_params, aux_params, x, y):
        p = dict(aux_params)
        p.update({n: v.astype(dtype) for n, v in train_params.items()})
        outs, new_aux = functional_call(net, p, x.astype(dtype), training=True)
        logits = outs[0].astype(jnp.float32)
        logp = jax.nn.log_softmax(logits)
        loss = -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))
        return loss, new_aux

    lr = 0.05
    momentum = 0.9

    def train_step(train_params, momenta, aux_params, x, y):
        (loss, new_aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            train_params, aux_params, x, y)
        new_m = {n: momentum * momenta[n] + grads[n] for n in train_params}
        new_p = {n: train_params[n] - lr * new_m[n] for n in train_params}
        aux = dict(aux_params)
        aux.update(new_aux)
        return new_p, new_m, aux, loss

    train_params = {n: params[n] for n in train_names}
    momenta = {n: jnp.zeros_like(params[n]) for n in train_names}
    aux_params = {n: params[n] for n in params if n in aux_names}

    rng = np.random.RandomState(0)
    xshape = (BATCH, 3, IMG, IMG) if layout == "NCHW" \
        else (BATCH, IMG, IMG, 3)
    x = jnp.asarray(rng.uniform(-1, 1, xshape).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 1000, BATCH).astype(np.int32))

    if MODE == "inference":
        # weights AND moving stats in bf16: fp32 stats would promote the
        # activations and break the all-bf16 conv chain
        all_params = {n: v.astype(dtype) for n, v in params.items()}

        def infer_step(p, xb):
            outs, _ = functional_call(net, p, xb.astype(dtype), training=False)
            return outs[0]

        compiled = jax.jit(infer_step).lower(all_params, x).compile()
        state = {}

        def run_step():
            state["out"] = compiled(all_params, x)
        # 50 timed iters has been the inference default since round 3
        rate = _timed_rate(run_step,
                           lambda: state["out"].block_until_ready(), BATCH,
                           default_iters=50)
        return {"imgs_per_sec": rate, "flops": _step_flops(compiled),
                "window": getattr(_timed_rate, "last_window", None)}

    # AOT-compile the whole training iteration as one XLA module with the
    # previous step's buffers donated (params/momenta/aux update in place)
    compiled = jax.jit(train_step, donate_argnums=(0, 1, 2)).lower(
        train_params, momenta, aux_params, x, y).compile()
    flops = _step_flops(compiled)
    # donation consumes the inputs, so thread the outputs forward
    state = {"t": (train_params, momenta, aux_params)}

    def run_step():
        tp, mo, ax = state["t"]
        tp, mo, ax, loss = compiled(tp, mo, ax, x, y)
        state["t"] = (tp, mo, ax)
        state["loss"] = loss
    rate = _timed_rate(run_step, lambda: state["loss"].block_until_ready(),
                       BATCH)
    return {"imgs_per_sec": rate, "flops": flops,
            "window": getattr(_timed_rate, "last_window", None)}


def _measure_int8(platform, device_kind):
    """int8 quantized ResNet-50 inference through the executor: gluon
    model-zoo net -> HybridBlock.export -> quantize_model graph pass
    (minmax calibration) -> jitted executor forward.  The quantized conv/FC
    kernels issue int8 x int8 -> int32 dot/conv (ops/quantization_ops.py),
    the MXU's native int8 path — the TPU-side analog of the reference's
    example/quantization int8 deployment.  No int8 V100 number exists in
    the reference's perf.md, so vs_baseline compares against its fp16
    inference headline (2085.51 img/s bs=32) with a note."""
    import tempfile
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.contrib import quantization as q

    net = vision.get_model("resnet50_v1", classes=1000)
    net.initialize(mx.init.Xavier())
    net(nd.zeros((1, 3, IMG, IMG)))  # materialize params
    tmp = tempfile.mkdtemp()
    prefix = os.path.join(tmp, "r50")
    net.export(prefix)
    sym, arg_params, aux_params = mx.model.load_checkpoint(prefix, 0)

    rng = np.random.RandomState(0)
    x_np = rng.uniform(-1, 1, (BATCH, 3, IMG, IMG)).astype(np.float32)
    calib = mx.io.NDArrayIter(
        rng.uniform(-1, 1, (BATCH, 3, IMG, IMG)).astype(np.float32),
        np.zeros(BATCH, np.float32), BATCH)
    qsym, qargs, qaux = q.quantize_model(sym, arg_params, aux_params,
                                         calib_data=calib,
                                         calib_mode="minmax")
    exe = qsym.simple_bind(mx.current_context(),
                           data=(BATCH, 3, IMG, IMG),
                           grad_req="null")
    exe.copy_params_from(qargs, qaux)
    x = nd.array(x_np)
    state = {}

    def run_step():
        state["out"] = exe.forward(is_train=False, data=x)[0]

    rate = _timed_rate(run_step, lambda: state["out"]._data, BATCH,
                       default_iters=50)
    window = getattr(_timed_rate, "last_window", None)
    print(json.dumps({
        **({"timed_window": window} if window else {}),
        "metric": METRIC,
        "value": round(rate, 2),
        "unit": "images/sec",
        "vs_baseline": (round(rate / 2085.51, 3)
                        if BATCH == 32 and IMG == 224 else None),
        "baseline_note": "vs the reference's fp16 V100 inference headline "
                         "(docs/faq/perf.md:164-180); no int8 V100 number "
                         "is published in-tree",
        "mfu": None,
        "step_flops": None,
        "platform": platform,
        "device": device_kind,
        "calib": "minmax",
        "mode": MODE,
    }), flush=True)


def _measure_transformer(platform, device_kind):
    """Decoder-LM training throughput: one donated-buffer XLA module per
    step (fwd+bwd+sgd) over the flash-attention TransformerLM.  Prints the
    JSON line itself (tokens/sec; no layout loop, no reference baseline —
    this is the beyond-parity transformer headline)."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "example", "gluon"))
    from transformer_lm import TransformerLM
    from mxnet_tpu.gluon.block import functional_call, param_values
    from mxnet_tpu import nd

    B = int(os.environ.get("BENCH_TFM_BATCH", "8"))
    T = int(os.environ.get("BENCH_TFM_SEQ", "1024"))
    dim = int(os.environ.get("BENCH_TFM_DIM", "768"))
    depth = int(os.environ.get("BENCH_TFM_DEPTH", "12"))
    vocab = int(os.environ.get("BENCH_TFM_VOCAB", "32768"))
    dtype = jnp.bfloat16

    heads = max(1, dim // 64)      # 64-wide heads; tiny dims fold to one
    net = TransformerLM(vocab, dim=dim, heads=heads, depth=depth,
                        max_len=T)
    net.initialize(mx.init.Xavier())
    pos_row = np.arange(T, dtype=np.int32)[None]
    net(nd.zeros((1, T), dtype="int32"), nd.array(pos_row))  # materialize
    params = param_values(net)
    pos = jnp.asarray(np.tile(pos_row, (B, 1)))

    def loss_fn(train_params, idx, y):
        p = {n: (v.astype(dtype) if v.dtype == jnp.float32 else v)
             for n, v in train_params.items()}
        outs, _ = functional_call(net, p, idx, pos, training=True)
        logits = outs[0].astype(jnp.float32)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))

    lr = 0.01

    def train_step(train_params, idx, y):
        loss, grads = jax.value_and_grad(loss_fn)(train_params, idx, y)
        return ({n: train_params[n] - lr * grads[n] for n in train_params},
                loss)

    rng = np.random.RandomState(0)
    idx = jnp.asarray(rng.randint(0, vocab, (B, T)).astype(np.int32))
    y = jnp.asarray(rng.randint(0, vocab, (B, T)).astype(np.int32))
    compiled = jax.jit(train_step, donate_argnums=(0,)).lower(
        params, idx, y).compile()
    flops = _step_flops(compiled)
    state = {"p": params}

    def run_step():
        state["p"], state["loss"] = compiled(state["p"], idx, y)
    tokens_per_sec = _timed_rate(
        run_step, lambda: state["loss"].block_until_ready(), B * T)
    tfm_mfu = _mfu(flops, tokens_per_sec, B * T, platform, device_kind)
    tfm_window = getattr(_timed_rate, "last_window", None)
    print(json.dumps({
        **({"timed_window": tfm_window} if tfm_window else {}),
        "metric": METRIC,
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec",
        "vs_baseline": None,
        "mfu": tfm_mfu,
        "step_flops": flops,
        "platform": platform,
        "device": device_kind,
        "config": {"batch": B, "seq": T, "dim": dim, "depth": depth,
                   "vocab": vocab},
        "mode": MODE,
        "data": "synthetic on-device",
    }), flush=True)


def _emit(results, platform, device_kind):
    """Print the result line for the layouts measured so far (the last
    line printed is the verdict)."""
    winner = max(results, key=lambda l: results[l]["imgs_per_sec"])
    best = results[winner]
    imgs_per_sec = best["imgs_per_sec"]
    window = best.get("window")
    print(json.dumps({
        **({"timed_window": window} if window else {}),
        "metric": METRIC,
        "value": round(imgs_per_sec, 2),
        "unit": "images/sec",
        "vs_baseline": (round(imgs_per_sec / BASELINE_IMGS_PER_SEC, 3)
                        if IS_HEADLINE else None),
        "mfu": _mfu(best["flops"], imgs_per_sec, BATCH, platform,
                    device_kind),
        "step_flops": best["flops"],
        "platform": platform,
        "device": device_kind,
        "layout": winner,
        "layouts": {l: round(r["imgs_per_sec"], 2)
                    for l, r in results.items()},
        "mode": MODE,
        # the timed step consumes a pre-staged on-device batch — this
        # measures kernel/step throughput (MFU), not the host input pipeline
        "data": "synthetic on-device",
        "sync": "host-fetch of final data-dependent step inside timed window",
    }), flush=True)


def main():
    platform, device_kind = _device()

    if MODE == "transformer":
        _measure_transformer(platform, device_kind)
        return 0
    if MODE == "int8":
        _measure_int8(platform, device_kind)
        return 0
    if MODE == "pipeline":
        repo = os.path.dirname(os.path.abspath(__file__))
        sys.path.insert(0, os.path.join(repo, "tools"))
        import input_bench
        input_bench.run(out_path=os.path.join(repo, "BENCH_PIPELINE.json"))
        return 0
    if MODE == "fused_fit":
        repo = os.path.dirname(os.path.abspath(__file__))
        sys.path.insert(0, os.path.join(repo, "tools"))
        import fit_bench
        fit_bench.run(out_path=os.path.join(repo, "BENCH_FUSED_FIT.json"))
        return 0

    layouts = ("NCHW", "NHWC") if LAYOUT == "AUTO" else (LAYOUT,)
    results = {}
    for layout in layouts:
        results[layout] = _measure(layout)
        _emit(results, platform, device_kind)
    return 0


if __name__ == "__main__":
    sys.exit(main())
