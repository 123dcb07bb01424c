#!/usr/bin/env python
"""chip_smoke.py: the quickest proof that the system still starts on the chip.

One process drives the two main paths once, through the entry points a user
calls, at the full width of the models the repo supports, and checks what
comes out by the repo's own means.  It needs a TPU: anywhere else it exits
non-zero and prints no ``ok`` line.

    python chip_smoke.py            # one chip: train, train_bf16, serve, kernel
    python chip_smoke.py --chips 4  # four chips: only the cross-chip paths

One chip:

* ``train``: ResNet-50 v1 (model zoo, 1000 classes, 3x224x224, batch 32)
  through ``Module.fit()`` -> ``CompiledTrainStep`` with a ``DeviceFeed``.
  The Module path has no dtype inference, so parameters and activations are
  float32 and the MXU runs XLA's default TPU precision (bf16 passes).  The
  same seeded batch is fed every step, so a step that trains makes the loss
  fall.
* ``train_bf16``: the dtype policy ``Module`` cannot carry, through the Gluon
  twin ``CompiledTrainStep.from_block``: the same network cast to bfloat16
  (parameters and activations) with float32 master weights and momentum in
  the optimizer (``multi_precision``), the same batch, the same checks.
* ``serve``: ``FleetRouter.load_decode`` with one ``DecodeEngine`` replica,
  ``submit_stream`` for eight requests of mixed prompt lengths (chunked
  prefill, continuous batching, prefix cache).  The repo's only decode model
  is ``TinyCausalLM``; it runs at the width a chip is built for (hidden 2048,
  16 heads x 128, 16 layers, vocabulary 50,304: the skeleton of the roadmap's
  first real model, not a published model) with random weights from
  ``--seed``.  Greedy tokens must equal ``generate_reference``.
* ``kernel``: the Pallas flash-attention kernel, compiled, against the dense
  reference.

Four chips (``--chips 4``): a ZeRO data-parallel ResNet-50 step over a
4-device mesh against the same global batch on one device (convs at
precision ``high``); ``ShardedDecodeModel(tp=4)`` against ``tp=1`` (depth cut
to 4 layers, matmuls at precision ``highest``); and two fleet replicas of
that 4-layer model, which must land on two chips, each with its own weights
and pools, and give the reference's tokens from either chip.  The
comparisons raise the precision so that a difference means a fault and not
rounding.

Every phase prints one JSON line (seconds, compile seconds, persistent-cache
hits, the devices its arrays live on, peak device bytes).  The last line is
``{"ok": true, "device": {...}}`` and is printed only if every phase passed.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading
import time
import traceback

import numpy as np

FULL = {
    "train": {"network": "resnet50_v1", "classes": 1000, "image": 224,
              "batch": 32, "steps": 8, "lr": 0.01},
    "serve": {
        "model": {"vocab_size": 50304, "hidden": 2048, "num_layers": 16,
                  "num_heads": 16, "max_len": 4096},
        "engine": {"max_slots": 8, "block_size": 16, "max_prompt_len": 512,
                   "max_new_tokens": 48, "prefill_chunk": 128},
        "prompt_lens": [512, 37, 130, 260, 64, 400, 18, 200],
        # (stream, source stream, shared leading tokens): prefix-cache food
        "shared": [(3, 0, 256), (5, 0, 384)],
        "new_tokens": [32, 48, 24, 40, 48, 24, 36, 30],
        "min_pool_bytes": 1 << 30,
    },
    "kernel": {"shape": (8, 12, 1024, 64), "dtype": "bfloat16"},
    "dp": {"network": "resnet50_v1", "classes": 1000, "image": 224,
           "batch": 32, "lr": 0.01},
    "tp_layers": 4,
}

# sizes of the CPU rehearsal (tests/test_tools.py): control flow only
TINY = {
    "train": {"network": "resnet18_v1", "classes": 10, "image": 32,
              "batch": 4, "steps": 4, "lr": 0.01},
    "serve": {
        "model": {"vocab_size": 48, "hidden": 32, "num_layers": 2,
                  "num_heads": 4, "max_len": 128},
        "engine": {"max_slots": 4, "block_size": 4, "max_prompt_len": 24,
                   "max_new_tokens": 8, "prefill_chunk": 8},
        "prompt_lens": [24, 3, 9, 18, 5, 22, 2, 12],
        "shared": [(3, 0, 16), (5, 0, 16)],
        "new_tokens": [6, 8, 4, 7, 8, 4, 6, 5],
        "min_pool_bytes": 0,
    },
    "kernel": {"shape": (1, 2, 256, 32), "dtype": "float32"},
    "dp": {"network": "resnet18_v1", "classes": 10, "image": 32,
           "batch": 8, "lr": 0.01},
    "tp_layers": 2,
}

# kernel vs dense reference, both read as float32: the reference rounds
# the softmax weights and its output to the input dtype
KERNEL_TOL = {"bfloat16": 2e-2, "float32": 2e-5}
# one optimizer step, four devices against one, as a share of the update,
# with convs at precision "high" (three bf16 passes).  At XLA's default TPU
# precision (one pass) the two programs round differently and fifty layers
# of backward carry that to 7e-2 in the first batch norm while the losses
# agree to 1.5e-8 (PERF.md, PR 21); a fault in the collectives is an error
# of order one in every layer
DP_TOL = 1e-2


class SmokeFailure(Exception):
    """A check of this script did not hold."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


class CompileMeter:
    """Sums XLA compile time and persistent-cache traffic from JAX's own
    monitoring events (any thread: the decode scheduler compiles too)."""

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.compiles = self.hits = self.writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += duration
                self.compiles += 1

    def _event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.writes += 1

    def read(self):
        with self._lock:
            return self.seconds, self.compiles, self.hits, self.writes


def device_names(arrays):
    """Sorted names of the devices that hold ``arrays`` (jax arrays)."""
    return sorted({str(d) for a in arrays for d in a.devices()})


def seeded_batch(cfg, seed):
    """One batch of images in [-1, 1) and integer labels, from ``seed``."""
    rng = np.random.RandomState(seed)
    x = rng.uniform(-1, 1, (cfg["batch"], 3, cfg["image"], cfg["image"]))
    return x.astype(np.float32), rng.randint(0, cfg["classes"], cfg["batch"])


def check_training(rec, losses, steps, state, dev, cache, compiles_at):
    """What both train phases hold a run of ``steps`` steps on one repeated
    batch to; ``state`` maps names to the NDArrays the step carries."""
    state_devices = device_names(v._data for v in state.values())
    rec.update({
        "steps": len(losses), "losses": [round(v, 4) for v in losses],
        "state_arrays": len(state), "state_devices": state_devices,
        "step_signatures": cache["misses"], "step_cache_hits": cache["hits"],
        "xla_compiles_after_step_1": compiles_at[-1] - compiles_at[0],
    })
    check(len(losses) == steps, "ran %d of %d steps" % (len(losses), steps))
    check(all(np.isfinite(losses)), "loss not finite: %r" % losses)
    check(losses[-1] < losses[0],
          "loss did not fall on a repeated batch: %r" % losses)
    check(state_devices == [dev],
          "params/optimizer state on %r, expected %r" % (state_devices, dev))
    check(cache["misses"] == 1 and cache["hits"] == steps - 1,
          "train step recompiled: %r" % (cache,))
    check(rec["xla_compiles_after_step_1"] == 0,
          "%d XLA compiles after step 1" % rec["xla_compiles_after_step_1"])


def run_phase(name, fn, meter):
    """Run one phase, print its line, return whether it passed."""
    import jax
    before = meter.read()
    t0 = time.perf_counter()
    rec = {"phase": name}
    try:
        fn(rec)     # fills rec as it goes: a failed phase keeps its findings
        rec["ok"] = True
    except Exception as exc:   # a failed phase is reported, the rest still run
        traceback.print_exc()
        rec["ok"] = False
        rec["error"] = "%s: %s" % (type(exc).__name__, exc)
    after = meter.read()
    rec["seconds"] = round(time.perf_counter() - t0, 3)
    rec["compile_seconds"] = round(after[0] - before[0], 3)
    rec["compiles"] = after[1] - before[1]
    rec["cache_hits"] = after[2] - before[2]
    rec["cache_writes"] = after[3] - before[3]
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    rec["peak_bytes"] = stats[0].get("peak_bytes_in_use")   # of the process
    rec["bytes_in_use"] = stats[0].get("bytes_in_use")
    if len(stats) > 1:
        rec["bytes_in_use_by_device"] = [s.get("bytes_in_use") for s in stats]
    print(json.dumps(rec), flush=True)
    gc.collect()
    return rec["ok"]


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_train(rec, cfg, seed, meter):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision

    ctx = mx.current_context()
    dev = str(ctx.jax_device())
    mx.random.seed(seed)
    net = vision.get_model(cfg["network"], classes=cfg["classes"])
    sym = mx.sym.SoftmaxOutput(net(mx.sym.var("data")), name="softmax")

    batch, steps, img = cfg["batch"], cfg["steps"], cfg["image"]
    x, y = seeded_batch(cfg, seed)
    train_iter = mx.io.NDArrayIter(np.tile(x, (steps, 1, 1, 1)),
                                   np.tile(y.astype(np.float32), steps),
                                   batch_size=batch)

    losses, batch_devices, compiles_at = [], set(), []

    def on_batch(param):
        losses.append(float(param.eval_metric.get_name_value()[0][1]))
        param.eval_metric.reset()
        for b in param.locals["window"]:
            for arr in list(b.data) + list(b.label):
                batch_devices.update(str(d) for d in arr._data.devices())
        compiles_at.append(meter.read()[1])

    mod = mx.mod.Module(sym)
    mod.fit(train_iter, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": cfg["lr"], "momentum": 0.9},
            eval_metric="ce", initializer=mx.init.Xavier(),
            batch_end_callback=on_batch, metric_interval=1,
            prefetch_to_device=ctx)

    cstep = mod._compiled_step
    check(cstep is not None, "fit() fell back to the eager loop")
    rec.update({
        "model": "%s %dx3x%dx%d float32" % (cfg["network"], batch, img, img),
        "batch_devices": sorted(batch_devices)})
    check_training(rec, losses, steps, cstep.state, dev, cstep.cache_stats(),
                   compiles_at)
    check(sorted(batch_devices) == [dev],
          "batches on %r, expected %r" % (sorted(batch_devices), dev))


def phase_train_bf16(rec, cfg, seed, meter):
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.module.compiled_step import CompiledTrainStep

    dev = str(mx.current_context().jax_device())
    mx.random.seed(seed)
    batch, steps, img = cfg["batch"], cfg["steps"], cfg["image"]
    net = vision.get_model(cfg["network"], classes=cfg["classes"])
    net.initialize(mx.init.Xavier())
    net(nd.zeros((1, 3, img, img)))   # materialize deferred shapes
    net.cast("bfloat16")
    optimizer = mx.optimizer.create("sgd", learning_rate=cfg["lr"],
                                    momentum=0.9, multi_precision=True)

    def ce_loss(outs, label):
        logp = nd.log_softmax(outs[0].astype("float32"))
        return -nd.mean(nd.pick(logp, label.astype("int32"), axis=1))

    cstep = CompiledTrainStep.from_block(net, ce_loss, optimizer)
    x, y = seeded_batch(cfg, seed)
    xb = nd.array(x).astype("bfloat16")
    yb = nd.array(y.astype(np.float32))
    losses, compiles_at = [], []
    for _ in range(steps):
        losses.append(float(cstep.step(xb, yb).asnumpy()[0]))
        compiles_at.append(meter.read()[1])

    dtypes = {}
    for name, arr in cstep.state.items():
        kind = {"p": "params", "o": "optimizer"}[name.split(":")[0]]
        dtypes.setdefault(kind, set()).add(str(arr.dtype))
    rec.update({
        "model": "%s %dx3x%dx%d bfloat16, float32 master weights"
                 % (cfg["network"], batch, img, img),
        "state_dtypes": {k: sorted(v) for k, v in dtypes.items()},
        "batch_devices": device_names([xb._data, yb._data])})
    check_training(rec, losses, steps, cstep.state, dev, cstep.cache_stats(),
                   compiles_at)
    check(rec["state_dtypes"] == {"params": ["bfloat16"],
                                  "optimizer": ["float32"]},
          "state dtypes %r" % rec["state_dtypes"])
    check(rec["batch_devices"] == [dev],
          "batch on %r, expected %r" % (rec["batch_devices"], dev))


def make_prompts(cfg, seed):
    rng = np.random.RandomState(seed + 1)
    vocab = cfg["model"]["vocab_size"]
    prompts = [rng.randint(0, vocab, n).astype(np.int32)
               for n in cfg["prompt_lens"]]
    for dst, src, n in cfg["shared"]:
        prompts[dst][:n] = prompts[src][:n]
    return prompts


def serve_streams(cfg, seed, name, tp=None, num_layers=None, replicas=1,
                  reference_streams=()):
    """Load one decode model into a fleet, stream every prompt through it,
    and return (tokens per stream, the engines' reports, reference tokens
    of ``reference_streams``).  ``tp`` wraps the model in
    ShardedDecodeModel."""
    from mxnet_tpu.serving.decode import (DecodeEngine, ShardedDecodeModel,
                                          TinyCausalLM)
    from mxnet_tpu.serving.fleet import FleetRouter

    model_cfg = dict(cfg["model"], seed=seed)
    if num_layers is not None:
        model_cfg["num_layers"] = num_layers
    ecfg = cfg["engine"]
    # one decode signature: the reference then runs the very executable
    # the scheduler runs, and equal tokens mean equal bits
    width = DecodeEngine.worst_case_width(
        ecfg["max_prompt_len"], ecfg["max_new_tokens"], ecfg["block_size"])

    def factory(engine_name):
        model = TinyCausalLM(**model_cfg)
        if tp is not None:
            model = ShardedDecodeModel(model, tp=tp)
        return DecodeEngine(model, name=engine_name, max_queue=16,
                            prefix_cache=True, width_blocks=[width], **ecfg)

    prompts = make_prompts(cfg, seed)
    router = FleetRouter(replicas=replicas)
    try:
        router.load_decode(name, factory, replicas=replicas, tp=tp)
        # the long prompt first: once its first token is out its pages are
        # registered, and the streams that share its head find them
        first = router.submit_stream(name, prompts[0],
                                     max_new_tokens=cfg["new_tokens"][0])
        deadline = time.monotonic() + 600
        while not first.tokens() and first.status is None:
            check(time.monotonic() < deadline, "no first token in 600 s")
            time.sleep(0.01)
        handles = [first] + [
            router.submit_stream(name, p, max_new_tokens=n)
            for p, n in zip(prompts[1:], cfg["new_tokens"][1:])]
        for h in handles:
            check(h.wait(600), "a stream did not finish in 600 s")
        statuses = sorted({str(h.status) for h in handles})
        check(statuses == ["OK"], "stream statuses %r: %r" % (
            statuses, [h.error for h in handles if h.error]))
        tokens = [list(h.tokens()) for h in handles]
        for t, n in zip(tokens, cfg["new_tokens"]):
            check(len(t) == n, "stream has %d of %d tokens" % (len(t), n))

        # terminal hooks and page frees land just after the last wait()
        engines = [router.engine(name, rid) for rid in
                   router.stats()["decode_models"][name]["placement"]]
        deadline = time.monotonic() + 10
        while any(e.kv_stats()["used"] or e.kv_stats()["reserved"]
                  for e in engines) and time.monotonic() < deadline:
            time.sleep(0.01)
        reports = []
        for eng in engines:
            kv = eng.kv_stats()
            snap = eng.stats_snapshot()
            reports.append({
                "devices": [str(d) for d in eng.devices],
                "placement": eng.placement(),
                "streams_ok": snap["ok"],
                "leaked_blocks": kv["allocated_total"] - kv["freed_total"],
                "steady_state_recompiles": (
                    snap["cache"]["recompiles"]
                    - snap["warmup"]["cache"]["misses"]),
                "prefix_hits": kv["prefix_hits"],
            })
        refs = {i: engines[0].generate_reference(
                    prompts[i], max_new_tokens=cfg["new_tokens"][i]).tolist()
                for i in reference_streams}
        return tokens, reports, refs
    finally:
        router.stop()


def phase_serve(rec, cfg, seed):
    import mxnet_tpu as mx
    dev = mx.current_context().jax_device()
    # references: a plain stream and one that was served from shared pages
    tokens, reports, refs = serve_streams(
        cfg, seed, "lm", reference_streams=(1, cfg["shared"][0][0]))
    (rep,) = reports
    pool_bytes = sum(rep["placement"]["pools"].values())
    rec.update({
        "model": "TinyCausalLM %r" % (cfg["model"],),
        "streams": len(tokens), "tokens_out": sum(len(t) for t in tokens),
        "engine_devices": rep["devices"],
        "param_bytes": rep["placement"]["params"],
        "pool_bytes": rep["placement"]["pools"],
        "leaked_blocks": rep["leaked_blocks"],
        "steady_state_recompiles": rep["steady_state_recompiles"],
        "prefix_hits": rep["prefix_hits"],
        "reference_streams": sorted(refs),
    })
    check(rep["devices"] == [str(dev)],
          "engine names %r, expected %r" % (rep["devices"], str(dev)))
    check(list(rep["placement"]["params"]) == [dev.id],
          "weights on devices %r" % list(rep["placement"]["params"]))
    check(list(rep["placement"]["pools"]) == [dev.id],
          "K/V pools on devices %r" % list(rep["placement"]["pools"]))
    check(pool_bytes >= cfg["min_pool_bytes"],
          "K/V pools hold %d bytes" % pool_bytes)
    check(rep["leaked_blocks"] == 0, "%d leaked K/V blocks"
          % rep["leaked_blocks"])
    check(rep["steady_state_recompiles"] == 0, "%d steady-state recompiles"
          % rep["steady_state_recompiles"])
    check(rep["prefix_hits"] >= 1, "the prefix cache was never hit")
    for i, ref in refs.items():
        check(tokens[i] == ref, "stream %d: %r != reference %r"
              % (i, tokens[i], ref))


def phase_kernel(rec, cfg, seed, interpret):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_ops import (_attention_reference,
                                          flash_attention)

    shape, dtype = cfg["shape"], cfg["dtype"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q, k, v = (jax.random.normal(kk, shape, jnp.float32).astype(dtype)
               for kk in keys)
    scale = 1.0 / np.sqrt(shape[-1])
    fused = jax.jit(lambda a, b, c: flash_attention(
        a, b, c, causal=True, interpret=True if interpret else None))
    rec.update({"shape": list(shape), "dtype": dtype, "causal": True,
                "interpret": interpret, "devices": device_names([q])})
    if interpret:
        out = fused(q, k, v)
    else:
        # run the executable whose text was checked, so that the dense
        # reference cannot stand in for the kernel
        compiled = fused.lower(q, k, v).compile()
        check("tpu_custom_call" in compiled.as_text(),
              "no tpu_custom_call in the compiled attention")
        out = compiled(q, k, v)
    ref = jax.jit(lambda a, b, c: _attention_reference(a, b, c, True,
                                                       scale))(q, k, v)
    out32 = np.asarray(out.astype(jnp.float32))
    ref32 = np.asarray(ref.astype(jnp.float32))
    tol = KERNEL_TOL[dtype]
    excess = np.abs(out32 - ref32) - (tol + tol * np.abs(ref32))
    rec["tolerance"] = "abs %g + rel %g" % (tol, tol)
    rec["max_abs_diff"] = float(np.abs(out32 - ref32).max())
    rec["output_devices"] = device_names([out])
    check(out32.shape == tuple(shape) and np.isfinite(out32).all(),
          "kernel output not finite or of shape %r" % (out32.shape,))
    check(float(excess.max()) <= 0, "kernel differs from the reference by "
          "%g" % rec["max_abs_diff"])


# ---------------------------------------------------------------------------
# four chips: only what exists across chips, and what it is compared with
# ---------------------------------------------------------------------------

def phase_dp4(rec, cfg, seed):
    """One ZeRO data-parallel step (parallel/zero.py: reduce-scatter,
    sharded update, all-gather) over a 4-device mesh, against the same
    global batch on one device.  Each replica normalizes its own quarter of
    the batch, so the one-device side takes the batch a quarter at a time
    and applies the mean gradient in plain numpy."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.block import functional_call, param_values
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel import (init_shard_update_state,
                                    make_data_parallel_train_step, make_mesh,
                                    replicated_spec, shard_batch)

    jax.config.update("jax_default_matmul_precision", "high")
    dp = 4
    devs = jax.devices()[:dp]
    mx.random.seed(seed)
    net = vision.get_model(cfg["network"], classes=cfg["classes"])
    net.initialize(mx.init.Xavier())
    img, batch = cfg["image"], cfg["batch"]
    net(nd.zeros((1, 3, img, img)))   # materialize deferred shapes
    host = {n: np.asarray(v) for n, v in param_values(net).items()}
    frozen = {n for n, p in net.collect_params().items()
              if p.grad_req == "null"}
    train = {n: v for n, v in host.items() if n not in frozen}
    stats = {n: v for n, v in host.items() if n in frozen}
    x, y = seeded_batch(cfg, seed)
    y = y.astype(np.int32)
    lr = cfg["lr"]

    def loss_fn(params, xy):
        xb, yb = xy
        outs, _ = functional_call(net, dict(stats, **params), xb,
                                  training=True)
        logp = jax.nn.log_softmax(outs[0])
        return -jnp.mean(jnp.take_along_axis(logp, yb[:, None], axis=1))

    def sgd_momentum(grads, momenta, params):
        new_m = jax.tree_util.tree_map(lambda m, g: 0.9 * m + g, momenta,
                                       grads)
        new_p = jax.tree_util.tree_map(lambda p, m: p - lr * m, params,
                                       new_m)
        return new_p, new_m

    zeros = {n: np.zeros_like(v) for n, v in train.items()}

    mesh4 = make_mesh(devices=devs)
    step4 = make_data_parallel_train_step(loss_fn, sgd_momentum, mesh4,
                                          shard_update=True)
    p4 = jax.device_put(train, replicated_spec(mesh4))
    s4 = init_shard_update_state(mesh4, train, zeros)
    new4, state4, loss4 = step4(p4, s4, shard_batch(mesh4, (x, y)))

    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    p1 = jax.device_put(train, devs[0])
    quarter = batch // dp
    parts = [grad_fn(p1, jax.device_put((x[i:i + quarter], y[i:i + quarter]),
                                        devs[0]))
             for i in range(0, batch, quarter)]
    loss1 = float(np.mean([float(l) for l, _ in parts]))
    # momentum starts at zero, so the first update is -lr * mean gradient
    delta1 = {n: -lr * np.mean([np.asarray(g[n]) for _, g in parts], axis=0)
              for n in train}

    loss4 = float(loss4)
    # each parameter's difference as a share of its own update, or of a
    # thousandth of the largest update where its own is smaller: a conv bias
    # that feeds a batch norm has a gradient of exactly zero, and what both
    # sides compute for it is rounding
    floor = 1e-3 * max(float(np.abs(d).max()) for d in delta1.values())
    diffs = sorted(
        ((float(np.abs(np.asarray(new4[n]) - old - delta1[n]).max()
                / max(float(np.abs(delta1[n]).max()), floor)), n)
         for n, old in train.items()), reverse=True)
    worst, worst_name = diffs[0]
    shard_bytes = mx.util.bytes_by_device(
        jax.tree_util.tree_leaves(state4["opt"]))
    rec.update({
        "model": "%s %dx3x%dx%d float32" % (cfg["network"], batch, img, img),
        "loss_dp4": loss4, "loss_one_device": loss1,
        "worst_relative_update_diff": worst, "worst_param": worst_name,
        "worst_five": [[n, round(d, 6)] for d, n in diffs[:5]],
        "tolerance": DP_TOL,
        "param_devices": device_names(new4.values()),
        "optimizer_state_bytes_by_device": shard_bytes,
    })
    check(np.isfinite(loss4) and abs(loss4 - loss1) <= DP_TOL * abs(loss1),
          "loss %r on four devices, %r on one" % (loss4, loss1))
    check(worst <= DP_TOL, "%s: updates differ by %g of the update"
          % (worst_name, worst))
    check(len(rec["param_devices"]) == dp, "params on %r"
          % rec["param_devices"])
    check(len(shard_bytes) == dp
          and max(shard_bytes.values()) == min(shard_bytes.values()),
          "optimizer state not split evenly: %r" % shard_bytes)


def phase_tp4(rec, cfg, seed, num_layers):
    import jax
    # the psums of tp=4 add the same products in another order: equal
    # greedy tokens are promised for float32 sums, not for bf16 passes
    jax.config.update("jax_default_matmul_precision", "highest")
    tok1, rep1, _ = serve_streams(cfg, seed, "lm-tp1", num_layers=num_layers)
    gc.collect()
    tok4, rep4, _ = serve_streams(cfg, seed, "lm-tp4", tp=4,
                                  num_layers=num_layers)
    pools1 = sum(rep1[0]["placement"]["pools"].values())
    pools4 = rep4[0]["placement"]["pools"]
    rec.update({
        "model": "TinyCausalLM %r, %d layers" % (cfg["model"], num_layers),
        "streams": len(tok1), "tokens_out": sum(len(t) for t in tok1),
        "tp1_devices": rep1[0]["devices"], "tp4_devices": rep4[0]["devices"],
        "tp1_pool_bytes": pools1, "tp4_pool_bytes_by_device": pools4,
        "tp4_param_bytes_by_device": rep4[0]["placement"]["params"],
        "leaked_blocks": [r["leaked_blocks"] for r in rep1 + rep4],
        "steady_state_recompiles": [r["steady_state_recompiles"]
                                    for r in rep1 + rep4],
    })
    differ = [i for i, (a, b) in enumerate(zip(tok1, tok4)) if a != b]
    check(not differ, "tp=4 tokens differ from tp=1 in streams %r" % differ)
    check(len(pools4) == 4, "tp=4 pools on devices %r" % sorted(pools4))
    check(all(b * 4 == pools1 for b in pools4.values()),
          "tp=4 pool bytes per device %r, tp=1 total %d" % (pools4, pools1))
    check(not any(rec["leaked_blocks"]), "leaked K/V blocks: %r"
          % rec["leaked_blocks"])
    check(not any(rec["steady_state_recompiles"]),
          "steady-state recompiles: %r" % rec["steady_state_recompiles"])


def phase_replicas(rec, cfg, seed, num_layers):
    """Two unsharded replicas behind the router take two chips, each with
    its own weights and pools, and either chip gives the reference's
    tokens (the reference runs on the first replica)."""
    tokens, reports, refs = serve_streams(
        cfg, seed, "lm-x2", replicas=2, num_layers=num_layers,
        reference_streams=range(len(cfg["prompt_lens"])))
    homes = [r["devices"] for r in reports]
    params = [r["placement"]["params"] for r in reports]
    pools = [r["placement"]["pools"] for r in reports]
    rec.update({
        "model": "TinyCausalLM %r, %d layers" % (cfg["model"], num_layers),
        "replica_devices": homes, "param_bytes": params, "pool_bytes": pools,
        "streams": len(tokens), "streams_by_replica": [r["streams_ok"]
                                                       for r in reports],
        "leaked_blocks": [r["leaked_blocks"] for r in reports],
        "steady_state_recompiles": [r["steady_state_recompiles"]
                                    for r in reports],
    })
    check(len(homes) == 2 and homes[0] != homes[1],
          "two replicas on %r" % homes)
    for held, what in ((params, "weights"), (pools, "K/V pools")):
        check(all(len(h) == 1 for h in held)
              and set(held[0]).isdisjoint(held[1]),
              "the replicas' %s are on devices %r" % (what, held))
        check(list(held[0].values()) == list(held[1].values()),
              "the replicas' %s differ in size: %r" % (what, held))
    # the pools shrink with the depth, and with nothing else
    want = cfg["min_pool_bytes"] * num_layers // cfg["model"]["num_layers"]
    check(sum(pools[0].values()) >= want,
          "K/V pools hold %r bytes, expected at least %d" % (pools, want))
    check(all(rec["streams_by_replica"])
          and sum(rec["streams_by_replica"]) == len(tokens),
          "streams served by each replica: %r" % rec["streams_by_replica"])
    differ = [i for i, ref in refs.items() if tokens[i] != ref]
    check(not differ, "streams %r differ from the reference" % differ)
    check(not any(rec["leaked_blocks"]), "leaked K/V blocks: %r"
          % rec["leaked_blocks"])
    check(not any(rec["steady_state_recompiles"]),
          "steady-state recompiles: %r" % rec["steady_state_recompiles"])


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the cross-chip paths (needs 4 chips)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and data")
    ap.add_argument("--rehearse", action="store_true",
                    help=argparse.SUPPRESS)   # tiny sizes, any platform,
    args = ap.parse_args(argv)                # never an ok line

    import jax
    devs = jax.devices()
    on_tpu = devs[0].platform == "tpu"
    if not (on_tpu or args.rehearse):
        print("chip_smoke: JAX's default backend is %r, not a TPU"
              % devs[0].platform, file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print("chip_smoke: --chips %d but JAX sees %d device(s)"
              % (args.chips, len(devs)), file=sys.stderr)
        return 1

    import mxnet_tpu as mx
    cfg = TINY if args.rehearse else FULL
    meter = CompileMeter()
    print(json.dumps({
        "compile_cache_dir": mx.util.compile_cache_dir(),
        "placed_by_JAX_COMPILATION_CACHE_DIR":
            bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "default_context": str(mx.current_context()),
        "seed": args.seed, "chips": args.chips}), flush=True)

    if args.chips == 4:
        phases = [
            ("dp4", lambda rec: phase_dp4(rec, cfg["dp"], args.seed)),
            ("tp4", lambda rec: phase_tp4(rec, cfg["serve"], args.seed,
                                          cfg["tp_layers"])),
            ("replicas", lambda rec: phase_replicas(rec, cfg["serve"],
                                                    args.seed,
                                                    cfg["tp_layers"])),
        ]
    else:
        phases = [
            ("train", lambda rec: phase_train(rec, cfg["train"], args.seed,
                                              meter)),
            ("train_bf16", lambda rec: phase_train_bf16(rec, cfg["train"],
                                                        args.seed, meter)),
            ("serve", lambda rec: phase_serve(rec, cfg["serve"], args.seed)),
            ("kernel", lambda rec: phase_kernel(rec, cfg["kernel"],
                                                args.seed,
                                                interpret=not on_tpu)),
        ]
    # every phase runs, also after a failure: one chip call, all the faults
    passed = [run_phase(name, fn, meter) for name, fn in phases]
    if not all(passed):
        return 1
    if args.rehearse:
        print("chip_smoke: rehearsal done; a rehearsal proves nothing about "
              "the chip and prints no ok line", file=sys.stderr)
        return 2
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
