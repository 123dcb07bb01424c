#!/usr/bin/env python
"""serve_bench — load generator for mxnet_tpu.serving.

Every profile reports its times and rates (``ttft_ms``, ``tokens_per_s``,
``speedup_*``) for whoever runs it on a chip, and gates its exit code on
counts alone: statuses, recompiles, leaked blocks, conservation, bitwise
streams.  The JSON report goes where ``--out`` says, else to stdout.

Seven load profiles:

* ``--profile batch`` (default) — the one-shot inference path: a small
  shape-polymorphic Gluon MLP under concurrent closed-loop clients firing
  a mixed-shape workload; reports throughput, latency percentiles, status
  counts, batching efficiency, and the compile-cache delta (which must be
  zero after warmup).
* ``--profile decode`` — the autoregressive path: hundreds of concurrent
  token streams with mixed prompt/output lengths through the continuous-
  batching DecodeEngine (serving/decode/), then the SAME workload through
  run-to-completion ("static") batching at equal slot count; reports token
  throughput, p50/p99 time-to-first-token, KV pool peak/leak, the
  steady-state recompile count, and the continuous-vs-static speedup.
* ``--profile fleet-decode`` — the stateful decode fleet: the same stream
  workload through ``FleetRouter.submit_stream`` across two replicas with
  one replica DRAINED mid-run, so every one of its live streams hands off
  (prefix + KV pages, lease-fenced) to the survivor; reports token
  throughput and TTFT p50/p99 measured ACROSS the handoff, the handoff
  count, and per-engine recompile/KV-leak gates.  The exit gate requires
  every stream to finish OK despite the drain.
* ``--profile prefix-spec`` — the stacked decode multipliers: a shared-
  prefix storm (one seeded system prompt, per-stream suffixes, a seeded-
  sampling minority) through a chunked-prefill baseline engine and then
  through the SAME workload with copy-on-write prefix caching +
  speculative decoding; reports tok/s, TTFT p50/p99, prefix hit-rate,
  CoW forks, speculative acceptance rate, and recompile/KV-leak gates.
  The exit gate requires fewer full-prompt prefills than streams, fewer
  prefill chunks than the baseline, and accepted speculated tokens.
* ``--profile sharded-decode`` — tensor-parallel serving at an EQUAL
  device budget: the same mixed prompt/output-length stream workload
  (with a seeded-sampling minority) through tp (default 2) unsharded
  engines splitting the streams round-robin, then through ONE
  ``ShardedDecodeModel(tp=...)`` engine — head-sharded K/V pools,
  compute-parallel Megatron kernels — taking every stream; both legs
  consume the same number of devices.  Reports tok/s, TTFT p50/p99,
  per-leg device counts, the per-decode-step collective bill
  (gathers/step == 0, psums/step == 2L+2, bytes/step from the runtime
  counters in ``parallel.collectives``, cross-checked against the
  mxshard static prediction — docs/COLLECTIVE_MAP.md), the sharded
  leg's per-device throughput relative to tp1, and the hard correctness
  gates: every stream OK, zero steady-state recompiles, zero leaked KV
  blocks, static collective/memory predictions == runtime counters,
  every OK stream (greedy AND sampled) token-identical to the
  single-device reference on both legs (tp1 bitwise outright; the
  sharded leg allclose in logits under the psum reduction-order
  relaxation).
* ``--profile disagg`` — disaggregated prefill/decode tiers vs a
  colocated fleet at an EQUAL device budget, under OPEN-loop load: both
  legs replay the identical seeded Poisson arrival trace
  (serving/traffic.py — arrivals fire on the wall clock, nothing waits
  on completions) with tenant mixes and a seeded-sampling minority;
  reports goodput under the p99 TTFT/TPOT SLOs
  (serving/stats.goodput_under_slo), the cross-tier handoff count and
  latency, and the hard gates — arrival-count conservation, cross-tier
  stream conservation, zero steady-state recompiles / leaked KV blocks
  on every engine of both tiers, every OK stream bitwise-equal to the
  single-engine reference.
* ``--profile deploy`` — zero-downtime weight hot-swap under OPEN-loop
  load: a two-replica decode fleet replays a seeded Poisson arrival
  trace while a ``DeploymentController`` (serving/deploy.py) rolls the
  fleet from checkpoint generation 1 to generation 2 MID-TRACE —
  build + warm the new engines outside the router lock, fence, commit,
  drain the old generation onto a same-generation sink, retire.
  Reports the swap duration, per-replica warmup compile counts,
  handoff/fence counts, and TTFT p99 for streams submitted during the
  swap window vs steady state; hard gates — zero dropped streams
  (every arrival terminates OK and the ledger conserves), every OK
  stream bitwise-equal to exactly ONE generation's reference (none
  torn, both generations observed), zero steady-state recompiles on
  the new AND the retired engines, zero leaked KV blocks fleet-wide.

Profiles live in the ``PROFILES`` table (one row each: runner and
pre-import environment); adding a profile is one entry plus its runner.

Usage:
  python tools/serve_bench.py                        # full batch run
  python tools/serve_bench.py --profile decode       # full decode run
  python tools/serve_bench.py --profile fleet-decode # drain-handoff bench
  python tools/serve_bench.py --profile prefix-spec  # stacked multipliers
  python tools/serve_bench.py --profile sharded-decode  # tp=2 vs tp=1
  python tools/serve_bench.py --profile disagg       # open-loop tiers
  python tools/serve_bench.py --profile deploy       # live weight swap
  python tools/serve_bench.py --smoke [--profile decode]  # tier-1 smokes
  python tools/serve_bench.py --clients 16 --requests 64 --out report.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np


def build_model(feat=16, hidden=32, classes=10):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import nn

    class PoolMLP(mx.gluon.HybridBlock):
        """(B, L, feat) -> mean over L -> MLP.  L varies per bucket."""

        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.h = nn.Dense(hidden, activation="relu", in_units=feat)
                self.out = nn.Dense(classes, in_units=hidden)

        def hybrid_forward(self, F, x):
            return self.out(self.h(F.mean(x, axis=1)))

    net = PoolMLP()
    net.initialize(mx.init.Xavier())
    return net


def run_bench(clients, requests_per_client, shapes, max_batch, linger_ms,
              timeout_ms, max_queue):
    from mxnet_tpu import serving

    net = build_model(feat=shapes[0][-1])
    server = serving.ModelServer()
    t0 = time.monotonic()
    model = server.load_model("bench", net, input_shapes=shapes,
                              max_batch=max_batch, linger_ms=linger_ms,
                              max_queue=max_queue)
    warmup_s = time.monotonic() - t0

    rng = np.random.RandomState(0)
    payloads = [rng.randn(*s).astype(np.float32) for s in shapes]
    latencies, statuses = [], {}
    lock = threading.Lock()
    barrier = threading.Barrier(clients)

    def client(cid):
        barrier.wait()
        for i in range(requests_per_client):
            x = payloads[(cid + i) % len(payloads)]
            res = server.predict("bench", x, timeout_ms=timeout_ms)
            with lock:
                statuses[res.status] = statuses.get(res.status, 0) + 1
                if res.status == serving.OK:
                    latencies.append(res.latency_ms)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(clients)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall_s = time.monotonic() - t0

    snap = server.stats()["models"]["bench"]
    server.stop()

    total = clients * requests_per_client
    # same nearest-rank estimator the server's stats() reports, so bench
    # reports and server snapshots agree on what "p99" means
    from mxnet_tpu.serving.stats import LatencyWindow
    window = LatencyWindow(capacity=max(1, len(latencies)))
    for ms in latencies:
        window.add(ms)
    pcts = {k: round(v, 3) for k, v in window.percentiles().items()}

    return {
        "workload": {
            "clients": clients,
            "requests_per_client": requests_per_client,
            "total_requests": total,
            "shapes": [list(s) for s in shapes],
            "max_batch": max_batch,
            "linger_ms": linger_ms,
            "timeout_ms": timeout_ms,
        },
        "warmup_s": round(warmup_s, 3),
        "wall_s": round(wall_s, 3),
        "throughput_rps": round(total / wall_s, 1) if wall_s else 0.0,
        "latency_ms": pcts,
        "statuses": statuses,
        "avg_batch": round(snap["avg_batch"], 3),
        "pad_waste": round(snap["pad_waste"], 4),
        "cache": snap["cache"],
        "warmup": snap["warmup"],
        "steady_state_recompiles": (snap["cache"]["recompiles"]
                                    - snap["warmup"]["cache"]["misses"]),
    }


def run_decode_bench(streams, slots, block_size, max_prompt, max_new, seed,
                     model_cfg):
    """Mixed prompt/output-length stream workload, continuous vs static.

    Both runs see the IDENTICAL stream list (same seeded prompts, same
    per-stream token budgets) on engines with equal slot counts; the only
    difference is the scheduler — iteration-level join/leave vs
    run-to-completion batches — so the speedup isolates continuous
    batching itself.  Two workload/config choices keep the comparison
    honest on that axis: output lengths are bimodal (mostly short, a
    long tail — the production mix run-to-completion batching handles
    worst), and both engines run a SINGLE attention-width signature so a
    decode step costs the same under either scheduler (the bucketed
    width ladder would otherwise hand the static leg a discount: its
    age-aligned batches ride the narrow rungs together).
    """
    from mxnet_tpu.serving.decode import DecodeEngine, TinyCausalLM

    model = TinyCausalLM(**model_cfg)
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, model.vocab_size,
                           rng.randint(1, max_prompt + 1)).tolist()
               for _ in range(streams)]
    budgets = [int(rng.randint(max(2, max_new * 2 // 3), max_new + 1))
               if rng.random() < 0.2
               else int(rng.randint(2, max(3, max_new // 4)))
               for _ in range(streams)]
    max_width = DecodeEngine.worst_case_width(max_prompt, max_new,
                                              block_size)

    def one(scheduling):
        t0 = time.monotonic()
        engine = DecodeEngine(model, name="bench-decode", max_slots=slots,
                              block_size=block_size,
                              max_prompt_len=max_prompt,
                              max_new_tokens=max_new, max_queue=streams,
                              width_blocks=[max_width],
                              scheduling=scheduling)
        warmup_s = time.monotonic() - t0
        t0 = time.monotonic()
        handles = [engine.submit(p, max_new_tokens=m)
                   for p, m in zip(prompts, budgets)]
        tokens = 0
        ttfts = []
        statuses = {}
        for h in handles:
            h.wait()
            statuses[h.status] = statuses.get(h.status, 0) + 1
            tokens += len(h.tokens())
            if h.ttft_ms is not None:
                ttfts.append(h.ttft_ms)
        wall = time.monotonic() - t0
        snap = engine.stats_snapshot()
        kv = engine.kv_stats()
        engine.stop()
        # same nearest-rank estimator the engine's stats_snapshot()
        # reports, so report and snapshot agree on what "p99" means
        from mxnet_tpu.serving.stats import LatencyWindow
        window = LatencyWindow(capacity=max(1, len(ttfts)))
        for ms in ttfts:
            window.add(ms)
        pcts = {k: round(v, 3)
                for k, v in window.percentiles(ps=(50, 99)).items()}
        return {
            "scheduling": scheduling,
            "warmup_s": round(warmup_s, 3),
            "wall_s": round(wall, 3),
            "tokens_out": tokens,
            "tokens_per_s": round(tokens / wall, 1) if wall else 0.0,
            "ttft_ms": pcts,
            "statuses": statuses,
            "prefills": snap["prefills"],
            "steps": snap["steps"],
            "avg_live_slots": round(snap["avg_live_slots"], 2),
            "steady_state_recompiles": (snap["cache"]["recompiles"]
                                        - snap["warmup"]["cache"]["misses"]),
            "kv_peak_blocks": kv["peak_used"],
            "kv_leaked_blocks": kv["allocated_total"] - kv["freed_total"],
        }

    continuous = one("continuous")
    static = one("static")
    speedup = (continuous["tokens_per_s"] / static["tokens_per_s"]
               if static["tokens_per_s"] else 0.0)
    return {
        "profile": "decode",
        "workload": {
            "streams": streams,
            "slots": slots,
            "block_size": block_size,
            "max_prompt_len": max_prompt,
            "max_new_tokens": max_new,
            "seed": seed,
            "model": dict(model_cfg),
        },
        "continuous": continuous,
        "static": static,
        "speedup_tokens_per_s": round(speedup, 3),
    }


def _decode_ok(report):
    """Exit gate for the decode profile: zero steady-state recompiles,
    zero leaked KV blocks, every stream OK, on BOTH schedulers."""
    for leg in (report["continuous"], report["static"]):
        if leg["steady_state_recompiles"] != 0 or leg["kv_leaked_blocks"]:
            return False
        if set(leg["statuses"]) != {"OK"}:
            return False
    return True


def run_fleet_decode_bench(streams, slots, block_size, max_prompt, max_new,
                           seed, model_cfg, replicas=2):
    """Stream workload through the fleet with one replica drained mid-run.

    Every per-replica KV pool is sized to hold the WHOLE stream set, so
    the drain is the only thing under test: with headroom guaranteed on
    the survivor, a single mid-run ``drain()`` must hand every live
    stream off (prefix + KV pages) and every stream must still finish OK
    — throughput and TTFT are measured across the handoff, not around
    it."""
    from mxnet_tpu.serving.decode import DecodeEngine, TinyCausalLM
    from mxnet_tpu.serving.fleet import FleetRouter

    max_width = DecodeEngine.worst_case_width(max_prompt, max_new,
                                              block_size)
    per_stream = -(-(max_prompt + max_new) // block_size)
    num_blocks = streams * per_stream + 1   # +1: the trash block

    def factory(name):
        model = TinyCausalLM(**model_cfg)
        return DecodeEngine(model, name=name, max_slots=slots,
                            block_size=block_size,
                            max_prompt_len=max_prompt,
                            max_new_tokens=max_new, max_queue=streams,
                            num_blocks=num_blocks,
                            width_blocks=[max_width])

    rng = np.random.RandomState(seed)
    vocab = model_cfg["vocab_size"]
    prompts = [rng.randint(0, vocab,
                           rng.randint(1, max_prompt + 1)).tolist()
               for _ in range(streams)]

    t0 = time.monotonic()
    router = FleetRouter(replicas=replicas, failover_budget=2)
    router.load_decode("bench-fleet", factory, replicas=replicas)
    warmup_s = time.monotonic() - t0

    drained = router.stats()["decode_models"]["bench-fleet"]["placement"][0]
    t0 = time.monotonic()
    handles = [router.submit_stream("bench-fleet", p,
                                    max_new_tokens=max_new)
               for p in prompts]
    router.drain(drained)       # mid-run: live streams hand off
    tokens = 0
    ttfts = []
    statuses = {}
    for h in handles:
        h.wait()
        statuses[h.status] = statuses.get(h.status, 0) + 1
        tokens += len(h.tokens())
        if h.ttft_ms is not None:
            ttfts.append(h.ttft_ms)
    wall = time.monotonic() - t0

    # settle: terminal hooks and KV frees land just after the last wait()
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        d = router.decode_stats.snapshot()
        eng = router.stats()["engines"].get("bench-fleet", {})
        if d["requests"] == (d["ok"] + d["timeouts"] + d["errors"]
                             + d["unavailable"]) \
                and all(s["kv"]["used"] == 0 and s["kv"]["reserved"] == 0
                        for s in eng.values()):
            break
        time.sleep(0.005)
    decode = router.decode_stats.snapshot()
    engines = {}
    for rid, snap in sorted(
            router.stats()["engines"].get("bench-fleet", {}).items()):
        kv = snap["kv"]
        engines[rid] = {
            "drained": rid == drained,
            "requests": snap["requests"],
            "imported": snap["imported"],
            "handed_off": snap["handed_off"],
            "steady_state_recompiles": (snap["cache"]["recompiles"]
                                        - snap["warmup"]["cache"]["misses"]),
            "kv_leaked_blocks": (kv["allocated_total"] - kv["freed_total"]),
            "kv_peak_blocks": kv["peak_used"],
        }
    router.stop()

    from mxnet_tpu.serving.stats import LatencyWindow
    window = LatencyWindow(capacity=max(1, len(ttfts)))
    for ms in ttfts:
        window.add(ms)
    pcts = {k: round(v, 3)
            for k, v in window.percentiles(ps=(50, 99)).items()}
    return {
        "profile": "fleet-decode",
        "workload": {
            "streams": streams,
            "slots": slots,
            "block_size": block_size,
            "max_prompt_len": max_prompt,
            "max_new_tokens": max_new,
            "seed": seed,
            "replicas": replicas,
            "model": dict(model_cfg),
        },
        "drained_mid_run": drained,
        "warmup_s": round(warmup_s, 3),
        "wall_s": round(wall, 3),
        "tokens_out": tokens,
        "tokens_per_s": round(tokens / wall, 1) if wall else 0.0,
        "ttft_ms": pcts,
        "statuses": statuses,
        "handoffs": decode["handoffs"],
        "fenced": decode["fenced"],
        "engines": engines,
    }


def _fleet_decode_ok(report):
    """Exit gate for the fleet-decode profile: every stream OK across the
    drain, at least one actual handoff, none fenced away, and zero
    steady-state recompiles / leaked KV blocks on every engine."""
    if set(report["statuses"]) != {"OK"}:
        return False
    if report["handoffs"] < 1 or report["fenced"]:
        return False
    for snap in report["engines"].values():
        if snap["steady_state_recompiles"] != 0 or snap["kv_leaked_blocks"]:
            return False
    return True


def run_prefix_spec_bench(streams, slots, block_size, chunk, max_prompt,
                          max_new, seed, model_cfg, spec_k=3,
                          shared_chunks=4, sampled_every=5):
    """Shared-prefix storm: stacked multipliers vs the plain chunked path.

    Every stream's prompt is the SAME seeded system prefix
    (``shared_chunks`` full prefill chunks) plus a short unique suffix —
    the internet-scale serving shape (one system prompt, many users).
    Both legs run the identical stream list on chunked engines; the only
    difference is the optimization stack:

    * **baseline** — chunked prefill only (no prefix cache, no
      speculation): every stream recomputes the full prompt, every decode
      step emits one token per dispatch.
    * **optimized** — copy-on-write prefix cache + speculative decoding
      with a self-draft (same params as the target, so greedy acceptance
      is 1.0 and the measured win is pure dispatch amortization: one
      unrolled draft call + one verify call commit up to ``spec_k + 1``
      tokens where the baseline spends one dispatch per token — the same
      quantity speculation buys on a real accelerator, where per-step
      launch + HBM reads dominate decode).

    Every ``sampled_every``-th stream runs seeded sampling instead of
    greedy (spec falls back to one verified token per round for those),
    so the report also witnesses sampled-stream replay under the full
    stack.  The first stream is submitted alone as the donor: its
    completed prefill registers the shared prefix the storm then hits."""
    from mxnet_tpu.serving.decode import DecodeEngine, TinyCausalLM

    rng = np.random.RandomState(seed)
    vocab = model_cfg["vocab_size"]
    shared = rng.randint(0, vocab, shared_chunks * chunk).tolist()
    prompts = [shared + rng.randint(0, vocab,
                                    rng.randint(1, max_prompt
                                                - len(shared) + 1)).tolist()
               for _ in range(streams)]
    for i in range(6, streams, 6):
        # exact repeats of the donor prompt: full-prompt hits, whose last
        # chunk recompute lands on ATTACHED pages and CoW-forks while the
        # other holders are live
        prompts[i] = list(prompts[0])
    sampling = [{"temperature": 0.8, "top_k": 12, "seed": 1000 + i}
                if i % sampled_every == sampled_every - 1 else {}
                for i in range(streams)]
    per_stream = -(-(max_prompt + max_new) // block_size)
    num_blocks = (slots + 4) * per_stream + 1

    def one(optimized):
        model = TinyCausalLM(**model_cfg)
        kw = {}
        if optimized:
            kw = dict(prefix_cache=True, spec_k=spec_k,
                      draft_model=TinyCausalLM(**model_cfg))
        t0 = time.monotonic()
        engine = DecodeEngine(model, name="bench-prefix-spec",
                              max_slots=slots, block_size=block_size,
                              max_prompt_len=max_prompt,
                              max_new_tokens=max_new, max_queue=streams,
                              num_blocks=num_blocks, prefill_chunk=chunk,
                              **kw)
        warmup_s = time.monotonic() - t0
        t0 = time.monotonic()
        # donor first: its completed prefill publishes the shared prefix
        donor = engine.submit(prompts[0], max_new_tokens=max_new,
                              **sampling[0])
        donor.wait()
        handles = [donor] + [
            engine.submit(p, max_new_tokens=max_new, **opts)
            for p, opts in zip(prompts[1:], sampling[1:])]
        tokens = 0
        ttfts = []
        statuses = {}
        for h in handles:
            h.wait()
            statuses[h.status] = statuses.get(h.status, 0) + 1
            tokens += len(h.tokens())
            if h.ttft_ms is not None:
                ttfts.append(h.ttft_ms)
        wall = time.monotonic() - t0
        snap = engine.stats_snapshot()
        kv = engine.kv_stats()
        cache = engine.cache_stats()
        engine.stop()
        prefill_chunks = sum(
            rec["hits"] + rec["misses"]
            for sig, rec in cache["signatures"].items()
            if sig.startswith("chunk|"))
        from mxnet_tpu.serving.stats import LatencyWindow
        window = LatencyWindow(capacity=max(1, len(ttfts)))
        for ms in ttfts:
            window.add(ms)
        pcts = {k: round(v, 3)
                for k, v in window.percentiles(ps=(50, 99)).items()}
        return {
            "optimized": optimized,
            "warmup_s": round(warmup_s, 3),
            "wall_s": round(wall, 3),
            "tokens_out": tokens,
            "tokens_per_s": round(tokens / wall, 1) if wall else 0.0,
            "ttft_ms": pcts,
            "statuses": statuses,
            "prefill_chunks": prefill_chunks,
            # streams that computed their WHOLE prompt (no shared pages
            # attached) — the "prefill count" the prefix cache shrinks
            "full_prompt_prefills": snap["requests"] - snap["prefix_hits"],
            "prefix_hits": snap["prefix_hits"],
            "prefix_hit_rate": round(
                snap["prefix_hits"] / max(1, snap["requests"]), 3),
            "prefix_blocks_shared": snap["prefix_blocks_shared"],
            "cow_forks": snap["cow_forks"],
            "spec_proposed": snap["spec_proposed"],
            "spec_accepted": snap["spec_accepted"],
            "spec_accept_rate": round(snap["spec_accept_rate"], 3),
            "steps": snap["steps"],
            "steady_state_recompiles": (snap["cache"]["recompiles"]
                                        - snap["warmup"]["cache"]["misses"]),
            "kv_peak_blocks": kv["peak_used"],
            "kv_leaked_blocks": kv["allocated_total"] - kv["freed_total"],
            "kv_evictions": kv["evictions"],
        }

    baseline = one(False)
    optimized = one(True)
    speedup = (optimized["tokens_per_s"] / baseline["tokens_per_s"]
               if baseline["tokens_per_s"] else 0.0)
    return {
        "profile": "prefix-spec",
        "workload": {
            "streams": streams,
            "slots": slots,
            "block_size": block_size,
            "prefill_chunk": chunk,
            "shared_prefix_tokens": len(shared),
            "max_prompt_len": max_prompt,
            "max_new_tokens": max_new,
            "spec_k": spec_k,
            "sampled_every": sampled_every,
            "seed": seed,
            "model": dict(model_cfg),
        },
        "baseline": baseline,
        "optimized": optimized,
        "speedup_tokens_per_s": round(speedup, 3),
    }


def _prefix_spec_ok(report):
    """Exit gate for the prefix-spec profile: every stream OK, zero
    steady-state recompiles and zero leaked KV blocks on both legs;
    the optimized leg must actually hit the prefix cache (fewer full
    prompt prefills than streams, fewer prefill chunks than the
    baseline) and accept speculated tokens.  ``speedup_tokens_per_s``
    is reported, not gated: a rate means something only on the chip."""
    for leg in (report["baseline"], report["optimized"]):
        if set(leg["statuses"]) != {"OK"}:
            return False
        if leg["steady_state_recompiles"] != 0 or leg["kv_leaked_blocks"]:
            return False
    opt = report["optimized"]
    streams = report["workload"]["streams"]
    if opt["full_prompt_prefills"] >= streams or opt["prefix_hits"] < 1:
        return False
    if opt["prefill_chunks"] >= report["baseline"]["prefill_chunks"]:
        return False
    if opt["spec_proposed"] < 1 or opt["spec_accepted"] < 1:
        return False
    return True


def measure_decode_step_collectives(model_cfg, tp, block_size):
    """Per-decode-step collective cost of the sharded engine, measured
    two independent ways and cross-checked:

    * **runtime** — the per-(kind, axis) counter deltas from
      ``parallel.collectives`` over ONE un-jitted ``decode_fn`` call (the
      shard_map body re-traces per call, so trace-time counts are
      per-step counts);
    * **static** — ``analysis.sharding_lint.predict_decode_step_collectives``
      derived from the compute-parallel kernel structure alone, no
      tracing (``2L + 2`` psums, zero gathers).

    ``static_matches_runtime`` (calls AND bytes, both kinds) is a
    ``_sharded_decode_ok`` exit gate: the lint's abstract sharding model
    must agree with what the wires actually carry."""
    import jax.numpy as jnp
    from mxnet_tpu.analysis.sharding_lint import (
        predict_decode_step_collectives)
    from mxnet_tpu.parallel.collectives import (collective_counters,
                                                collective_totals,
                                                reset_collective_counters)
    from mxnet_tpu.serving.decode import ShardedDecodeModel, TinyCausalLM

    model = ShardedDecodeModel(TinyCausalLM(**model_cfg), tp=tp)
    S, W = 2, 2
    pool_shape = (model.num_layers, S * W + 1, block_size,
                  model.num_heads, model.head_dim)
    k_pool = model.zeros_pool(pool_shape)
    v_pool = model.zeros_pool(pool_shape)
    p = {n: a._data for n, a in model.param_dict().items()}
    reset_collective_counters()
    model.decode_fn(p, jnp.zeros((S,), jnp.int32),
                    jnp.zeros((S,), jnp.int32),
                    jnp.zeros((S, W), jnp.int32),
                    k_pool._data, v_pool._data)
    per_axis = collective_counters()
    totals = collective_totals()
    reset_collective_counters()
    predicted = predict_decode_step_collectives(model, slots=S)
    gathers = totals.get("all_gather", {"calls": 0, "bytes": 0})
    psums = totals.get("psum", {"calls": 0, "bytes": 0})
    return {
        "gathers_per_step": gathers["calls"],
        "psums_per_step": psums["calls"],
        "collective_bytes_per_step": sum(v["bytes"]
                                         for v in totals.values()),
        "per_kind": totals,
        "per_axis": per_axis,
        "static_predicted": predicted,
        "static_matches_runtime": (
            predicted["all_gather"]["calls"] == gathers["calls"]
            and predicted["all_gather"]["bytes"] == gathers["bytes"]
            and predicted["psum"]["calls"] == psums["calls"]
            and predicted["psum"]["bytes"] == psums["bytes"]),
    }


def measure_decode_step_peak_bytes(model_cfg, tp, block_size):
    """Per-decode-step device-memory peak of the sharded engine, measured
    two independent ways and cross-checked:

    * **runtime** — the region-peak bytes from
      ``mxnet_tpu.memory_accounting`` over ONE un-jitted ``decode_fn``
      call under ``track_region("bench:decode-step")`` (the collective
      wrappers record their output temps into the active region);
    * **static** — ``analysis.memory_lint.predict_decode_step_peak_bytes``
      derived from the compute-parallel kernel structure alone, no
      tracing (the psum-output temps are the only collective temps a
      step materializes — the gathered-weight/pool temps are gone).

    ``static_matches_runtime`` (exact bytes) is a ``_sharded_decode_ok``
    exit gate: the lint's abstract footprint model must agree with what
    the accountant actually charges."""
    import jax.numpy as jnp
    from mxnet_tpu.analysis.memory_lint import (
        predict_decode_step_peak_bytes)
    from mxnet_tpu.memory_accounting import (device_memory_stats,
                                             memory_counters,
                                             reset_memory_counters,
                                             track_region)
    from mxnet_tpu.serving.decode import ShardedDecodeModel, TinyCausalLM

    model = ShardedDecodeModel(TinyCausalLM(**model_cfg), tp=tp)
    S, W = 2, 2
    pool_shape = (model.num_layers, S * W + 1, block_size,
                  model.num_heads, model.head_dim)
    k_pool = model.zeros_pool(pool_shape)
    v_pool = model.zeros_pool(pool_shape)
    p = {n: a._data for n, a in model.param_dict().items()}
    reset_memory_counters()
    with track_region("bench:decode-step"):
        model.decode_fn(p, jnp.zeros((S,), jnp.int32),
                        jnp.zeros((S,), jnp.int32),
                        jnp.zeros((S, W), jnp.int32),
                        k_pool._data, v_pool._data)
    region = memory_counters().get("bench:decode-step",
                                   {"temps": 0, "peak_bytes": 0,
                                    "live_bytes": 0})
    reset_memory_counters()
    predicted = predict_decode_step_peak_bytes(model, slots=S)
    return {
        "region": "bench:decode-step",
        "temps_per_step": region["temps"],
        "runtime_peak_bytes": region["peak_bytes"],
        "static_predicted_peak_bytes": predicted,
        "live_bytes_after": region["live_bytes"],
        "static_matches_runtime": predicted == region["peak_bytes"],
        "device_memory_stats_available": device_memory_stats() is not None,
    }


def run_sharded_decode_bench(streams, slots, block_size, max_prompt,
                             max_new, seed, model_cfg, tp=2):
    """Tensor-parallel vs replicated decode at an equal device budget.

    The ``tp1`` leg runs ``tp`` independent single-device engines and
    splits the stream list round-robin across them; the ``tp2`` leg runs
    ONE engine over ``ShardedDecodeModel(tp=tp)`` — head-sharded K/V
    pools, compute-parallel Megatron kernels — and takes every stream.
    Both legs consume exactly ``tp`` devices, see the identical seeded
    workload (mixed prompt and output lengths, every 4th stream
    seeded-sampled), and are held to the same bar: every stream's tokens
    TOKEN-identical to the single-device reference for its (prompt,
    budget, sampling) triple (the tp1 leg is bitwise outright; the
    sharded leg's logits are allclose under the documented psum
    reduction-order relaxation, and its greedy/sampled token streams
    must still match exactly).  With the gather tax gone the sharded
    leg's per-device throughput is gated at >= 0.8x of tp1 — each device
    runs 1/tp of the FLOPs and pays ``2L + 2`` small psums per step."""
    from mxnet_tpu.serving.decode import (DecodeEngine, ShardedDecodeModel,
                                          TinyCausalLM)

    max_width = DecodeEngine.worst_case_width(max_prompt, max_new,
                                              block_size)
    per_stream = -(-(max_prompt + max_new) // block_size)
    rng = np.random.RandomState(seed)
    vocab = model_cfg["vocab_size"]
    prompts = [rng.randint(0, vocab,
                           rng.randint(1, max_prompt + 1)).tolist()
               for _ in range(streams)]
    budgets = [int(rng.randint(2, max_new + 1)) for _ in range(streams)]
    sampling = [{"temperature": 0.8, "top_k": 8, "seed": 2000 + i}
                if i % 4 == 3 else {} for i in range(streams)]

    # single-device references: the token-identity bar for BOTH legs
    ref_eng = DecodeEngine(TinyCausalLM(**model_cfg), name="bench-shard-ref",
                           max_slots=slots, block_size=block_size,
                           max_prompt_len=max_prompt,
                           max_new_tokens=max_new, max_queue=streams,
                           num_blocks=streams * per_stream + 1,
                           width_blocks=[max_width])
    try:
        refs = [ref_eng.generate_reference(p, b, **opts).tolist()
                for p, b, opts in zip(prompts, budgets, sampling)]
    finally:
        ref_eng.stop()

    def one(tp_degree, n_engines):
        share = -(-streams // n_engines)

        def build(i):
            model = TinyCausalLM(**model_cfg)
            if tp_degree > 1:
                model = ShardedDecodeModel(model, tp=tp_degree)
            return DecodeEngine(model,
                                name="bench-shard-tp%d-%d" % (tp_degree, i),
                                max_slots=slots, block_size=block_size,
                                max_prompt_len=max_prompt,
                                max_new_tokens=max_new, max_queue=streams,
                                num_blocks=share * per_stream + 1,
                                width_blocks=[max_width])

        t0 = time.monotonic()
        engines = [build(i) for i in range(n_engines)]
        warmup_s = time.monotonic() - t0
        t0 = time.monotonic()
        handles = [engines[i % n_engines].submit(p, max_new_tokens=b,
                                                 **opts)
                   for i, (p, b, opts) in enumerate(zip(prompts, budgets,
                                                        sampling))]
        tokens = 0
        ttfts = []
        statuses = {}
        token_equal = True
        for i, h in enumerate(handles):
            h.wait()
            statuses[h.status] = statuses.get(h.status, 0) + 1
            toks = list(h.tokens())
            tokens += len(toks)
            if h.status == "OK" and toks != refs[i]:
                token_equal = False
            if h.ttft_ms is not None:
                ttfts.append(h.ttft_ms)
        wall = time.monotonic() - t0
        recompiles = leaked = peak = devices = 0
        for e in engines:
            snap = e.stats_snapshot()
            kv = e.kv_stats()
            recompiles += (snap["cache"]["recompiles"]
                           - snap["warmup"]["cache"]["misses"])
            leaked += kv["allocated_total"] - kv["freed_total"]
            peak += kv["peak_used"]
            devices += e.tp_degree
            e.stop()
        from mxnet_tpu.serving.stats import LatencyWindow
        window = LatencyWindow(capacity=max(1, len(ttfts)))
        for ms in ttfts:
            window.add(ms)
        pcts = {k: round(v, 3)
                for k, v in window.percentiles(ps=(50, 99)).items()}
        return {
            "tp_degree": tp_degree,
            "engines": n_engines,
            "devices": devices,
            "warmup_s": round(warmup_s, 3),
            "wall_s": round(wall, 3),
            "tokens_out": tokens,
            "tokens_per_s": round(tokens / wall, 1) if wall else 0.0,
            "ttft_ms": pcts,
            "statuses": statuses,
            "token_equal_reference": token_equal,
            "steady_state_recompiles": recompiles,
            "kv_peak_blocks": peak,
            "kv_leaked_blocks": leaked,
        }

    tp1 = one(1, tp)
    tp2 = one(tp, 1)
    collectives = measure_decode_step_collectives(model_cfg, tp,
                                                  block_size)
    memory = measure_decode_step_peak_bytes(model_cfg, tp, block_size)
    return {
        "profile": "sharded-decode",
        "collectives": collectives,
        "memory": memory,
        "workload": {
            "streams": streams,
            "slots": slots,
            "block_size": block_size,
            "max_prompt_len": max_prompt,
            "max_new_tokens": max_new,
            "sampled_every": 4,
            "tp": tp,
            "seed": seed,
            "model": dict(model_cfg),
        },
        "tp1": tp1,
        "tp2": tp2,
        "relative_tokens_per_s": (round(tp2["tokens_per_s"]
                                        / tp1["tokens_per_s"], 3)
                                  if tp1["tokens_per_s"] else 0.0),
    }


def _sharded_decode_ok(report):
    """Exit gate for the sharded-decode profile: on BOTH equal-device
    legs every stream finishes OK, every OK stream (greedy and sampled)
    is token-identical to the single-device reference, and zero
    steady-state recompiles / leaked KV blocks; the legs must actually
    consume the same device count and the sharded leg must report the
    declared tp_degree.  The static collective AND memory models must
    both match the measured per-step reality exactly (calls, bytes, and
    peak-bytes), the decode step must pay ZERO gathers (the count that
    says the gather-at-use wrapper is gone), and the decode-step
    accounting region must drain.  ``relative_tokens_per_s`` is
    reported, not gated: a rate means something only on the chip."""
    for leg in (report["tp1"], report["tp2"]):
        if set(leg["statuses"]) != {"OK"}:
            return False
        if not leg["token_equal_reference"]:
            return False
        if leg["steady_state_recompiles"] != 0 or leg["kv_leaked_blocks"]:
            return False
    if report["tp1"]["devices"] != report["tp2"]["devices"]:
        return False
    if report["tp2"]["tp_degree"] != report["workload"]["tp"]:
        return False
    if not report["collectives"]["static_matches_runtime"]:
        return False
    if report["collectives"]["gathers_per_step"] != 0:
        return False
    mem = report["memory"]
    if not mem["static_matches_runtime"]:
        return False
    if mem["runtime_peak_bytes"] <= 0 or mem["live_bytes_after"] != 0:
        return False
    return True


def run_disagg_bench(rate_hz, duration_s, slots, block_size, chunk,
                     max_prompt, max_new, seed, model_cfg, devices=4,
                     prefill_replicas=None, slo_ttft_ms=250.0,
                     slo_tpot_ms=150.0, time_scale=1.0):
    """Disaggregated vs colocated serving at an EQUAL device budget,
    under OPEN-loop load.

    Both legs replay the IDENTICAL seeded Poisson arrival trace
    (serving/traffic.py) with the same prompts, budgets, tenants, and
    seeded-sampling minority — arrivals fire on the wall clock whether
    or not the system keeps up, so tail latency is earned, not
    negotiated.  The **colocated** leg runs ``devices`` full chunked
    engines behind one ``FleetRouter``; the **disagg** leg splits the
    same device count into a prefill-only tier and a decode tier behind
    a ``DisaggRouter`` (every stream hands off at its first token).
    The headline number is goodput under the p99 TTFT/TPOT SLOs
    (serving/stats.goodput_under_slo); the hard gates are
    arrival-count conservation, cross-tier stream conservation, zero
    steady-state recompiles and zero leaked KV blocks on every engine
    of both legs, and every OK stream BITWISE-equal to the single-
    engine reference for its (prompt, budget, sampling) triple."""
    from mxnet_tpu.memory_accounting import (memory_counters,
                                             reset_memory_counters)
    from mxnet_tpu.serving import traffic
    from mxnet_tpu.serving.decode import DecodeEngine, TinyCausalLM
    from mxnet_tpu.serving.disagg import DisaggRouter
    from mxnet_tpu.serving.fleet import FleetRouter
    from mxnet_tpu.serving.stats import goodput_under_slo

    if prefill_replicas is None:
        prefill_replicas = max(1, devices // 2)
    decode_replicas = devices - prefill_replicas
    if decode_replicas < 1:
        raise ValueError("need devices > prefill_replicas")

    arrivals = traffic.poisson_trace(rate_hz, duration_s, seed=seed)
    tenants = traffic.tenant_mix(arrivals, {"free": 1.0, "paid": 3.0},
                                 seed=seed)
    n = len(arrivals)
    rng = np.random.RandomState(seed)
    vocab = model_cfg["vocab_size"]
    prompts = [rng.randint(0, vocab,
                           rng.randint(1, max_prompt + 1)).tolist()
               for _ in range(n)]
    budgets = [int(rng.randint(2, max_new + 1)) for _ in range(n)]
    sampling = [{"temperature": 0.8, "top_k": 8, "seed": 3000 + i}
                if i % 4 == 3 else {} for i in range(n)]
    max_width = DecodeEngine.worst_case_width(max_prompt, max_new,
                                              block_size)
    per_stream = -(-(max_prompt + max_new) // block_size)
    # KV capacity off the table on both legs (every engine could hold the
    # whole trace): the axis under test is tier interference, not memory
    num_blocks = n * per_stream + 1

    def full_engine(name):
        return DecodeEngine(TinyCausalLM(**model_cfg), name=name,
                            max_slots=slots, block_size=block_size,
                            max_prompt_len=max_prompt,
                            max_new_tokens=max_new, max_queue=max(8, n),
                            num_blocks=num_blocks,
                            width_blocks=[max_width], prefill_chunk=chunk)

    def prefill_engine(name):
        return DecodeEngine(TinyCausalLM(**model_cfg), name=name,
                            max_slots=slots, block_size=block_size,
                            max_prompt_len=max_prompt,
                            max_new_tokens=max_new, max_queue=max(8, n),
                            num_blocks=num_blocks, prefill_chunk=chunk,
                            prefill_only=True)

    ref_eng = full_engine("bench-disagg-ref")
    try:
        refs = [ref_eng.generate_reference(p, b, **opts).tolist()
                for p, b, opts in zip(prompts, budgets, sampling)]
    finally:
        ref_eng.stop()
    # clean HBM-accountant slate for the two measured legs: every kv:*
    # region charged from here on belongs to a leg engine
    reset_memory_counters()

    def drive(submit_stream, ledger, engine_snaps, extra=None):
        """Replay the trace open-loop and account one leg."""
        handles = [None] * n

        def submit(i, _t):
            handles[i] = submit_stream(
                "bench-disagg", prompts[i], max_new_tokens=budgets[i],
                tenant=tenants[i], **sampling[i])

        t0 = time.monotonic()
        fired = traffic.replay(arrivals, submit, time_scale=time_scale)
        for h in handles:
            h.wait(60.0)
        wall = time.monotonic() - t0
        rows, bitwise = [], True
        statuses = {}
        for i, h in enumerate(handles):
            status, toks, ttft, latency, _err = h.snapshot()
            statuses[status] = statuses.get(status, 0) + 1
            rows.append({"status": status, "ttft_ms": ttft,
                         "latency_ms": latency, "tokens": len(toks)})
            if status == "OK" and list(toks) != refs[i]:
                bitwise = False
        # settle: terminal hooks and KV frees land just after last wait()
        conserved = pools_whole = False
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            d = ledger()
            conserved = d["requests"] == (d["ok"] + d["timeouts"]
                                          + d["errors"] + d["unavailable"])
            snaps = engine_snaps()
            pools_whole = all(
                s["kv"]["used"] == 0 and s["kv"]["reserved"] == 0
                for s in snaps.values())
            if conserved and pools_whole:
                break
            time.sleep(0.005)
        snaps = engine_snaps()
        engines = {}
        for key, s in sorted(snaps.items()):
            kv = s["kv"]
            engines[key] = {
                "requests": s["requests"],
                "imported": s["imported"],
                "handed_off": s["handed_off"],
                "steady_state_recompiles": (
                    s["cache"]["recompiles"]
                    - s["warmup"]["cache"]["misses"]),
                "kv_leaked_blocks": (kv["allocated_total"]
                                     - kv["freed_total"]),
                "kv_peak_blocks": kv["peak_used"],
            }
        good = goodput_under_slo(rows, slo_ttft_ms=slo_ttft_ms,
                                 slo_tpot_ms=slo_tpot_ms)
        leg = {
            "arrivals": n,
            "fired": fired,
            "wall_s": round(wall, 3),
            "statuses": statuses,
            "goodput": good,
            "goodput_per_s": round(good["good"] / wall, 2) if wall else 0.0,
            "bitwise_equal_reference": bitwise,
            "conserved": conserved,
            "pools_whole": pools_whole,
            "engines": engines,
        }
        if extra:
            leg.update(extra())
        return leg

    # -- colocated leg ---------------------------------------------------
    t0 = time.monotonic()
    router = FleetRouter(replicas=devices, failover_budget=2)
    router.load_decode("bench-disagg", full_engine, replicas=devices)
    colo_warm = time.monotonic() - t0
    try:
        colocated = drive(
            router.submit_stream,
            lambda: router.decode_stats.snapshot(),
            lambda: {rid: s for rid, s in router.stats()["engines"]
                     .get("bench-disagg", {}).items()})
    finally:
        router.stop()
    colocated["warmup_s"] = round(colo_warm, 3)
    colocated["devices"] = devices

    # -- disaggregated leg (same device count, split) --------------------
    t0 = time.monotonic()
    dr = DisaggRouter(prefill_replicas=prefill_replicas,
                      decode_replicas=decode_replicas, failover_budget=2)
    dr.load("bench-disagg", prefill_engine, full_engine,
            prefill_replicas=prefill_replicas,
            decode_replicas=decode_replicas)
    disagg_warm = time.monotonic() - t0

    def disagg_engines():
        stats = dr.stats()
        out = {}
        for tier in ("prefill", "decode"):
            for rid, s in stats[tier]["engines"] \
                    .get("bench-disagg", {}).items():
                out["%s/%s" % (tier, rid)] = s
        return out

    try:
        disagg = drive(
            dr.submit_stream,
            lambda: dr.prefill.decode_stats.snapshot(),
            disagg_engines,
            extra=lambda: {"handoffs": dr.stats()["disagg"]})
    finally:
        dr.stop()
    disagg["warmup_s"] = round(disagg_warm, 3)
    disagg["devices"] = devices
    disagg["prefill_replicas"] = prefill_replicas
    disagg["decode_replicas"] = decode_replicas

    speedup = (disagg["goodput_per_s"] / colocated["goodput_per_s"]
               if colocated["goodput_per_s"] else 0.0)
    # fleet-wide HBM accounting across BOTH legs' engines: every KV-block
    # region must drain (alloc == freed, zero live) once the engines
    # stop; the :pools subregions are alloc-only (engine-lifetime pools)
    # and the :import subregions record balanced handoff staging, so the
    # balance gate reads only the block-ledger regions
    kv_regions = {r: c for r, c in memory_counters().items()
                  if r.startswith("kv:")}
    blocks = {r: c for r, c in kv_regions.items()
              if not r.endswith((":pools", ":import"))}
    memory = {
        "kv_regions": len(kv_regions),
        "kv_alloc_bytes": sum(c["alloc_bytes"]
                              for c in kv_regions.values()),
        "kv_freed_bytes": sum(c["freed_bytes"]
                              for c in kv_regions.values()),
        # block-ledger live bytes: must drain to zero once engines stop
        "kv_live_bytes": sum(c["live_bytes"] for c in blocks.values()),
        # engine-lifetime pools: charged once at warmup, never freed
        "kv_pool_bytes": sum(c["live_bytes"]
                             for r, c in kv_regions.items()
                             if r.endswith(":pools")),
        "kv_peak_bytes": sum(c["peak_bytes"]
                             for c in kv_regions.values()),
        "balanced": bool(blocks) and all(
            c["alloc_bytes"] == c["freed_bytes"] and c["live_bytes"] == 0
            for c in blocks.values()),
    }
    return {
        "profile": "disagg",
        "memory": memory,
        "workload": {
            "rate_hz": rate_hz,
            "duration_s": duration_s,
            "time_scale": time_scale,
            "arrivals": n,
            "slots": slots,
            "block_size": block_size,
            "prefill_chunk": chunk,
            "max_prompt_len": max_prompt,
            "max_new_tokens": max_new,
            "devices": devices,
            "slo_p99_ttft_ms": slo_ttft_ms,
            "slo_p99_tpot_ms": slo_tpot_ms,
            "tenant_weights": {"free": 1.0, "paid": 3.0},
            "sampled_every": 4,
            "seed": seed,
            "model": dict(model_cfg),
        },
        "colocated": colocated,
        "disagg": disagg,
        "speedup_goodput": round(speedup, 3),
    }


def _disagg_ok(report):
    """Exit gate for the disagg profile: both equal-device legs replay
    the full trace (arrival-count conservation), settle their stream
    conservation ledgers, keep every KV pool whole with zero leaks and
    zero steady-state recompiles on every engine (both tiers), and
    every OK stream is bitwise-equal to the reference; the disagg leg
    must actually hand off (at least one cross-tier handoff, none
    failed), and the HBM accountant's KV block regions must drain across
    both legs (``memory.balanced``).  The >= 1.2x goodput bar is
    reported, not gated — on a
    shared-core CPU host the tiers contend for the same silicon (see
    the report's ``speedup_goodput`` and docs/SERVING.md)."""
    for leg in (report["colocated"], report["disagg"]):
        if leg["fired"] != leg["arrivals"]:
            return False
        if not (leg["conserved"] and leg["pools_whole"]
                and leg["bitwise_equal_reference"]):
            return False
        for snap in leg["engines"].values():
            if snap["steady_state_recompiles"] != 0 \
                    or snap["kv_leaked_blocks"]:
                return False
    hand = report["disagg"]["handoffs"]
    if hand["handoffs"] < 1 or hand["handoff_failures"]:
        return False
    if report["colocated"]["devices"] != report["disagg"]["devices"]:
        return False
    mem = report["memory"]
    if not mem["balanced"] or mem["kv_alloc_bytes"] <= 0:
        return False
    return True


def run_deploy_bench(rate_hz, duration_s, slots, block_size, max_prompt,
                     max_new, seed, model_cfg, replicas=2, time_scale=1.0):
    """Live weight hot-swap under OPEN-loop load (serving/deploy.py).

    One ``FleetRouter`` (``replicas`` decode replicas) serves a seeded
    Poisson arrival trace while a ``DeploymentController`` rolls the
    fleet from checkpoint generation 1 to generation 2 MID-TRACE (the
    swap triggers once ~10% of arrivals have fired).  Two weight
    generations exist on disk as manifest-committed checkpoints; the
    per-generation greedy/sampled references make "every stream finishes
    against exactly one weight generation" checkable bitwise.  Hard
    gates: zero dropped streams (every arrival terminates OK and the
    ledger conserves), both generations observed among the OK streams
    (the swap really overlapped traffic), zero steady-state recompiles
    on the NEW engines and on the RETIRED generation-1 engines, and zero
    leaked KV blocks fleet-wide (HBM accountant).  TTFT p99 for streams
    submitted during the swap window is reported beside the steady-state
    p99, not gated."""
    import shutil
    import tempfile

    from mxnet_tpu import model as model_mod
    from mxnet_tpu import symbol as sym_mod
    from mxnet_tpu.memory_accounting import (memory_counters,
                                             reset_memory_counters)
    from mxnet_tpu.serving import traffic
    from mxnet_tpu.serving.decode import DecodeEngine, TinyCausalLM
    from mxnet_tpu.serving.deploy import DeploymentController
    from mxnet_tpu.serving.fleet import FleetRouter

    arrivals = traffic.poisson_trace(rate_hz, duration_s, seed=seed)
    n = len(arrivals)
    rng = np.random.RandomState(seed)
    vocab = model_cfg["vocab_size"]
    prompts = [rng.randint(0, vocab,
                           rng.randint(1, max_prompt + 1)).tolist()
               for _ in range(n)]
    budgets = [int(rng.randint(2, max_new + 1)) for _ in range(n)]
    sampling = [{"temperature": 0.8, "top_k": 8, "seed": 3000 + i}
                if i % 4 == 3 else {} for i in range(n)]
    max_width = DecodeEngine.worst_case_width(max_prompt, max_new,
                                              block_size)
    per_stream = -(-(max_prompt + max_new) // block_size)
    # KV capacity off the table (any engine could hold the whole trace):
    # the axis under test is the swap, not memory pressure
    num_blocks = n * per_stream + 1
    engine_kw = dict(max_slots=slots, block_size=block_size,
                     max_prompt_len=max_prompt, max_new_tokens=max_new,
                     max_queue=max(8, n), num_blocks=num_blocks,
                     width_blocks=[max_width])

    # two weight generations, published as manifest-committed checkpoints
    gen_cfg = {1: dict(model_cfg),
               2: dict(model_cfg, seed=model_cfg["seed"] + 1)}
    tmpdir = tempfile.mkdtemp(prefix="serve-bench-deploy-")
    prefix = os.path.join(tmpdir, "ck")
    refs = {}
    try:
        for gen, cfg in sorted(gen_cfg.items()):
            lm = TinyCausalLM(**cfg)
            model_mod.save_checkpoint(prefix, gen, sym_mod.Variable("data"),
                                      dict(lm._params), {})
            ref_eng = DecodeEngine(TinyCausalLM(**cfg),
                                   name="bench-deploy-ref%d" % gen,
                                   **engine_kw)
            try:
                refs[gen] = [ref_eng.generate_reference(p, b,
                                                        **opts).tolist()
                             for p, b, opts in zip(prompts, budgets,
                                                   sampling)]
            finally:
                ref_eng.stop()

        def builder(srv_name, arg_params, aux_params, generation):
            return DecodeEngine(
                TinyCausalLM(params=arg_params, **gen_cfg[1]),
                name=srv_name, generation=generation, **engine_kw)

        reset_memory_counters()
        t0_warm = time.monotonic()
        router = FleetRouter(replicas=replicas, failover_budget=2)
        router.load_decode(
            "bench-deploy",
            lambda nm: DecodeEngine(TinyCausalLM(**gen_cfg[1]), name=nm,
                                    **engine_kw),
            replicas=replicas)
        ctl = DeploymentController(router, prefix,
                                   engines={"bench-deploy": builder})
        boot = ctl.deploy(1)
        assert boot["status"] == "deployed", boot
        warmup_s = time.monotonic() - t0_warm
        # hold the generation-1 engines: their recompile gate outlives
        # their retirement
        placement = router.stats()["decode_models"]["bench-deploy"][
            "placement"]
        old_engines = [router.engine("bench-deploy", rid)
                       for rid in placement]

        handles = [None] * n
        submit_t = [None] * n
        swap_at = max(1, n // 10)
        swap_trigger = threading.Event()
        swap_result = {}

        def submit(i, _t):
            submit_t[i] = time.monotonic()
            handles[i] = router.submit_stream(
                "bench-deploy", prompts[i], max_new_tokens=budgets[i],
                **sampling[i])
            if i + 1 == swap_at:
                swap_trigger.set()

        def swapper():
            if not swap_trigger.wait(60.0):
                return
            swap_result["t0"] = time.monotonic()
            try:
                swap_result["report"] = ctl.deploy(2)
            except Exception as exc:      # surfaces in the gate
                swap_result["error"] = "%s: %s" % (type(exc).__name__, exc)
            swap_result["t1"] = time.monotonic()

        swap_thread = threading.Thread(target=swapper, daemon=True)
        swap_thread.start()
        wall0 = time.monotonic()
        fired = traffic.replay(arrivals, submit, time_scale=time_scale)
        for h in handles:
            if h is not None:
                h.wait(60.0)
        swap_thread.join(120.0)
        wall = time.monotonic() - wall0

        # deterministic post-swap probes: whatever the trace/swap timing
        # race produced, these streams run on the FINAL generation and
        # must match ITS reference bitwise (and, with the engine gate
        # below, without a single recompile)
        final_gen = (2 if (swap_result.get("report") or {}).get(
            "status") == "deployed" else 1)
        probe_rows = []
        probe_handles = [(i, router.submit_stream(
            "bench-deploy", prompts[i], max_new_tokens=budgets[i],
            **sampling[i])) for i in range(min(4, n))]
        probes_bitwise = True
        for i, h in probe_handles:
            h.wait(30.0)
            status, toks, _t, _l, _e = h.snapshot()
            probe_rows.append({"status": status, "tokens": len(toks)})
            if status != "OK" or list(toks) != refs[final_gen][i]:
                probes_bitwise = False

        # settle the ledger and the pools before reading the gates
        conserved = pools_whole = False
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            d = router.decode_stats.snapshot()
            conserved = d["requests"] == (d["ok"] + d["timeouts"]
                                          + d["errors"]
                                          + d["unavailable"])
            snaps = router.stats()["engines"].get("bench-deploy", {})
            pools_whole = all(
                s["kv"]["used"] == 0 and s["kv"]["reserved"] == 0
                for s in snaps.values())
            if conserved and pools_whole:
                break
            time.sleep(0.005)

        # per-stream verdicts: every OK stream must equal ONE
        # generation's reference bitwise; swap-window membership comes
        # from the submit timestamp
        statuses = {}
        rows_in, rows_out = [], []
        ok_by_gen = {1: 0, 2: 0}
        torn = 0
        t_sw0 = swap_result.get("t0")
        t_sw1 = swap_result.get("t1")
        for i, h in enumerate(handles):
            status, toks, ttft, latency, _err = h.snapshot()
            statuses[status] = statuses.get(status, 0) + 1
            in_window = (t_sw0 is not None and t_sw1 is not None
                         and t_sw0 <= submit_t[i] <= t_sw1)
            (rows_in if in_window else rows_out).append(
                {"status": status, "ttft_ms": ttft,
                 "latency_ms": latency, "tokens": len(toks)})
            if status == "OK":
                toks = list(toks)
                m1 = toks == refs[1][i]
                m2 = toks == refs[2][i]
                if m1 and not m2:
                    ok_by_gen[1] += 1
                elif m2 and not m1:
                    ok_by_gen[2] += 1
                elif not m1 and not m2:
                    torn += 1
        if probes_bitwise:
            ok_by_gen[final_gen] += len(probe_handles)

        def p99(rows):
            vals = sorted(r["ttft_ms"] for r in rows
                          if r["ttft_ms"] is not None)
            if not vals:
                return None
            return vals[min(len(vals) - 1,
                            int(round(0.99 * (len(vals) - 1))))]

        ttft_in, ttft_out = p99(rows_in), p99(rows_out)
        engines = {}
        snaps = router.stats()["engines"].get("bench-deploy", {})
        for rid, s in sorted(snaps.items()):
            kv = s["kv"]
            engines[rid] = {
                "generation": s.get("generation"),
                "requests": s["requests"],
                "imported": s["imported"],
                "handed_off": s["handed_off"],
                "steady_state_recompiles": (
                    s["cache"]["recompiles"]
                    - s["warmup"]["cache"]["misses"]),
                "kv_leaked_blocks": (kv["allocated_total"]
                                     - kv["freed_total"]),
                "kv_peak_blocks": kv["peak_used"],
            }
        # the retired generation-1 engines: lived from warmup through
        # retirement — any miss beyond their warmup is a swap-caused
        # recompile
        retired = {}
        for eng in old_engines:
            retired[eng.name] = {
                "steady_state_recompiles": (
                    eng.cache_stats()["misses"]
                    - eng.warmup_report["cache"]["misses"]),
            }
        deploy_stats = router.stats()["deploy"]
        router.stop()

        kv_regions = {r: c for r, c in memory_counters().items()
                      if r.startswith("kv:")}
        blocks = {r: c for r, c in kv_regions.items()
                  if not r.endswith((":pools", ":import"))}
        memory = {
            "kv_regions": len(kv_regions),
            "kv_alloc_bytes": sum(c["alloc_bytes"]
                                  for c in kv_regions.values()),
            "kv_live_bytes": sum(c["live_bytes"]
                                 for c in blocks.values()),
            "balanced": bool(blocks) and all(
                c["alloc_bytes"] == c["freed_bytes"]
                and c["live_bytes"] == 0 for c in blocks.values()),
        }
        swap_report = swap_result.get("report")
        return {
            "profile": "deploy",
            "workload": {
                "rate_hz": rate_hz,
                "duration_s": duration_s,
                "time_scale": time_scale,
                "arrivals": n,
                "fired": fired,
                "replicas": replicas,
                "slots": slots,
                "block_size": block_size,
                "max_prompt_len": max_prompt,
                "max_new_tokens": max_new,
                "sampled_every": 4,
                "swap_at_arrival": swap_at,
                "seed": seed,
                "model": dict(model_cfg),
            },
            "wall_s": round(wall, 3),
            "warmup_s": round(warmup_s, 3),
            "statuses": statuses,
            "conserved": conserved,
            "pools_whole": pools_whole,
            "ok_by_generation": ok_by_gen,
            "torn_streams": torn,
            "probes": {"rows": probe_rows, "bitwise": probes_bitwise,
                       "generation": final_gen},
            "swap": {
                "status": (swap_report or {}).get("status"),
                "error": swap_result.get("error"),
                "swap_ms": (swap_report or {}).get("swap_ms"),
                "handoffs": (swap_report or {}).get("handoffs"),
                "fenced": (swap_report or {}).get("fenced"),
                "warmup_compiles": (swap_report or {}).get(
                    "warmup_compiles"),
                "generation": deploy_stats["generation"],
                "streams_during_swap": len(rows_in),
                "ttft_p99_during_swap_ms": ttft_in,
                "ttft_p99_steady_ms": ttft_out,
            },
            "engines": engines,
            "retired_engines": retired,
            "memory": memory,
        }
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)


def _deploy_bench_ok(report):
    """Exit gate for the deploy profile: the full trace fires and every
    stream ends OK (zero dropped), the ledger conserves and pools drain,
    the swap commits generation 2 with streams observed finishing on
    BOTH generations and none torn, zero steady-state recompiles on the
    new AND the retired engines, and zero leaked KV blocks (per-engine
    and HBM-accountant-wide).  The swap-window and steady TTFT p99 are
    reported, not gated: a latency means something only on the chip."""
    wl = report["workload"]
    if wl["fired"] != wl["arrivals"]:
        return False
    if report["statuses"] != {"OK": wl["arrivals"]}:
        return False
    if not (report["conserved"] and report["pools_whole"]):
        return False
    swap = report["swap"]
    if swap["status"] != "deployed" or swap["error"] is not None \
            or swap["generation"] != 2:
        return False
    if report["torn_streams"] != 0:
        return False
    if report["ok_by_generation"][1] < 1 \
            or report["ok_by_generation"][2] < 1:
        return False
    if not report["probes"]["bitwise"] \
            or report["probes"]["generation"] != 2:
        return False
    if swap["streams_during_swap"] < 1:
        return False
    for snap in report["engines"].values():
        if snap["steady_state_recompiles"] != 0 \
                or snap["kv_leaked_blocks"]:
            return False
        if snap["generation"] != 2:
            return False
    for snap in report["retired_engines"].values():
        if snap["steady_state_recompiles"] != 0:
            return False
    return bool(report["memory"]["balanced"])


def _main_sharded_decode(args, ap):
    if args.smoke:
        args.streams, args.slots = 12, 4
        args.block_size, args.max_prompt, args.max_new = 4, 8, 12
        model_cfg = dict(vocab_size=32, hidden=16, num_layers=1,
                         num_heads=2, max_len=32, seed=7)
    else:
        # the single-engine decode defaults are oversized for a
        # two-leg comparison bench; scale down unless overridden
        if args.streams == ap.get_default("streams"):
            args.streams = 32
        if args.max_new == ap.get_default("max_new"):
            args.max_new = 24
        model_cfg = dict(vocab_size=48, hidden=32, num_layers=2,
                         num_heads=2, max_len=128, seed=7)
    report = run_sharded_decode_bench(
        args.streams, args.slots, args.block_size, args.max_prompt,
        args.max_new, args.seed, model_cfg, tp=args.tp)
    _write_report(report, args.out)
    for key in ("tp1", "tp2"):
        leg = report[key]
        print("%s: %d engine(s) x tp=%d (%d device(s))  %s tok/s  "
              "ttft p50/p99: %s/%s ms  token-equal: %s"
              % (key, leg["engines"], leg["tp_degree"], leg["devices"],
                 leg["tokens_per_s"], leg["ttft_ms"]["p50"],
                 leg["ttft_ms"]["p99"], leg["token_equal_reference"]))
    coll = report["collectives"]
    print("collectives/step: %d gather(s), %d psum(s), %d byte(s)  "
          "static==runtime: %s"
          % (coll["gathers_per_step"], coll["psums_per_step"],
             coll["collective_bytes_per_step"],
             coll["static_matches_runtime"]))
    mem = report["memory"]
    print("memory/step: %d temp(s), peak %d byte(s)  "
          "static==runtime: %s"
          % (mem["temps_per_step"], mem["runtime_peak_bytes"],
             mem["static_matches_runtime"]))
    print("relative: %sx" % report["relative_tokens_per_s"])
    return 0 if _sharded_decode_ok(report) else 1


def _main_prefix_spec(args, ap):
    if args.smoke:
        # 1 chunk + 3 spec + ladder signatures per engine: cheap on
        # 1-core CI; the 1.5x bar is waived (timing noise at this
        # size) — the structural gates are not
        streams, slots = 10, 4
        block_size, chunk, max_prompt, max_new = 4, 4, 24, 10
        spec_k, shared_chunks = 2, 4
        model_cfg = dict(vocab_size=32, hidden=16, num_layers=1,
                         num_heads=2, max_len=64, seed=7)
    else:
        streams, slots = 48, 8
        block_size, chunk, max_prompt, max_new = 8, 8, 96, 24
        spec_k, shared_chunks = 4, 10
        model_cfg = dict(vocab_size=48, hidden=32, num_layers=2,
                         num_heads=2, max_len=160, seed=7)
    report = run_prefix_spec_bench(
        streams, slots, block_size, chunk, max_prompt, max_new,
        args.seed, model_cfg, spec_k=spec_k,
        shared_chunks=shared_chunks)
    _write_report(report, args.out)
    b, o = report["baseline"], report["optimized"]
    print("baseline:  %s tok/s  ttft p50/p99: %s/%s ms  "
          "prefill chunks: %d"
          % (b["tokens_per_s"], b["ttft_ms"]["p50"], b["ttft_ms"]["p99"],
             b["prefill_chunks"]))
    print("optimized: %s tok/s  ttft p50/p99: %s/%s ms  "
          "prefill chunks: %d  hit-rate: %s  cow: %d  accept: %s"
          % (o["tokens_per_s"], o["ttft_ms"]["p50"], o["ttft_ms"]["p99"],
             o["prefill_chunks"], o["prefix_hit_rate"], o["cow_forks"],
             o["spec_accept_rate"]))
    print("speedup: %sx" % report["speedup_tokens_per_s"])
    return 0 if _prefix_spec_ok(report) else 1


def _main_fleet_decode(args, ap):
    if args.smoke:
        args.streams, args.slots = 12, 4
        args.block_size, args.max_prompt, args.max_new = 4, 8, 12
        model_cfg = dict(vocab_size=32, hidden=16, num_layers=1,
                         num_heads=2, max_len=32, seed=7)
    else:
        # the single-engine decode defaults are oversized for a
        # two-replica drain bench; scale down unless overridden
        if args.streams == ap.get_default("streams"):
            args.streams = 32
        if args.max_new == ap.get_default("max_new"):
            args.max_new = 24
        model_cfg = dict(vocab_size=48, hidden=32, num_layers=2,
                         num_heads=2, max_len=128, seed=7)
    report = run_fleet_decode_bench(
        args.streams, args.slots, args.block_size, args.max_prompt,
        args.max_new, args.seed, model_cfg, replicas=args.replicas)
    _write_report(report, args.out)
    print("fleet-decode: %s tok/s  ttft p50/p99: %s/%s ms  "
          "handoffs: %d  fenced: %d  drained: %s"
          % (report["tokens_per_s"], report["ttft_ms"]["p50"],
             report["ttft_ms"]["p99"], report["handoffs"],
             report["fenced"], report["drained_mid_run"]))
    return 0 if _fleet_decode_ok(report) else 1


def _main_decode(args, ap):
    if args.smoke:
        # 4 prefill + 1 (pinned) width signature per engine: cheap on
        # 1-core CI
        args.streams, args.slots = 16, 4
        args.block_size, args.max_prompt, args.max_new = 4, 8, 12
        model_cfg = dict(vocab_size=32, hidden=16, num_layers=1,
                         num_heads=2, max_len=32, seed=7)
    else:
        model_cfg = dict(vocab_size=48, hidden=32, num_layers=2,
                         num_heads=2, max_len=128, seed=7)
    report = run_decode_bench(args.streams, args.slots, args.block_size,
                              args.max_prompt, args.max_new, args.seed,
                              model_cfg)
    _write_report(report, args.out)
    c, s = report["continuous"], report["static"]
    print("continuous: %s tok/s  ttft p50/p99: %s/%s ms  avg_live: %s"
          % (c["tokens_per_s"], c["ttft_ms"]["p50"], c["ttft_ms"]["p99"],
             c["avg_live_slots"]))
    print("static:     %s tok/s  ttft p50/p99: %s/%s ms  avg_live: %s"
          % (s["tokens_per_s"], s["ttft_ms"]["p50"], s["ttft_ms"]["p99"],
             s["avg_live_slots"]))
    print("speedup: %sx  steady-state recompiles: %d/%d"
          % (report["speedup_tokens_per_s"],
             c["steady_state_recompiles"], s["steady_state_recompiles"]))
    return 0 if _decode_ok(report) else 1


def _main_disagg(args, ap):
    if args.smoke:
        args.slots = 4
        args.block_size, args.max_prompt, args.max_new = 4, 8, 12
        args.devices, args.prefill_replicas = 2, 1
        rate_hz, duration_s = 40.0, 0.6
        model_cfg = dict(vocab_size=32, hidden=16, num_layers=1,
                         num_heads=2, max_len=32, seed=7)
    else:
        if args.slots == ap.get_default("slots"):
            args.slots = 4
        if args.max_new == ap.get_default("max_new"):
            args.max_new = 24
        rate_hz, duration_s = args.rate_hz, args.duration_s
        model_cfg = dict(vocab_size=48, hidden=32, num_layers=2,
                         num_heads=2, max_len=128, seed=7)
    report = run_disagg_bench(
        rate_hz, duration_s, args.slots, args.block_size,
        args.block_size, args.max_prompt, args.max_new, args.seed,
        model_cfg, devices=args.devices,
        prefill_replicas=args.prefill_replicas,
        slo_ttft_ms=args.slo_ttft_ms, slo_tpot_ms=args.slo_tpot_ms,
        time_scale=args.time_scale)
    _write_report(report, args.out)
    for key in ("colocated", "disagg"):
        leg = report[key]
        g = leg["goodput"]
        print("%s: %d/%d good (%s/s)  ttft p99: %s ms  tpot p99: %s ms  "
              "bitwise: %s"
              % (key, g["good"], g["total"], leg["goodput_per_s"],
                 round(g["ttft_ms"]["p99"], 2),
                 round(g["tpot_ms"]["p99"], 3),
                 leg["bitwise_equal_reference"]))
    mem = report["memory"]
    print("memory: %d kv region(s), %d byte(s) allocated, balanced: %s"
          % (mem["kv_regions"], mem["kv_alloc_bytes"], mem["balanced"]))
    print("handoffs: %d (failed %d)  speedup: %sx"
          % (report["disagg"]["handoffs"]["handoffs"],
             report["disagg"]["handoffs"]["handoff_failures"],
             report["speedup_goodput"]))
    return 0 if _disagg_ok(report) else 1


def _main_deploy(args, ap):
    if args.smoke:
        args.slots = 4
        args.block_size, args.max_prompt, args.max_new = 4, 8, 12
        args.replicas = 2
        # the trace must OUTLAST the swap (two engine warmups) so
        # generation-2 traffic is organic, not just the probes
        rate_hz, duration_s = 20.0, 3.5
        model_cfg = dict(vocab_size=32, hidden=16, num_layers=1,
                         num_heads=2, max_len=32, seed=7)
    else:
        if args.slots == ap.get_default("slots"):
            args.slots = 4
        if args.max_new == ap.get_default("max_new"):
            args.max_new = 24
        # the full-size swap is ~8 s (two 2-layer engine warmups + the
        # retire drain); the trace must outlast it so generation-2
        # traffic is organic, not just the probes
        if args.duration_s == ap.get_default("duration_s"):
            args.duration_s = 12.0
        rate_hz, duration_s = args.rate_hz, args.duration_s
        model_cfg = dict(vocab_size=48, hidden=32, num_layers=2,
                         num_heads=2, max_len=128, seed=7)
    report = run_deploy_bench(
        rate_hz, duration_s, args.slots, args.block_size,
        args.max_prompt, args.max_new, args.seed, model_cfg,
        replicas=args.replicas, time_scale=args.time_scale)
    _write_report(report, args.out)
    swap = report["swap"]
    print("deploy: %d stream(s) all %s  by generation: %s  torn: %d"
          % (report["workload"]["arrivals"], report["statuses"],
             report["ok_by_generation"], report["torn_streams"]))
    print("swap: %s gen %s in %s ms  handoffs: %d  fenced: %d  "
          "warmup compiles: %s"
          % (swap["status"], swap["generation"], swap["swap_ms"],
             swap["handoffs"] or 0, swap["fenced"] or 0,
             swap["warmup_compiles"]))
    print("ttft p99: %s ms during swap (%d stream(s)) vs %s ms steady  "
          "memory balanced: %s"
          % (swap["ttft_p99_during_swap_ms"], swap["streams_during_swap"],
             swap["ttft_p99_steady_ms"], report["memory"]["balanced"]))
    return 0 if _deploy_bench_ok(report) else 1


def _main_batch(args, ap):
    if args.smoke:
        args.clients, args.requests = 4, 6
        args.shapes = "4x16,8x16"
        args.max_batch = 4          # 6 warmup compiles: cheap on 1-core CI
    shapes = [tuple(int(d) for d in s.split("x"))
              for s in args.shapes.split(",")]
    report = run_bench(args.clients, args.requests, shapes, args.max_batch,
                       args.linger_ms, args.timeout_ms, args.max_queue)
    _write_report(report, args.out)
    print("throughput: %s req/s  p50/p95/p99: %s/%s/%s ms  avg_batch: %s  "
          "steady-state recompiles: %d"
          % (report["throughput_rps"], report["latency_ms"]["p50"],
             report["latency_ms"]["p95"], report["latency_ms"]["p99"],
             report["avg_batch"], report["steady_state_recompiles"]))
    return 0 if report["steady_state_recompiles"] == 0 else 1


def _write_report(report, out):
    """The report goes where ``--out`` says; with no ``--out`` it is
    printed to stdout, ahead of the summary lines."""
    if out is None:
        json.dump(report, sys.stdout, indent=2)
        sys.stdout.write("\n")
        return
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print("wrote %s" % out)


# The profile registry: ONE row per profile — argparse choices,
# pre-import environment, and the runner all derive from here
# (tests/test_disagg.py drift-gates this table against the module
# docstring).
PROFILES = {
    "batch": {"run": _main_batch},
    "decode": {"run": _main_decode},
    "fleet-decode": {"run": _main_fleet_decode},
    "prefix-spec": {"run": _main_prefix_spec},
    "sharded-decode": {
        "run": _main_sharded_decode,
        # the mesh needs real (virtual) devices — set before jax loads
        "env": {"XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
    },
    "disagg": {"run": _main_disagg},
    "deploy": {"run": _main_deploy},
}


def main(argv=None):
    ap = argparse.ArgumentParser(prog="serve_bench", description=__doc__)
    ap.add_argument("--profile", choices=tuple(sorted(PROFILES)),
                    default="batch")
    ap.add_argument("--replicas", type=int, default=2,
                    help="[fleet-decode] decode replicas (one is drained)")
    ap.add_argument("--tp", type=int, default=2,
                    help="[sharded-decode] tensor-parallel degree (also "
                         "the unsharded leg's engine count)")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--requests", type=int, default=40,
                    help="requests per client")
    ap.add_argument("--shapes", default="4x16,8x16,16x16,32x16",
                    help="comma list of LxF per-request shapes")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--linger-ms", type=float, default=2.0)
    ap.add_argument("--timeout-ms", type=float, default=5000.0)
    ap.add_argument("--max-queue", type=int, default=1024)
    ap.add_argument("--streams", type=int, default=192,
                    help="[decode] concurrent token streams")
    ap.add_argument("--slots", type=int, default=8,
                    help="[decode] decode batch slots")
    ap.add_argument("--block-size", type=int, default=8,
                    help="[decode] KV cache block size (tokens)")
    ap.add_argument("--max-prompt", type=int, default=16,
                    help="[decode] max prompt length")
    ap.add_argument("--max-new", type=int, default=96,
                    help="[decode] max generated tokens per stream")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rate-hz", type=float, default=24.0,
                    help="[disagg] open-loop Poisson arrival rate")
    ap.add_argument("--duration-s", type=float, default=4.0,
                    help="[disagg] open-loop trace duration")
    ap.add_argument("--time-scale", type=float, default=1.0,
                    help="[disagg] replay speed (0.5 = twice as fast)")
    ap.add_argument("--devices", type=int, default=4,
                    help="[disagg] total device budget for BOTH legs")
    ap.add_argument("--prefill-replicas", type=int, default=None,
                    help="[disagg] prefill-tier share of --devices "
                         "(default: half)")
    ap.add_argument("--slo-ttft-ms", type=float, default=250.0,
                    help="[disagg] p99 time-to-first-token SLO")
    ap.add_argument("--slo-tpot-ms", type=float, default=150.0,
                    help="[disagg] p99 time-per-output-token SLO")
    ap.add_argument("--out", default=None,
                    help="where the JSON report goes (default: stdout)")
    ap.add_argument("--smoke", action="store_true",
                    help="small fast run for tier-1 (overrides sizes)")
    args = ap.parse_args(argv)
    prof = PROFILES[args.profile]
    for key, val in prof.get("env", {}).items():
        os.environ.setdefault(key, val)
    return prof["run"](args, ap)


if __name__ == "__main__":
    sys.exit(main())
