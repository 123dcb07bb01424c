"""mxnet_tpu.serving.decode: continuous batching + paged KV cache
(docs/SERVING.md#autoregressive-decode).

Covers the decode acceptance gates: >= 64 concurrent streams with
iteration-level join/leave produce greedy outputs BITWISE-equal to a
one-request-at-a-time reference with ZERO steady-state recompiles across
mixed prompt/output lengths; KV block accounting conserves (allocated ==
freed after drain, admission sheds when the pool is exhausted); deadlines,
breaker, and teardown terminate streams with statuses, never exceptions;
the seeded decode chaos scenario holds its invariants; continuous
scheduling finishes a seeded workload in fewer engine steps than
run-to-completion batching; and the serve_bench decode profile (smoke)
passes its report-schema / zero-recompile / zero-leak gates.
"""
import json
import os
import sys
import threading

import numpy as np
import pytest

from mxnet_tpu import faults, serving
from mxnet_tpu.analysis import schedule
from mxnet_tpu.serving.decode import (DecodeEngine, PagedKVCache,
                                      TinyCausalLM)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def engine():
    """One warmed engine shared by the read-mostly tests (warmup compiles
    the whole prefill x width signature menu once per module)."""
    model = TinyCausalLM(vocab_size=48, hidden=32, num_layers=2,
                        num_heads=2, max_len=64, seed=7)
    eng = DecodeEngine(model, name="t", max_slots=8, block_size=4,
                       max_prompt_len=16, max_new_tokens=24, max_queue=256)
    yield eng
    eng.stop()


# ---------------------------------------------------------------------------
# the acceptance gate: 64 concurrent streams, bitwise, zero recompiles
# ---------------------------------------------------------------------------

def test_single_stream_greedy_matches_reference(engine):
    stream = engine.generate([3, 1, 4, 1, 5], max_new_tokens=8,
                             timeout_ms=30000)
    assert stream.status == serving.OK
    ref = engine.generate_reference([3, 1, 4, 1, 5], 8)
    assert stream.tokens() == ref.tolist()
    assert len(stream.tokens()) == 8
    assert stream.ttft_ms is not None
    assert stream.latency_ms >= stream.ttft_ms


def test_64_concurrent_streams_bitwise_equal_zero_recompiles(engine):
    n = 64
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 48, rng.randint(1, 17)).tolist()
               for _ in range(n)]
    budgets = [int(rng.randint(1, 25)) for _ in range(n)]
    # one-request-at-a-time reference: same CachedOp signatures, private
    # pools, no scheduler
    refs = [engine.generate_reference(p, m).tolist()
            for p, m in zip(prompts, budgets)]
    warm = engine.warmup_report
    assert warm["compiles"] == warm["signatures"]
    misses_before = engine.cache_stats()["misses"]
    before = engine.stats_snapshot()
    kv_before = engine.kv_stats()

    streams = [None] * n
    barrier = threading.Barrier(8)

    def client(cid):
        barrier.wait()
        for i in range(cid, n, 8):
            streams[i] = engine.submit(prompts[i], max_new_tokens=budgets[i],
                                       timeout_ms=60000)

    threads = [threading.Thread(target=client, args=(c,)) for c in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i, s in enumerate(streams):
        assert s.wait(60), "stream %d never terminated" % i
        assert s.status == serving.OK, (i, s)
        # BITWISE: continuous batching (mixed neighbors, mixed widths)
        # must not perturb a single token of any stream
        assert s.tokens() == refs[i], (
            "stream %d diverged from its reference" % i)

    after = engine.stats_snapshot()
    assert after["ok"] - before["ok"] == n
    assert after["requests"] - before["requests"] == n
    # iteration-level scheduling actually shared the step: with 64 streams
    # over 8 slots the average occupancy must be well above 1
    assert after["avg_live_slots"] > 2.0
    # ZERO steady-state recompiles across mixed prompt/output lengths
    assert engine.cache_stats()["misses"] == misses_before
    # KV block accounting: the pool is whole again after the drain
    kv = engine.kv_stats()
    assert kv["used"] == 0 and kv["reserved"] == 0
    assert kv["allocated_total"] - kv_before["allocated_total"] \
        == kv["freed_total"] - kv_before["freed_total"] > 0


# ---------------------------------------------------------------------------
# streaming
# ---------------------------------------------------------------------------

def test_streaming_iterator_and_callback_deliver_every_token(engine):
    seen = []
    stream = engine.submit([9, 8, 7], max_new_tokens=6, timeout_ms=30000,
                           on_token=seen.append)
    got = list(stream)            # yields incrementally until terminal
    assert stream.status == serving.OK
    assert got == stream.tokens() == seen
    assert len(got) == 6
    # iterating a finished stream replays the full token list
    assert list(stream) == got


def test_deadline_mid_stream_times_out_with_prefix(engine):
    prompt = [2, 4, 6]
    ref = engine.generate_reference(prompt, 24).tolist()
    stream = engine.generate(prompt, max_new_tokens=24, timeout_ms=4)
    assert stream.status == serving.TIMEOUT
    toks = stream.tokens()
    assert len(toks) < 24
    # a partial stream is a strict PREFIX of the reference: ending early
    # must never tear or cross-contaminate what was already emitted
    assert toks == ref[:len(toks)]


# ---------------------------------------------------------------------------
# admission: invalid prompts, pool exhaustion
# ---------------------------------------------------------------------------

def test_invalid_prompts_rejected_before_any_execution(engine):
    misses = engine.cache_stats()["misses"]
    cases = [
        np.arange(17),                 # longer than max_prompt_len
        [1, 2, 999],                   # token id outside the vocab
        [],                            # empty
        [[1, 2]],                      # not 1-D
        [0.5, 1.5],                    # non-integer ids
    ]
    for bad in cases:
        stream = engine.submit(bad, max_new_tokens=4)
        assert stream.status == serving.INVALID_INPUT, (bad, stream)
        assert not stream.admitted
    stream = engine.submit([1], max_new_tokens=9999)    # over the budget cap
    assert stream.status == serving.INVALID_INPUT
    assert engine.cache_stats()["misses"] == misses     # nothing compiled


def test_pool_exhaustion_sheds_overloaded_and_recovers():
    import time
    model = TinyCausalLM(vocab_size=16, hidden=16, num_layers=1,
                        num_heads=2, max_len=48, seed=1)
    # capacity 9 allocatable blocks: one worst-case stream reserves all 9
    eng = DecodeEngine(model, name="tiny", max_slots=2, block_size=4,
                       num_blocks=10, max_prompt_len=4, max_new_tokens=32,
                       warmup=True)
    try:
        first_tok = []
        s1 = eng.submit([1, 2, 3, 4], max_new_tokens=32, timeout_ms=30000,
                        on_token=first_tok.append)
        # wait until s1 JOINED (its reservation claims the whole pool);
        # it then has ~31 decode steps left — plenty of window to observe
        # the shed
        deadline = time.monotonic() + 10.0
        while not first_tok and s1.snapshot()[0] is None \
                and time.monotonic() < deadline:
            time.sleep(0.001)
        assert first_tok and s1.snapshot()[0] is None
        s2 = eng.submit([1], max_new_tokens=8)
        assert s2.status == serving.OVERLOADED
        assert "KV blocks" in s2.error
        assert not s2.admitted
        assert s1.result().status == serving.OK
        # blocks freed: the next stream is admitted and completes
        s3 = eng.generate([2, 3], max_new_tokens=4, timeout_ms=30000)
        assert s3.status == serving.OK
        kv = eng.kv_stats()
        assert kv["used"] == 0 and kv["reserved"] == 0
        assert kv["allocated_total"] == kv["freed_total"]
        snap = eng.stats_snapshot()
        assert snap["shed"] == 1 and snap["ok"] == 2
    finally:
        eng.stop()


def test_oversized_stream_is_invalid_not_starved():
    model = TinyCausalLM(vocab_size=16, hidden=16, num_layers=1,
                        num_heads=2, max_len=32, seed=1)
    eng = DecodeEngine(model, name="tiny2", max_slots=1, block_size=4,
                       num_blocks=3, max_prompt_len=8, max_new_tokens=16,
                       warmup=False)
    try:
        # needs ceil(24/4)=6 blocks but the pool only has 2: rejecting at
        # admission beats queueing a stream that could never join
        stream = eng.submit(list(range(8)), max_new_tokens=16)
        assert stream.status == serving.INVALID_INPUT
        assert "pool" in stream.error
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# self-healing: breaker per-stream, teardown drain
# ---------------------------------------------------------------------------

def test_breaker_opens_after_failures_and_recovers():
    model = TinyCausalLM(vocab_size=16, hidden=16, num_layers=1,
                        num_heads=2, max_len=16, seed=2)
    eng = DecodeEngine(model, name="brk", max_slots=2, block_size=4,
                       max_prompt_len=2, max_new_tokens=4,
                       breaker_threshold=2, breaker_backoff_ms=30.0)
    try:
        persistent = faults.FaultPlan(0).add("serving.predict", kind="fatal")
        with faults.plan(persistent):
            statuses = [eng.generate([1], max_new_tokens=2,
                                     timeout_ms=10000).status
                        for _ in range(4)]
        # the first K=2 fail their execution (ERROR); once open, admission
        # fast-fails with the retryable status — no queueing, no XLA call
        assert statuses[:2] == [serving.ERROR] * 2, statuses
        assert serving.UNAVAILABLE in statuses[2:], statuses
        assert eng.health() == "UNAVAILABLE"
        # faults cleared: the half-open probe re-closes the breaker
        import time
        deadline = time.monotonic() + 5.0
        recovered = False
        while time.monotonic() < deadline:
            res = eng.generate([1], max_new_tokens=2, timeout_ms=10000)
            if res.status == serving.OK:
                recovered = True
                break
            time.sleep(0.01)
        assert recovered, "breaker never recovered after faults cleared"
        assert eng.health() == "HEALTHY"
        kv = eng.kv_stats()
        assert kv["used"] == 0 and kv["allocated_total"] == kv["freed_total"]
    finally:
        eng.stop()


def test_stop_drains_streams_unavailable_and_pool_whole():
    model = TinyCausalLM(vocab_size=16, hidden=16, num_layers=1,
                        num_heads=2, max_len=32, seed=3)
    # warmup=False: submissions queue behind the first lazy compile, so
    # stop() reliably catches streams in flight
    eng = DecodeEngine(model, name="drain", max_slots=2, block_size=4,
                       max_prompt_len=4, max_new_tokens=16, warmup=False)
    streams = [eng.submit([1, 2], max_new_tokens=16) for _ in range(6)]
    eng.stop()
    for s in streams:
        assert s.wait(10)
        assert s.status in (serving.OK, serving.UNAVAILABLE), s
    assert any(s.status == serving.UNAVAILABLE for s in streams)
    snap = eng.stats_snapshot()
    assert snap["requests"] == snap["ok"] + snap["timeouts"] \
        + snap["errors"] + snap["unavailable"]
    kv = eng.kv_stats()
    assert kv["used"] == 0 and kv["reserved"] == 0
    assert kv["allocated_total"] == kv["freed_total"]
    # post-stop submission: clean retryable status, not an exception
    assert eng.submit([1]).status == serving.UNAVAILABLE


# ---------------------------------------------------------------------------
# paged KV cache unit behavior
# ---------------------------------------------------------------------------

def test_kv_cache_reserve_grow_free_accounting():
    cache = PagedKVCache(num_layers=1, num_blocks=5, block_size=4,
                         num_heads=2, head_dim=4)
    assert cache.capacity() == 4                 # block 0 is trash
    assert cache.blocks_for_tokens(1) == 1
    assert cache.blocks_for_tokens(4) == 1
    assert cache.blocks_for_tokens(5) == 2
    assert cache.reserve("a", 3)
    assert not cache.reserve("b", 2)             # 4 - 3 reserved < 2
    assert cache.reserve("b", 1)
    assert cache.available_unreserved() == 0
    b0 = cache.grow("a")
    assert b0 != 0                               # trash block never handed out
    assert cache.table("a", 4) == [b0, 0, 0, 0]  # trash-padded
    cache.ensure_capacity("a", 9)                # 3 blocks total
    with pytest.raises(Exception):
        cache.grow("a")                          # past its reservation
    assert cache.used() == 3
    assert cache.free_seq("a") == 3
    cache.release("b")
    st = cache.stats()
    assert st["used"] == 0 and st["reserved"] == 0
    assert st["allocated_total"] == st["freed_total"] == 3


def test_decode_counters_land_in_profiler_dump(engine, tmp_path):
    from mxnet_tpu import profiler
    trace = str(tmp_path / "decode_profile.json")
    profiler.set_config(filename=trace)
    profiler.set_state("run")
    try:
        res = engine.generate([5, 6, 7], max_new_tokens=6, timeout_ms=30000)
        assert res.status == serving.OK
    finally:
        profiler.set_state("stop")
        profiler.dump()
    events = json.load(open(trace))["traceEvents"]
    counters = {e["name"] for e in events if e.get("ph") == "C"}
    for name in ("t:live_seqs", "t:kv_blocks_used", "t:ttft_ms",
                 "t:tokens_per_s"):
        assert name in counters, counters


# ---------------------------------------------------------------------------
# chaos: the mxstress "decode" scenario (5 seeds, tier-1 budget)
# ---------------------------------------------------------------------------

def test_mxstress_decode_scenario_zero_violations():
    report = schedule.stress(seeds=schedule.FAULT_SMOKE_SEEDS,
                             scenarios=("decode",))
    flat = ["seed %s [%s] %s" % (seed, scen, v)
            for seed, per_seed in report["seeds"].items()
            for scen, violations in per_seed.items()
            for v in violations]
    assert report["violations"] == 0, "\n".join(flat)
    assert report["preemptions"] > 0        # the harness really perturbed


# ---------------------------------------------------------------------------
# what continuous batching buys, as counts under a seed
# ---------------------------------------------------------------------------

def test_continuous_scheduling_takes_fewer_steps_than_static():
    """The same streams at the same slot count: iteration-level
    join/leave refills a slot the step after a short stream leaves it,
    run-to-completion batching holds every slot until the batch's
    longest stream ends.  So continuous scheduling emits the same tokens
    in fewer engine steps at a higher mean of live slots — the counts a
    tokens/s ratio on the chip would follow from."""
    model = TinyCausalLM(vocab_size=32, hidden=16, num_layers=1,
                        num_heads=2, max_len=32, seed=7)
    rng = np.random.RandomState(0)
    n, slots, max_prompt, max_new = 32, 4, 8, 16
    prompts = [rng.randint(0, 32, rng.randint(1, max_prompt + 1)).tolist()
               for _ in range(n)]
    # mostly short outputs with a long tail: the mix a static batch
    # handles worst
    budgets = [int(rng.randint(12, max_new + 1)) if rng.random() < 0.25
               else int(rng.randint(2, 5)) for _ in range(n)]
    width = DecodeEngine.worst_case_width(max_prompt, max_new, 4)
    legs = {}
    for scheduling in ("continuous", "static"):
        eng = DecodeEngine(model, name="sched-" + scheduling,
                           max_slots=slots, block_size=4,
                           max_prompt_len=max_prompt,
                           max_new_tokens=max_new, max_queue=n,
                           width_blocks=[width], scheduling=scheduling)
        try:
            handles = [eng.submit(p, max_new_tokens=m)
                       for p, m in zip(prompts, budgets)]
            for h in handles:
                assert h.wait(60.0) and h.status == serving.OK
            snap = eng.stats_snapshot()
            legs[scheduling] = (snap["steps"], snap["avg_live_slots"],
                                [h.tokens() for h in handles])
        finally:
            eng.stop()
    (steps_c, live_c, toks_c), (steps_s, live_s, toks_s) = \
        legs["continuous"], legs["static"]
    assert toks_c == toks_s                 # the same work, token for token
    assert [len(t) for t in toks_c] == budgets
    assert steps_c < steps_s, (steps_c, steps_s)
    assert live_c > live_s, (live_c, live_s)
    # no scheduler can beat every slot live on every step
    assert steps_c * slots >= sum(budgets) - n   # prefill emits token 1


# ---------------------------------------------------------------------------
# serve_bench decode profile: the smoke's report and count gates
# ---------------------------------------------------------------------------

def test_serve_bench_decode_smoke_artifact(tmp_path):
    # in-process, like the batch-profile smoke: a fresh interpreter costs
    # ~15 s of jax import on the 1-core tier-1 box for no extra coverage
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import serve_bench
    out = str(tmp_path / "report.json")
    rc = serve_bench.main(["--smoke", "--profile", "decode", "--out", out])
    assert rc == 0
    report = json.load(open(out))
    assert report["profile"] == "decode"
    for leg in ("continuous", "static"):
        rec = report[leg]
        assert rec["steady_state_recompiles"] == 0
        assert rec["kv_leaked_blocks"] == 0
        assert rec["statuses"] == {"OK": report["workload"]["streams"]}
        # reported for a chip run, never compared with a number here
        assert set(rec["ttft_ms"]) == {"p50", "p99"}
        assert "tokens_per_s" in rec
    assert "speedup_tokens_per_s" in report
