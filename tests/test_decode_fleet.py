"""Stateful decode fleet (docs/ROBUSTNESS.md "Stream handoff").

Tier-1 gates for the decode-fleet tentpole:

* **KV-aware routing** — ``FleetRouter.submit_stream`` places new streams
  on the replica with the most free KV blocks / shallowest queue, and a
  placed stream is pinned (session affinity) via its ``(rid, lease
  generation)`` fencing token.
* **Fenced handoff** — ``drain()`` quiesces the replica's engines,
  exports every live stream (prefix + KV pages), bumps the lease
  generation, and resumes each stream on a survivor: the merged token
  stream stays bitwise-equal to the uninterrupted greedy reference.  A
  stale generation can neither import a snapshot nor emit tokens (no
  duplicate or torn tokens — the zombie-replica guard).
* **Crash path** — ``kill_replica()`` terminates the dead replica's
  streams UNAVAILABLE with their valid prefixes, bounded, never hanging;
  the prefix re-admits as a prompt and continues bitwise against
  ``generate_reference(prompt + prefix)``.
* **Multi-tenant QoS** — per-tenant token budgets and weighted-fair
  admission: an over-budget tenant sheds OVERLOADED while others flow.
* **Chaos** — the mxstress ``decode_fleet`` scenario (one replica drained
  AND another killed under a multi-tenant storm) holds stream/tenant/KV
  conservation over the FAULT_SMOKE_SEEDS set.
* **Bench** — ``serve_bench --profile fleet-decode`` (mid-run drain)
  meets its count gates.
"""
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from mxnet_tpu.base import MXNetError
from mxnet_tpu.kvstore_server import (LeaseExpired, MembershipTable,
                                      UnknownWorker)
from mxnet_tpu.serving import OK, OVERLOADED, UNAVAILABLE
from mxnet_tpu.serving.decode import DecodeEngine, TinyCausalLM
from mxnet_tpu.serving.fleet import DRAINING, LIVE, FleetRouter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_MODEL_KW = dict(vocab_size=20, hidden=16, num_layers=1, num_heads=2,
                 max_len=24, seed=13)
_ENGINE_KW = dict(max_slots=2, block_size=4, num_blocks=9, max_prompt_len=4,
                  max_new_tokens=5, max_queue=6, width_blocks=[4])
_PROMPT = [3, 1, 2]
_MAX_NEW = 5


def _factory(name, **over):
    kw = dict(_ENGINE_KW)
    kw.update(over)
    return DecodeEngine(TinyCausalLM(**_MODEL_KW), name=name, **kw)


def _fleet(replicas=2, copies=None, engine_kw=None, **router_kw):
    router_kw.setdefault("failover_budget", 2)
    router = FleetRouter(replicas=replicas, **router_kw)
    router.load_decode("lm", lambda n: _factory(n, **(engine_kw or {})),
                       replicas=copies if copies is not None else replicas)
    return router


@pytest.fixture(scope="module")
def ref():
    """Greedy reference for _PROMPT (identical params per factory call,
    so one reference is valid fleet-wide)."""
    eng = _factory("ref")
    try:
        return eng.generate_reference(_PROMPT, _MAX_NEW).tolist()
    finally:
        eng.stop()


@pytest.fixture(scope="module")
def fleet2(ref):
    """One warmed 2-replica fleet shared by the read-mostly tests."""
    router = _fleet(replicas=2)
    yield router
    router.stop()


def _requests_by_rid(router, name="lm"):
    return {rid: snap["requests"]
            for rid, snap in router.stats()["engines"][name].items()}


# ---------------------------------------------------------------------------
# KV-aware routing + session affinity
# ---------------------------------------------------------------------------

def test_submit_stream_prefers_replica_with_free_kv(fleet2, ref):
    placement = fleet2.stats()["decode_models"]["lm"]["placement"]
    pinned, free = placement[0], placement[1]
    before = _requests_by_rid(fleet2)
    # starve the first replica's pool: 6 of 8 blocks promised elsewhere
    cache = fleet2.engine("lm", pinned)._cache
    assert cache.reserve("pin", 6)
    try:
        s = fleet2.submit_stream("lm", _PROMPT, max_new_tokens=_MAX_NEW)
        assert s.wait(10)
        assert s.status == OK and s.tokens() == ref
    finally:
        cache.release("pin")
    after = _requests_by_rid(fleet2)
    assert after[free] == before[free] + 1, "stream routed to the full pool"
    assert after[pinned] == before[pinned]


def test_admitted_stream_is_pinned_with_a_fencing_token(fleet2):
    before = _requests_by_rid(fleet2)
    s = fleet2.submit_stream("lm", _PROMPT, max_new_tokens=_MAX_NEW)
    assert s.wait(10) and s.status == OK
    owner = s.owner()
    assert isinstance(owner, tuple) and len(owner) == 2
    rid, gen = owner
    assert isinstance(gen, int)
    after = _requests_by_rid(fleet2)
    assert after[rid] == before[rid] + 1   # the token names the home engine


def test_unknown_engine_name_raises(fleet2):
    with pytest.raises(MXNetError, match="no decode engine"):
        fleet2.submit_stream("nope", _PROMPT)


# ---------------------------------------------------------------------------
# fenced handoff on drain: bitwise equality across the migration
# ---------------------------------------------------------------------------

def test_drain_hands_streams_off_bitwise_equal(ref):
    # the pool must let ONE survivor absorb every stream (6 x 3-block
    # worst case + trash block) — the drain itself is what's under test
    router = _fleet(replicas=2,
                    engine_kw=dict(num_blocks=19, max_queue=12,
                                   max_slots=4))
    try:
        placement = router.stats()["decode_models"]["lm"]["placement"]
        # slow the workers down so the drain catches live streams mid-flight
        slow = lambda t: time.sleep(0.005)
        streams = [router.submit_stream("lm", _PROMPT,
                                        max_new_tokens=_MAX_NEW,
                                        on_token=slow)
                   for _ in range(6)]
        router.drain(placement[0])
        for s in streams:
            assert s.wait(20), "stream hung across the drain"
            assert s.status == OK, (s.status, s.error)
            assert s.tokens() == ref, "handed-off stream diverged"
        d = router.decode_stats.snapshot()
        assert d["handoffs"] >= 1, "drain never actually migrated a stream"
        assert d["fenced"] == 0
        assert router.replicas()[placement[0]] == DRAINING
        # the drained engine parked without leaking its pool
        kv = router.engine("lm", placement[0]).kv_stats()
        assert kv["used"] == 0 and kv["reserved"] == 0
        # enable() resumes the drained engine; it serves again
        router.enable(placement[0])
        s = router.submit_stream("lm", _PROMPT, max_new_tokens=_MAX_NEW)
        assert s.wait(10) and s.status == OK and s.tokens() == ref
    finally:
        router.stop()


# ---------------------------------------------------------------------------
# lease fencing: the zombie-replica negative paths (satellite 4)
# ---------------------------------------------------------------------------

def test_membership_generation_check_is_generation_only():
    table = MembershipTable(lease_ttl_s=3600.0)
    g1 = table.register("w").generation
    table.check_generation("w", g1)            # current: fine
    g2 = table.register("w").generation        # the fence bump
    assert g2 > g1
    table.check_generation("w", g2)
    with pytest.raises(LeaseExpired):
        table.check_generation("w", g1)        # stale: fenced out
    with pytest.raises(UnknownWorker):
        table.generation("ghost")


def test_stale_generation_cannot_import_or_emit(ref):
    eng_a = _factory("zombie-a")
    eng_b = _factory("zombie-b")
    try:
        old = ("r", 1)
        stream = eng_a.submit(_PROMPT, max_new_tokens=_MAX_NEW,
                              on_token=lambda t: time.sleep(0.01),
                              owner=old)
        deadline = time.monotonic() + 10
        while not stream.tokens() and time.monotonic() < deadline:
            time.sleep(0.002)
        assert stream.tokens(), "no prefix before the handoff"
        assert eng_a.quiesce(5.0)
        exported = dict(eng_a.export_streams())
        snap = exported[stream]
        prefix = stream.tokens()
        # the fence: the stream is re-owned to the next generation
        stream.set_owner(("r", 2))
        # a zombie emission under the old generation is dropped silently
        stream._emit(99, owner=old)
        assert stream.tokens() == prefix, "stale generation emitted a token"
        # a zombie import under the old generation is refused outright
        with pytest.raises(MXNetError, match="fencing token"):
            eng_b.import_stream(snap, stream=stream, owner=old)
        # the current generation resumes and finishes bitwise-clean
        eng_b.import_stream(snap, stream=stream, owner=("r", 2))
        assert stream.wait(10) and stream.status == OK
        assert stream.tokens() == ref, "duplicate or torn tokens"
    finally:
        eng_a.stop()
        eng_b.stop()


# ---------------------------------------------------------------------------
# crash path: UNAVAILABLE with a valid prefix, then re-admission
# ---------------------------------------------------------------------------

def test_kill_terminates_with_prefix_then_readmits():
    # roomier prompts so prompt + prefix re-admits below max_prompt_len
    router = _fleet(replicas=2,
                    engine_kw=dict(max_prompt_len=9, num_blocks=14,
                                   width_blocks=[5]))
    try:
        prompt = [3]
        s = router.submit_stream("lm", prompt, max_new_tokens=_MAX_NEW,
                                 on_token=lambda t: time.sleep(0.03))
        deadline = time.monotonic() + 10
        while not s.tokens() and time.monotonic() < deadline:
            time.sleep(0.002)
        assert s.tokens(), "no tokens before the kill"
        rid = s.owner()[0]
        assert router.kill_replica(rid)
        assert s.wait(10), "stream hung past the replica death"
        assert s.status == UNAVAILABLE
        prefix = s.tokens()
        survivor = router.stats()["decode_models"]["lm"]["placement"][0]
        eng = router.engine("lm", survivor)
        full_ref = eng.generate_reference(prompt, _MAX_NEW).tolist()
        assert prefix == full_ref[:len(prefix)], "crash tore the prefix"
        # re-admit with the prefix as prompt; prefill-computed K/V is not
        # bitwise decode-computed K/V, so the reference is a fresh
        # generate_reference over prompt + prefix — never the old suffix
        readmit = list(prompt) + prefix
        ref2 = eng.generate_reference(readmit, _MAX_NEW).tolist()
        s2 = router.submit_stream("lm", readmit, max_new_tokens=_MAX_NEW)
        assert s2.wait(10) and s2.status == OK
        assert s2.tokens() == ref2
    finally:
        router.stop()


# ---------------------------------------------------------------------------
# multi-tenant QoS
# ---------------------------------------------------------------------------

def test_token_budget_sheds_overloaded_while_others_flow(fleet2, ref):
    fleet2.set_tenant("capped", token_budget=4)   # below one stream's need
    shed = fleet2.submit_stream("lm", _PROMPT, max_new_tokens=_MAX_NEW,
                                tenant="capped")
    assert shed.status == OVERLOADED and not shed.admitted
    assert "token budget" in shed.error
    assert shed.tokens() == []
    flow = fleet2.submit_stream("lm", _PROMPT, max_new_tokens=_MAX_NEW,
                                tenant="other")
    assert flow.wait(10) and flow.status == OK and flow.tokens() == ref
    snap = fleet2.tenant_snapshot()
    assert snap["capped"]["qos_sheds"] >= 1
    assert snap["other"]["ok"] >= 1
    assert snap["capped"]["inflight_tokens"] == 0


def test_weighted_share_sheds_only_under_contention(ref):
    router = _fleet(replicas=1)
    try:
        router.set_tenant("greedy", weight=1.0)
        router.set_tenant("vip", weight=4.0)
        rid = router.stats()["decode_models"]["lm"]["placement"][0]
        cache = router.engine("lm", rid)._cache
        assert cache.reserve("pin", 7)        # 1 unreserved block left
        try:
            # greedy's fair share is 32 * 1/5 tokens; a new stream needs 8
            # and the pool can't cover it -> weighted-fair shed
            s = router.submit_stream("lm", _PROMPT, max_new_tokens=_MAX_NEW,
                                     tenant="greedy")
            assert s.status == OVERLOADED
            assert "weighted share" in s.error
            # vip is under ITS share: the QoS gate passes it through (the
            # engine-level headroom refusal is a different, retryable path)
            s2 = router.submit_stream("lm", _PROMPT,
                                      max_new_tokens=_MAX_NEW, tenant="vip")
            assert s2.status != OVERLOADED or "share" not in (s2.error or "")
        finally:
            cache.release("pin")
        # contention gone: the same greedy tenant flows again
        s3 = router.submit_stream("lm", _PROMPT, max_new_tokens=_MAX_NEW,
                                  tenant="greedy")
        assert s3.wait(10) and s3.status == OK and s3.tokens() == ref
    finally:
        router.stop()


# ---------------------------------------------------------------------------
# observability fall-through (satellites 1 + 2) and scaling hooks
# ---------------------------------------------------------------------------

def test_engine_exposes_kv_headroom_and_health():
    eng = _factory("obs")
    try:
        snap = eng.stats_snapshot()
        assert snap["kv_capacity"] == 8
        assert snap["kv_blocks_free"] == 8          # idle: whole pool free
        assert snap["draining"] is False
        assert eng.health() == "HEALTHY"
        sig = eng.routing_signals()
        assert sig["kv_blocks_free"] == 8 and sig["kv_capacity"] == 8
        assert sig["kv_block_size"] == 4 and not sig["draining"]
        stats = eng.stats.snapshot()
        assert stats["kv_blocks_free"] == 8 and stats["kv_capacity"] == 8
    finally:
        eng.stop()


def test_kv_blocks_free_counter_lands_in_profiler_dump(tmp_path):
    from mxnet_tpu import profiler
    eng = _factory("prof")
    trace = str(tmp_path / "fleet_decode_profile.json")
    profiler.set_config(filename=trace)
    profiler.set_state("run")
    try:
        res = eng.generate(_PROMPT, max_new_tokens=_MAX_NEW,
                           timeout_ms=30000)
        assert res.status == OK
    finally:
        profiler.set_state("stop")
        profiler.dump()
        eng.stop()
    events = json.load(open(trace))["traceEvents"]
    counters = {e["name"] for e in events if e.get("ph") == "C"}
    assert "prof:kv_blocks_free" in counters, counters


def test_fleet_health_and_stats_fall_through_to_engines(fleet2):
    assert fleet2.health("lm") == "HEALTHY"
    with pytest.raises(MXNetError, match="no model"):
        fleet2.health("ghost")
    snap = fleet2.stats()
    placement = snap["decode_models"]["lm"]["placement"]
    assert len(placement) == 2
    for rid in placement:
        eng_snap = snap["engines"]["lm"][rid]
        assert eng_snap["kv_capacity"] == 8
        assert "kv_blocks_free" in eng_snap and "cache" in eng_snap
        assert snap["replicas"][rid]["engines"] == ["lm"]
    assert "decode" in snap and "tenants" in snap
    # an engine's INTERNAL breaker opening degrades the fleet answer even
    # though the router's own breaker never saw a failure
    eng = fleet2.engine("lm", placement[0])
    for _ in range(32):
        eng.breaker.on_failure()
        if eng.health() != "HEALTHY":
            break
    assert eng.health() != "HEALTHY"
    try:
        assert fleet2.health("lm") == "DEGRADED"
    finally:
        eng.breaker.on_success()
    assert fleet2.health("lm") == "HEALTHY"


def test_scaling_advice_and_policy_hooks(fleet2):
    assert fleet2.scaling_advice()["action"] == "scale_in"   # idle fleet
    placement = fleet2.stats()["decode_models"]["lm"]["placement"]
    caches = [fleet2.engine("lm", rid)._cache for rid in placement]
    for cache in caches:
        assert cache.reserve("pressure", 7)     # 7/8 promised: util 0.875
    fired = []
    fleet2.set_scaling_policy(scale_out=lambda router, adv:
                              fired.append(adv["action"]))
    try:
        advice = fleet2.poll_scaling()
        assert advice["action"] == "scale_out"
        assert advice["kv_utilization"] >= 0.85
        assert fired == ["scale_out"]
    finally:
        for cache in caches:
            cache.release("pressure")
        fleet2.set_scaling_policy()
    with pytest.raises(ValueError):
        fleet2.set_scaling_policy(high=0.2, low=0.8)


# ---------------------------------------------------------------------------
# iterator-vs-stop regression (satellite 3)
# ---------------------------------------------------------------------------

def test_iterating_stream_survives_engine_stop(ref):
    eng = _factory("stop-iter")
    stream = eng.submit(_PROMPT, max_new_tokens=_MAX_NEW,
                        on_token=lambda t: time.sleep(0.02))
    assert stream.admitted
    stopper = threading.Thread(target=lambda: (time.sleep(0.05),
                                               eng.stop()))
    stopper.start()
    got = []
    for tok in stream:          # must terminate cleanly, never hang
        got.append(tok)
    stopper.join(20)
    assert not stopper.is_alive()
    assert stream.status in (OK, UNAVAILABLE)
    assert got == ref[:len(got)], "partial prefix torn by the teardown"
    assert got == stream.tokens()


# ---------------------------------------------------------------------------
# chaos: the mxstress "decode_fleet" scenario (5 seeds, tier-1 budget)
# ---------------------------------------------------------------------------

def test_decode_fleet_chaos_five_seeds_zero_violations():
    from mxnet_tpu.analysis import schedule
    report = schedule.stress(seeds=schedule.FAULT_SMOKE_SEEDS,
                             scenarios=("decode_fleet",))
    flat = ["seed %s [%s] %s" % (seed, scen, v)
            for seed, per_seed in report["seeds"].items()
            for scen, violations in per_seed.items()
            for v in violations]
    assert report["violations"] == 0, "\n".join(flat)
    assert report["preemptions"] > 0        # the harness really perturbed


# ---------------------------------------------------------------------------
# serve_bench fleet-decode profile: the smoke's report and count gates
# ---------------------------------------------------------------------------

def test_serve_bench_fleet_decode_smoke_artifact(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import serve_bench
    out = str(tmp_path / "report.json")
    rc = serve_bench.main(["--smoke", "--profile", "fleet-decode",
                           "--out", out])
    assert rc == 0
    report = json.load(open(out))
    assert report["profile"] == "fleet-decode"
    assert report["statuses"] == {"OK": report["workload"]["streams"]}
    assert report["handoffs"] >= 1 and report["fenced"] == 0
    # reported for a chip run, never compared with a number here
    assert set(report["ttft_ms"]) == {"p50", "p99"}
    assert "tokens_per_s" in report
    drained = report["drained_mid_run"]
    assert report["engines"][drained]["drained"] is True
    for snap in report["engines"].values():
        assert snap["steady_state_recompiles"] == 0
        assert snap["kv_leaked_blocks"] == 0
