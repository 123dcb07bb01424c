"""Disaggregated prefill/decode serving (docs/SERVING.md "Disaggregated
prefill/decode").

Tier-1 gates for the disaggregation tentpole:

* **Handoff-at-first-token** — every stream admitted at the prefill tier
  emits its TTFT token there, hands off (K/V pages + sampler state +
  fencing token) to the decode tier, and finishes BITWISE-equal to the
  colocated single-engine reference, greedy and seeded-sampled alike.
* **One ledger** — cross-tier conservation settles on the prefill
  router's ``decode_stats`` (``requests == ok + timeouts + errors +
  unavailable``); the decode router admits nothing directly.
* **Failed adoption degrades, never hangs** — a draining/full decode
  tier terminates the stream UNAVAILABLE with its one-token prefix
  intact for re-admission.
* **Autoscaler** — SLO-breach scale-out joins a WARM replica
  (warm-before-cutover), idle scale-in drains the victim (in-flight
  streams migrate and stay bitwise), cooldown spaces actions, and
  decisions land as profiler Counters gated on ``profiling_active()``.
* **Open-loop traffic** — seeded Poisson/bursty/diurnal traces are
  bit-identical per seed and ``replay`` fires every arrival
  (arrival-count conservation), never waiting on completions.
* **Chaos + bench** — the mxstress ``disagg`` scenario holds over
  FAULT_SMOKE_SEEDS and ``serve_bench --profile disagg`` (smoke) passes
  its gates: goodput counted under p99 TTFT/TPOT SLOs on both
  equal-device legs, >= 1 handoff with zero failures, zero steady-state
  recompiles and zero leaked KV blocks on every engine of both legs.
"""
import json
import os
import re
import sys

import pytest

from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving import OK, UNAVAILABLE, traffic
from mxnet_tpu.serving.decode import DecodeEngine, TinyCausalLM
from mxnet_tpu.serving.disagg import Autoscaler, DisaggRouter, TierPolicy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_MODEL_KW = dict(vocab_size=24, hidden=16, num_layers=1, num_heads=2,
                 max_len=32, seed=11)
_ENGINE_KW = dict(max_slots=2, block_size=4, num_blocks=32,
                  max_prompt_len=8, max_new_tokens=6, max_queue=16,
                  prefill_chunk=4)
_PROMPTS = [[5, 3, 7, 1], [2, 6, 4], [9, 8, 1, 2, 3], [7, 7]]
_SAMPLE = dict(temperature=0.8, top_k=6, seed=321)


def _prefill_factory(name):
    return DecodeEngine(TinyCausalLM(**_MODEL_KW), name=name,
                        prefill_only=True, **_ENGINE_KW)


def _decode_factory(name):
    return DecodeEngine(TinyCausalLM(**_MODEL_KW), name=name, **_ENGINE_KW)


def _make_router(prefill=1, decode=1):
    dr = DisaggRouter(prefill_replicas=prefill, decode_replicas=decode,
                      failover_budget=2)
    dr.load("lm", _prefill_factory, _decode_factory,
            prefill_replicas=prefill, decode_replicas=decode)
    return dr


@pytest.fixture(scope="module")
def refs():
    """Colocated single-engine references (greedy + sampled) — the
    bitwise contract is disaggregated-vs-colocated."""
    eng = _decode_factory("disagg-ref")
    try:
        greedy = [eng.generate_reference(p, 6).tolist() for p in _PROMPTS]
        sampled = [eng.generate_reference(p, 6, **_SAMPLE).tolist()
                   for p in _PROMPTS]
    finally:
        eng.stop()
    return greedy, sampled


# ---------------------------------------------------------------------------
# open-loop traffic generation (serving/traffic.py)
# ---------------------------------------------------------------------------

def test_poisson_trace_seeded_reproducible():
    a = traffic.poisson_trace(50.0, 2.0, seed=7)
    b = traffic.poisson_trace(50.0, 2.0, seed=7)
    c = traffic.poisson_trace(50.0, 2.0, seed=8)
    assert a == b                       # bit-identical per seed
    assert a != c
    assert a == sorted(a)
    assert all(0.0 <= t < 2.0 for t in a)
    # roughly rate * duration arrivals (loose: Poisson tail)
    assert 40 <= len(a) <= 170


def test_bursty_trace_reproducible_and_denser_in_bursts():
    a = traffic.bursty_trace(50.0, 4.0, seed=3, burst_factor=6.0,
                             burst_fraction=0.25, n_bursts=2)
    assert a == traffic.bursty_trace(50.0, 4.0, seed=3, burst_factor=6.0,
                                     burst_fraction=0.25, n_bursts=2)
    assert a == sorted(a) and all(0.0 <= t < 4.0 for t in a)
    # each 2 s period bursts in its first 0.5 s at 6x: the burst windows
    # must be visibly denser than the off-burst remainder
    in_burst = sum(1 for t in a if (t % 2.0) < 0.5)
    per_s_burst = in_burst / 1.0
    per_s_base = (len(a) - in_burst) / 3.0
    assert per_s_burst > 2.0 * per_s_base


def test_diurnal_trace_reproducible():
    a = traffic.diurnal_trace(80.0, 2.0, seed=5, depth=0.8)
    assert a == traffic.diurnal_trace(80.0, 2.0, seed=5, depth=0.8)
    assert a == sorted(a) and all(0.0 <= t < 2.0 for t in a)
    assert a != traffic.diurnal_trace(80.0, 2.0, seed=6, depth=0.8)


def test_trace_validation_errors():
    with pytest.raises(ValueError, match="rate_hz"):
        traffic.poisson_trace(0.0, 1.0)
    with pytest.raises(ValueError, match="duration_s"):
        traffic.poisson_trace(1.0, 0.0)
    with pytest.raises(ValueError, match="burst_factor"):
        traffic.bursty_trace(1.0, 1.0, burst_factor=0.5)
    with pytest.raises(ValueError, match="burst_fraction"):
        traffic.bursty_trace(1.0, 1.0, burst_fraction=1.0)
    with pytest.raises(ValueError, match="depth"):
        traffic.diurnal_trace(1.0, 1.0, depth=1.0)
    with pytest.raises(ValueError, match="at least one tenant"):
        traffic.tenant_mix([0.1], {})
    with pytest.raises(ValueError, match="weight"):
        traffic.tenant_mix([0.1], {"a": 0.0})
    with pytest.raises(ValueError, match="time_scale"):
        traffic.replay([0.1], lambda i, t: None, time_scale=0.0)


def test_tenant_mix_reproducible_aligned_and_weighted():
    arrivals = traffic.poisson_trace(200.0, 2.0, seed=1)
    mix = traffic.tenant_mix(arrivals, {"free": 1.0, "paid": 3.0}, seed=2)
    assert mix == traffic.tenant_mix(arrivals, {"free": 1.0, "paid": 3.0},
                                     seed=2)
    assert len(mix) == len(arrivals)
    assert set(mix) == {"free", "paid"}
    # 3:1 weighting: paid dominates (loose bound, seeded draw)
    assert mix.count("paid") > 2 * mix.count("free")


def test_replay_fires_every_arrival_in_order():
    """Arrival-count conservation under an injected clock: every arrival
    fires exactly once, in order, at-or-after its scheduled offset."""
    arrivals = traffic.poisson_trace(100.0, 1.0, seed=9)
    clock = [0.0]

    def now():
        return clock[0]

    def sleep(dt):
        clock[0] += dt

    fired = []
    n = traffic.replay(arrivals, lambda i, t: fired.append((i, t)),
                       now=now, sleep=sleep)
    assert n == len(arrivals) == len(fired)
    assert fired == [(i, t) for i, t in enumerate(arrivals)]
    assert clock[0] >= arrivals[-1]


def test_replay_open_loop_never_drops_when_behind():
    """A submit path slower than the arrival gaps must not drop or delay
    later arrivals indefinitely — past-due arrivals fire immediately."""
    arrivals = [0.001 * i for i in range(50)]
    clock = [0.0]
    fired = []

    def slow_submit(i, t):
        fired.append(i)
        clock[0] += 0.01            # 10x slower than the arrival gap

    n = traffic.replay(arrivals, slow_submit,
                       now=lambda: clock[0],
                       sleep=lambda dt: clock.__setitem__(0, clock[0] + dt))
    assert n == 50 and fired == list(range(50))


# ---------------------------------------------------------------------------
# DisaggRouter: handoff-at-first-token, bitwise, one ledger
# ---------------------------------------------------------------------------

def test_handoff_bitwise_greedy_and_sampled(refs):
    greedy_refs, sampled_refs = refs
    with _make_router() as dr:
        streams = []
        for p in _PROMPTS:
            streams.append((dr.submit_stream("lm", list(p),
                                             max_new_tokens=6), False))
            streams.append((dr.submit_stream("lm", list(p),
                                             max_new_tokens=6, **_SAMPLE),
                            True))
        for i, (s, sampled) in enumerate(streams):
            assert s.wait(30.0), "stream %d never terminated" % i
            ref = (sampled_refs if sampled else greedy_refs)[i // 2]
            assert s.status == OK, (i, s.status, s.error)
            assert s.tokens() == ref, (i, s.tokens(), ref)
            assert s.ttft_ms is not None
        hand = dr.stats()["disagg"]
        assert hand["handoffs"] == len(streams)
        assert hand["handoff_failures"] == 0
        assert "p50" in hand["handoff_ms"]


def test_cross_tier_conservation_on_single_ledger():
    with _make_router() as dr:
        for p in _PROMPTS:
            s = dr.submit_stream("lm", list(p), max_new_tokens=6)
            assert s.wait(30.0) and s.status == OK
        ledger = dr.prefill.decode_stats.snapshot()
        assert ledger["requests"] == len(_PROMPTS)
        assert ledger["requests"] == (ledger["ok"] + ledger["timeouts"]
                                      + ledger["errors"]
                                      + ledger["unavailable"])
        # the decode tier admits nothing directly: adopted streams are
        # not submissions, so its ledger stays at zero requests
        assert dr.decode.decode_stats.snapshot()["requests"] == 0
        # the decode ENGINE did the work: it imported every stream
        d_eng = dr.decode.stats()["engines"]["lm"]
        assert sum(s["imported"] for s in d_eng.values()) == len(_PROMPTS)
        p_eng = dr.prefill.stats()["engines"]["lm"]
        assert sum(s["handed_off"] for s in p_eng.values()) == len(_PROMPTS)


def test_prefill_factory_must_be_prefill_only():
    # the per-engine check raises "must be built with prefill_only=True";
    # the rebalancer treats a refusing factory as an unplaceable replica,
    # so the load surfaces as a placement failure — either way it FAILS
    dr = DisaggRouter(prefill_replicas=1, decode_replicas=1)
    try:
        with pytest.raises(MXNetError,
                           match="prefill_only=True|could not place"):
            dr.load("lm", _decode_factory, _decode_factory)
        # the failed load rolled the decode tier back: the name is free
        dr.load("lm", _prefill_factory, _decode_factory)
        s = dr.submit_stream("lm", [5, 3, 7], max_new_tokens=4)
        assert s.wait(30.0) and s.status == OK
    finally:
        dr.stop()


def test_failed_adoption_terminates_unavailable_with_prefix():
    """With the only decode replica draining, the handoff finds no home:
    the stream must terminate UNAVAILABLE carrying its one-token (TTFT)
    prefix for re-admission — and the ledger still conserves."""
    with _make_router() as dr:
        (rid,) = [r for r, st in dr.decode.replicas().items()
                  if st == "LIVE"]
        dr.decode.drain(rid)
        s = dr.submit_stream("lm", [5, 3, 7, 1], max_new_tokens=6)
        assert s.wait(30.0)
        assert s.status == UNAVAILABLE, (s.status, s.error)
        assert len(s.tokens()) == 1     # exactly the TTFT token
        hand = dr.stats()["disagg"]
        assert hand["handoff_failures"] >= 1
        ledger = dr.prefill.decode_stats.snapshot()
        assert ledger["requests"] == (ledger["ok"] + ledger["timeouts"]
                                      + ledger["errors"]
                                      + ledger["unavailable"])
        assert ledger["unavailable"] >= 1


def test_scaling_advice_per_tier_breakdown():
    with _make_router() as dr:
        advice = dr.scaling_advice()
        assert set(advice) == {"prefill", "decode"}
        for tier in ("prefill", "decode"):
            tier_advice = advice[tier]
            assert tier_advice["action"] in ("scale_out", "scale_in",
                                             "hold")
            row = tier_advice["engines"]["lm"]
            assert row["replicas"] == 1
            assert row["devices_in_use"] >= 1
            assert 0.0 <= row["kv_utilization"] <= 1.0
            assert 0.0 <= row["queue_fill"] <= 1.0
            assert isinstance(row["reasons"], list)


# ---------------------------------------------------------------------------
# Autoscaler: SLO-driven scale-out/in, cooldown, profiler counters
# ---------------------------------------------------------------------------

def test_tier_policy_validation():
    with pytest.raises(ValueError, match="min_replicas"):
        TierPolicy(min_replicas=0)
    with pytest.raises(ValueError, match="max_replicas"):
        TierPolicy(min_replicas=3, max_replicas=2)
    with pytest.raises(ValueError, match="kv_low < kv_high"):
        TierPolicy(kv_low=0.9, kv_high=0.5)
    with pytest.raises(ValueError, match="queue_low < queue_high"):
        TierPolicy(queue_low=0.9, queue_high=0.5)


def test_autoscaler_scale_out_on_slo_breach_joins_warm_replica(refs):
    greedy_refs, _ = refs
    with _make_router() as dr:
        # populate the TTFT window so the p99 signal is live
        s = dr.submit_stream("lm", list(_PROMPTS[0]), max_new_tokens=6)
        assert s.wait(30.0) and s.status == OK
        sc = Autoscaler(
            dr,
            prefill=TierPolicy(max_replicas=2, slo_p99_ttft_ms=1e-6),
            decode=TierPolicy(max_replicas=2))
        decisions = sc.poll()
        pre = decisions["prefill"]
        assert pre["action"] == "scale_out", pre
        assert pre["replicas"] == 2
        assert any("TTFT" in r for r in pre["reasons"])
        assert pre["p99_ttft_ms"] is not None
        # decode tier had no breach and sits at min_replicas: hold
        assert decisions["decode"]["action"] == "hold"
        assert [d["tier"] for d in sc.decisions] == ["prefill"]
        # the joined replica is placed AND warm: traffic through the
        # scaled tier still lands bitwise (a cold engine would recompile
        # or misroute, not silently match the reference)
        dr.wait_converged(10.0)
        placement = dr.prefill.stats()["decode_models"]["lm"]["placement"]
        assert len(placement) == 2
        for i, p in enumerate(_PROMPTS):
            s = dr.submit_stream("lm", list(p), max_new_tokens=6)
            assert s.wait(30.0) and s.status == OK
            assert s.tokens() == greedy_refs[i]
        for snap in dr.prefill.stats()["engines"]["lm"].values():
            assert (snap["cache"]["recompiles"]
                    == snap["warmup"]["cache"]["misses"])


def test_autoscaler_scale_in_drains_victim_and_streams_survive(refs):
    greedy_refs, _ = refs
    with _make_router(decode=2) as dr:
        # in-flight streams when the victim drains: they must migrate
        # and finish bitwise, not die with the replica
        streams = [dr.submit_stream("lm", list(p), max_new_tokens=6)
                   for p in _PROMPTS]
        sc = Autoscaler(
            dr,
            prefill=TierPolicy(),
            decode=TierPolicy(min_replicas=1, kv_low=0.98, kv_high=0.99,
                              queue_low=0.98, queue_high=0.99))
        decisions = sc.poll()
        dec = decisions["decode"]
        assert dec["action"] == "scale_in", dec
        assert dec["replicas"] == 1
        live = [r for r, st in dr.decode.replicas().items() if st == "LIVE"]
        assert len(live) == 1
        for i, s in enumerate(streams):
            assert s.wait(30.0), "stream %d never terminated" % i
            assert s.status == OK, (i, s.status, s.error)
            assert s.tokens() == greedy_refs[i]


def test_autoscaler_cooldown_spaces_actions():
    with _make_router() as dr:
        s = dr.submit_stream("lm", list(_PROMPTS[0]), max_new_tokens=6)
        assert s.wait(30.0) and s.status == OK
        sc = Autoscaler(
            dr,
            prefill=TierPolicy(max_replicas=4, slo_p99_ttft_ms=1e-6,
                               cooldown_s=3600.0),
            decode=TierPolicy())
        assert sc.poll()["prefill"]["action"] == "scale_out"
        second = sc.poll()["prefill"]
        assert second["action"] == "hold"
        assert any("cooldown" in r for r in second["reasons"])
        assert len([d for d in sc.decisions
                    if d["tier"] == "prefill"]) == 1


def test_autoscaler_and_handoff_counters_in_profiler_dump(tmp_path):
    from mxnet_tpu import profiler
    trace = str(tmp_path / "disagg_profile.json")
    profiler.set_config(filename=trace)
    profiler.set_state("run")
    try:
        with _make_router() as dr:
            s = dr.submit_stream("lm", list(_PROMPTS[0]), max_new_tokens=6)
            assert s.wait(30.0) and s.status == OK
            Autoscaler(dr).poll()
    finally:
        profiler.set_state("stop")
        profiler.dump()
    events = json.load(open(trace))["traceEvents"]
    counters = {e["name"] for e in events if e.get("ph") == "C"}
    for name in ("prefill:handoff_ms", "prefill:replicas",
                 "decode:replicas", "prefill:slo_p99_ttft_ms",
                 "decode:slo_p99_tpot_ms"):
        assert name in counters, (name, counters)


# ---------------------------------------------------------------------------
# chaos: the mxstress "disagg" scenario (5 seeds, tier-1 budget)
# ---------------------------------------------------------------------------

def test_disagg_chaos_five_seeds_zero_violations():
    from mxnet_tpu.analysis import schedule
    report = schedule.stress(seeds=schedule.FAULT_SMOKE_SEEDS,
                             scenarios=("disagg",))
    flat = ["seed %s [%s] %s" % (seed, scen, v)
            for seed, per_seed in report["seeds"].items()
            for scen, violations in per_seed.items()
            for v in violations]
    assert report["violations"] == 0, "\n".join(flat)
    assert report["preemptions"] > 0        # the harness really perturbed


# ---------------------------------------------------------------------------
# serve_bench disagg profile: registry drift, smoke, committed artifact
# ---------------------------------------------------------------------------

def _import_serve_bench():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import serve_bench
    return serve_bench


def test_profiles_table_is_single_source_of_truth(capsys):
    """The PROFILES registry drives argparse choices and dispatch —
    drift between the table, the CLI, and the docstring fails here, not
    in production.  No profile names a file: a report goes where
    ``--out`` says."""
    serve_bench = _import_serve_bench()
    for name, prof in serve_bench.PROFILES.items():
        assert callable(prof["run"]), name
        assert set(prof) <= {"run", "env"}, name
        assert name in serve_bench.__doc__, (
            "profile %r missing from the serve_bench docstring" % name)
    assert "disagg" in serve_bench.PROFILES
    # the CLI's --profile choices come FROM the table (a profile added
    # to the table is immediately invocable)
    with pytest.raises(SystemExit):
        serve_bench.main(["--profile", "no-such-profile"])
    err = capsys.readouterr().err
    # argparse quotes the choices on some Python versions and not others
    listed = set(re.findall(r"[a-z][a-z-]*", err.split("choose from")[-1]))
    assert listed == set(serve_bench.PROFILES)


def test_scan_prefixes_cover_disagg_package():
    """mxlint --since must trigger the sharding lint when serving/disagg/
    changes (the pass skip keys on SCAN_PREFIXES)."""
    from mxnet_tpu.analysis.sharding_lint import SCAN_PREFIXES
    assert "mxnet_tpu/serving/disagg/" in SCAN_PREFIXES


def test_serve_bench_disagg_smoke_artifact(tmp_path):
    serve_bench = _import_serve_bench()
    out = str(tmp_path / "report.json")
    rc = serve_bench.main(["--smoke", "--profile", "disagg",
                           "--out", out])
    assert rc == 0
    report = json.load(open(out))
    assert report["profile"] == "disagg"
    _check_disagg_report(report)


def _check_disagg_report(report):
    wl = report["workload"]
    assert wl["arrivals"] > 0
    for key in ("colocated", "disagg"):
        leg = report[key]
        assert leg["fired"] == leg["arrivals"] == wl["arrivals"], key
        assert sum(leg["statuses"].values()) == wl["arrivals"], key
        assert leg["conserved"] is True, key
        assert leg["pools_whole"] is True, key
        assert leg["bitwise_equal_reference"] is True, key
        good = leg["goodput"]
        assert good["total"] == wl["arrivals"]
        assert 0 <= good["good"] <= good["ok"] <= good["total"]
        # times and rates are reported for a chip run, never compared
        # with a number here
        assert {"p50", "p99"} <= set(good["ttft_ms"])
        assert {"p50", "p99"} <= set(good["tpot_ms"])
        assert "goodput_per_s" in leg
        for ekey, snap in leg["engines"].items():
            assert snap["steady_state_recompiles"] == 0, (key, ekey)
            assert snap["kv_leaked_blocks"] == 0, (key, ekey)
    hand = report["disagg"]["handoffs"]
    assert hand["handoffs"] >= 1
    assert hand["handoff_failures"] == 0
    assert report["colocated"]["devices"] == report["disagg"]["devices"]
    # the prefill tier never decodes: every engine there handed off or
    # degraded, none kept a stream past its first token
    p_requests = sum(s["requests"]
                     for k, s in report["disagg"]["engines"].items()
                     if k.startswith("prefill/"))
    p_handed = sum(s["handed_off"]
                   for k, s in report["disagg"]["engines"].items()
                   if k.startswith("prefill/"))
    assert p_requests > 0 and p_handed > 0
    # the HBM accountant's ledger of KV blocks drains over both legs;
    # the engine-lifetime pools stay charged
    mem = report["memory"]
    assert mem["balanced"] is True and mem["kv_regions"] >= 1
    assert mem["kv_live_bytes"] == 0
    assert mem["kv_pool_bytes"] > 0 and mem["kv_peak_bytes"] > 0
