"""Zero-downtime continuous deployment (docs/ROBUSTNESS.md "Rolling
deployment").

Tier-1 gates for the generation-fenced live weight hot-swap:

* the full swap: ``DeploymentController.poll()`` resolves the newest
  manifest-complete checkpoint, warms the new generation OUTSIDE the
  router lock, fences, commits one atomic routing flip, retires — post-
  swap output is bitwise the new generation's, a stream in flight ACROSS
  the swap finishes bitwise on the generation it started on (invariant
  13), and a repeated poll is a no-op;
* health-gated rollback: an ``slo_probe`` complaint in the canary window
  reverts to the previous generation bitwise and records the rejection;
* chaos: a controller killed at EVERY ``deploy.*`` fault point — and a
  replica killed mid-swap — leaves the fleet HEALTHY on ONE consistent
  generation, and a fresh controller's ``recover()`` + redeploy succeed
  (plus the mxstress ``deploy`` scenario over FAULT_SMOKE_SEEDS);
* manifest edges: a torn newest entry is simply not a candidate, legacy
  prefixes need the explicit ``allow_unverified`` opt-in, a generation
  published mid-swap QUEUES behind the running swap (never interleaves);
* the train->serve loop: a fit killed mid-run and resumed via
  ``fit(auto_resume=True)`` publishes a checkpoint the controller
  deploys, and the served weights are bitwise the uninterrupted run's;
* ``model.prune_checkpoints``: retention GC that never touches the
  newest complete entry, spares in-progress (newer torn) saves and
  shared files, and sweeps ``write_atomic`` crash debris;
* ``FleetRouter.wait_converged(reason_on_timeout=True)`` diagnoses a
  wedged rebalance instead of parking the caller;
* observability: ``deploy:generation`` / ``deploy:swap_ms`` /
  ``deploy:rollbacks`` profiler counters, the ``stats()["deploy"]``
  section, and the serve_bench ``deploy`` profile artifact gates.
"""
import json
import os
import re
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faults, nd
from mxnet_tpu import model as model_mod
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving import OK, deploy
from mxnet_tpu.serving.decode import DecodeEngine, TinyCausalLM
from mxnet_tpu.serving.fleet import FleetRouter
from mxnet_tpu.serving.health import HEALTHY

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_MODEL_KW = dict(vocab_size=20, hidden=16, num_layers=1, num_heads=2,
                 max_len=24)
_ENGINE_KW = dict(max_slots=2, block_size=4, num_blocks=12,
                  max_prompt_len=4, max_new_tokens=5, max_queue=8,
                  width_blocks=[4])
_PROMPT = [3, 1, 2]
_SEED_A, _SEED_B = 7, 99


def _save_gen(prefix, epoch, seed):
    """Publish one TinyCausalLM weight generation as a manifest-complete
    checkpoint epoch."""
    lm = TinyCausalLM(seed=seed, **_MODEL_KW)
    model_mod.save_checkpoint(prefix, epoch, mx.sym.Variable("data"),
                              dict(lm._params), {})


def _build_engine(srv_name, arg_params, aux_params, generation):
    lm = TinyCausalLM(params=arg_params, **_MODEL_KW)
    return DecodeEngine(lm, name=srv_name, generation=generation,
                        **_ENGINE_KW)


def _baseline_engine(name):
    return DecodeEngine(TinyCausalLM(seed=_SEED_A, **_MODEL_KW),
                        name=name, **_ENGINE_KW)


@pytest.fixture(scope="module")
def refs():
    """Greedy references per weight generation; distinct by fixture."""
    out = {}
    for seed in (_SEED_A, _SEED_B):
        eng = DecodeEngine(TinyCausalLM(seed=seed, **_MODEL_KW),
                           name="deploy-ref%d" % seed, **_ENGINE_KW)
        try:
            out[seed] = eng.generate_reference(_PROMPT, 5).tolist()
        finally:
            eng.stop()
    assert out[_SEED_A] != out[_SEED_B], "seeds give identical outputs"
    return out


def _fresh_fleet(prefix, replicas=2):
    """A live fleet on generation-1 (seed A) weights, published at
    ``prefix`` epoch 1 and rolled in so every engine carries the tag."""
    _save_gen(prefix, 1, _SEED_A)
    router = FleetRouter(replicas=replicas, failover_budget=2)
    router.load_decode("lm", _baseline_engine, replicas=replicas)
    ctl = deploy.DeploymentController(router, prefix,
                                      engines={"lm": _build_engine})
    rep = ctl.poll()
    assert rep["status"] == "deployed" and rep["generation"] == 1
    return router, ctl


def _stream_tokens(router, timeout=15.0, **kw):
    s = router.submit_stream("lm", _PROMPT, max_new_tokens=5, **kw)
    assert s.wait(timeout), "stream hung"
    assert s.status == OK, (s.status, s.error)
    return s.tokens()


# ---------------------------------------------------------------------------
# the full swap: bitwise flip, mid-swap pinning, idempotence, rollback
# ---------------------------------------------------------------------------

def test_full_swap_is_bitwise_and_idempotent(tmp_path, refs):
    prefix = str(tmp_path / "ck")
    router, ctl = _fresh_fleet(prefix)
    with router:
        assert _stream_tokens(router) == refs[_SEED_A]
        _save_gen(prefix, 2, _SEED_B)
        rep = ctl.poll()
        assert rep["status"] == "deployed" and rep["generation"] == 2
        assert rep["previous"] == 1
        # every staged replica reports its warmup compile count
        placed = router.stats()["decode_models"]["lm"]["placement"]
        assert set(rep["warmup_compiles"]) == {"lm@%s" % r for r in placed}
        assert all(c > 0 for c in rep["warmup_compiles"].values())
        assert _stream_tokens(router) == refs[_SEED_B]
        # nothing new: poll is a no-op, the fleet keeps serving
        assert ctl.poll() is None
        st = router.stats()["deploy"]
        assert st["generation"] == 2 and st["previous"] == 1
        assert st["in_progress"] is None and st["retiring"] == 0
        # the swap left zero steady-state recompiles on the new engines
        for rid, snap in router.stats()["engines"]["lm"].items():
            assert snap["generation"] == 2, rid
            assert snap["cache"]["recompiles"] \
                == snap["warmup"]["cache"]["misses"], rid


def test_mid_swap_stream_finishes_on_its_own_generation(tmp_path, refs):
    prefix = str(tmp_path / "ck")
    router, ctl = _fresh_fleet(prefix)
    with router:
        _save_gen(prefix, 2, _SEED_B)
        slow = lambda t: time.sleep(0.01)
        pre = router.submit_stream("lm", _PROMPT, max_new_tokens=5,
                                   on_token=slow)
        rep = ctl.poll()
        assert rep["status"] == "deployed" and rep["generation"] == 2
        assert pre.wait(20.0), "pre-swap stream hung"
        # started on generation 1 -> finished bitwise on generation 1,
        # even though the fleet committed generation 2 mid-stream
        assert pre.status == OK and pre.tokens() == refs[_SEED_A], \
            (pre.status, pre.tokens())
        assert _stream_tokens(router) == refs[_SEED_B]


def test_slo_probe_rollback_restores_old_weights_bitwise(tmp_path, refs):
    prefix = str(tmp_path / "ck")
    router, ctl = _fresh_fleet(prefix)
    with router:
        _save_gen(prefix, 2, _SEED_B)
        bad = deploy.DeploymentController(
            router, prefix, engines={"lm": _build_engine},
            slo_probe=lambda r: "ttft regression (planted)")
        rep = bad.poll()
        assert rep["status"] == "rolled_back"
        assert "planted" in rep["rollback_reason"]
        st = router.stats()["deploy"]
        assert st["generation"] == 1
        assert st["last_rollback"] == {"generation": 2,
                                       "reason": "ttft regression "
                                                 "(planted)"}
        assert router.health() == HEALTHY
        assert _stream_tokens(router) == refs[_SEED_A], \
            "rollback left the wrong weights serving"
        # epoch 2 is still the newest on disk: the controller keeps
        # trying (and keeps getting vetoed) rather than wedging
        assert bad.poll()["status"] == "rolled_back"
        assert bad.stats()["rollbacks"] == 2


# ---------------------------------------------------------------------------
# chaos: controller killed at every deploy.* fault point; replica killed
# mid-swap.  Either way: ONE consistent generation, clean redeploy.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("site", ["deploy.resolve", "deploy.warmup",
                                  "deploy.cutover", "deploy.commit"])
def test_controller_killed_at_fault_point_leaves_old_generation(
        tmp_path, refs, site):
    prefix = str(tmp_path / "ck")
    router, ctl = _fresh_fleet(prefix)
    with router:
        _save_gen(prefix, 2, _SEED_B)
        plan = faults.FaultPlan(0).add(site, kind="crash", times=1)
        with faults.plan(plan):
            with pytest.raises(faults.SimulatedCrash):
                ctl.poll()
        # the controller "died".  A fresh one recovers; the fleet must be
        # HEALTHY on the OLD generation with no staging debris.
        ctl2 = deploy.DeploymentController(router, prefix,
                                           engines={"lm": _build_engine})
        rec = ctl2.recover()
        assert rec["generation"] == 1, (site, rec)
        assert router.health() == HEALTHY, site
        st = router.stats()["deploy"]
        assert st["generation"] == 1 and st["in_progress"] is None \
            and st["retiring"] == 0, (site, st)
        assert _stream_tokens(router) == refs[_SEED_A], site
        # and the queued generation still deploys cleanly afterwards
        rep = ctl2.poll()
        assert rep["status"] == "deployed" and rep["generation"] == 2, site
        assert _stream_tokens(router) == refs[_SEED_B], site


def test_replica_killed_mid_swap_never_mixes_generations(
        tmp_path, refs, monkeypatch):
    prefix = str(tmp_path / "ck")
    router, ctl = _fresh_fleet(prefix)
    with router:
        _save_gen(prefix, 2, _SEED_B)
        # kill a replica during the SECOND warmup: one staged copy lands
        # on a replica that then dies, and the staging sweep must abort
        # the swap rather than commit a partial flip
        warmups = []
        real_fp = faults.fault_point

        def chaos_fp(site, **info):
            if site == "deploy.warmup":
                warmups.append(info)
                if len(warmups) == 2:
                    router.kill_replica(info["rid"])
            return real_fp(site, **info)

        monkeypatch.setattr(faults, "fault_point", chaos_fp)
        with pytest.raises(MXNetError, match="died mid-swap"):
            ctl.poll()
        monkeypatch.setattr(faults, "fault_point", real_fp)
        # whatever died, the survivors serve ONE consistent generation
        ctl2 = deploy.DeploymentController(router, prefix,
                                           engines={"lm": _build_engine})
        ctl2.recover()
        gen = router.stats()["deploy"]["generation"]
        assert gen == 1
        assert _stream_tokens(router) == refs[_SEED_A]
        # repair the fleet; the queued generation deploys once converged
        router.add_replica()
        assert router.wait_converged(timeout_s=10.0)
        rep = ctl2.poll()
        assert rep["status"] == "deployed" and rep["generation"] == 2
        assert _stream_tokens(router) == refs[_SEED_B]


def test_deploy_chaos_five_seeds_zero_violations():
    from mxnet_tpu.analysis import schedule
    report = schedule.stress(seeds=schedule.FAULT_SMOKE_SEEDS,
                             scenarios=("deploy",))
    flat = ["seed %s [%s] %s" % (seed, scen, v)
            for seed, per_seed in report["seeds"].items()
            for scen, violations in per_seed.items()
            for v in violations]
    assert report["violations"] == 0, "\n".join(flat)
    assert report["preemptions"] > 0        # the harness really perturbed


# ---------------------------------------------------------------------------
# manifest edges: torn newest entry, legacy prefix, mid-swap publish
# ---------------------------------------------------------------------------

def test_torn_newest_checkpoint_is_not_a_candidate(tmp_path, refs):
    prefix = str(tmp_path / "ck")
    router, ctl = _fresh_fleet(prefix)
    with router:
        # epoch 2 lands torn (crashed mid-write): its manifest entry
        # fails the hash check, so the watcher never even stages it
        _save_gen(prefix, 2, _SEED_B)
        with open("%s-0002.params" % prefix, "r+b") as f:
            f.seek(10)
            f.write(b"\xff\xff\xff\xff")
        assert model_mod.latest_complete_checkpoint(prefix) == 1
        assert ctl.poll() is None
        assert router.stats()["deploy"]["generation"] == 1
        assert _stream_tokens(router) == refs[_SEED_A]
        # the repaired publish (epoch 3) deploys normally
        _save_gen(prefix, 3, _SEED_B)
        rep = ctl.poll()
        assert rep["status"] == "deployed" and rep["generation"] == 3
        assert _stream_tokens(router) == refs[_SEED_B]


def test_legacy_prefix_needs_allow_unverified_opt_in(tmp_path, refs):
    prefix = str(tmp_path / "legacy")
    _save_gen(prefix, 1, _SEED_B)
    os.remove("%s-manifest.json" % prefix)
    router = FleetRouter(replicas=2, failover_budget=2)
    with router:
        router.load_decode("lm", _baseline_engine, replicas=2)
        strict = deploy.DeploymentController(router, prefix,
                                             engines={"lm": _build_engine})
        # no manifest -> nothing provably complete -> nothing to deploy
        assert strict.poll() is None
        legacy = deploy.DeploymentController(router, prefix,
                                             engines={"lm": _build_engine},
                                             allow_unverified=True)
        rep = legacy.poll()
        assert rep["status"] == "deployed" and rep["generation"] == 1
        assert _stream_tokens(router) == refs[_SEED_B]


def test_generation_published_mid_swap_queues_not_interleaves(
        tmp_path, refs):
    prefix = str(tmp_path / "ck")
    router, ctl = _fresh_fleet(prefix)
    staging = threading.Event()

    def slow_build(srv_name, arg_params, aux_params, generation):
        staging.set()
        time.sleep(0.15)    # hold the swap open while epoch 3 publishes
        return _build_engine(srv_name, arg_params, aux_params, generation)

    slow_ctl = deploy.DeploymentController(router, prefix,
                                           engines={"lm": slow_build})
    with router:
        _save_gen(prefix, 2, _SEED_B)
        first = threading.Thread(target=slow_ctl.deploy, args=(2,))
        first.start()
        assert staging.wait(10.0), "first swap never started staging"
        _save_gen(prefix, 3, _SEED_A)
        # queued behind the running swap on the controller's swap lock:
        # this poll() BLOCKS until generation 2 commits, then rolls 3
        rep = slow_ctl.poll()
        first.join(30.0)
        assert rep["status"] == "deployed" and rep["generation"] == 3
        assert rep["previous"] == 2, "mid-swap publish interleaved"
        history = [(h["previous"], h["generation"])
                   for h in slow_ctl.stats()["history"]]
        assert history == [(1, 2), (2, 3)]
        assert _stream_tokens(router) == refs[_SEED_A]


# ---------------------------------------------------------------------------
# the train->serve loop: crash mid-fit, auto_resume, publish, deploy
# ---------------------------------------------------------------------------

_N, _F = 16, 5


def _fit_data():
    from mxnet_tpu import io
    rng = np.random.RandomState(11)
    X = rng.randn(_N, _F).astype(np.float32)
    Y = (rng.rand(_N) > 0.5).astype(np.float32)
    return io.NDArrayIter(X, Y, batch_size=8)


def _run_fit(prefix, resume=False, crash_plan=None):
    x = mx.sym.Variable("data")
    y = mx.sym.FullyConnected(x, num_hidden=4, name="fc1")
    y = mx.sym.Activation(y, act_type="relu")
    y = mx.sym.FullyConnected(y, num_hidden=2, name="fc2")
    mod = mx.mod.Module(mx.sym.SoftmaxOutput(y, name="softmax"),
                        context=mx.cpu())
    cbs = [mx.callback.module_checkpoint(mod, prefix,
                                         save_optimizer_states=True)]
    mx.random.seed(1234)
    kw = dict(num_epoch=2, optimizer="sgd",
              optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
              initializer=mx.init.Xavier(), epoch_end_callback=cbs)
    if crash_plan is not None:
        with faults.plan(crash_plan):
            mod.fit(_fit_data(), **kw)
    else:
        mod.fit(_fit_data(), auto_resume=resume, **kw)
    return mod.get_params()


class _FitNet(mx.gluon.HybridBlock):
    """The Gluon serving twin of the fitted symbol module."""

    def __init__(self, **kw):
        super().__init__(**kw)
        with self.name_scope():
            from mxnet_tpu.gluon import nn
            self.fc1 = nn.Dense(4, activation="relu", in_units=_F)
            self.fc2 = nn.Dense(2, in_units=4)

    def hybrid_forward(self, F, x):
        return self.fc2(self.fc1(x))


def _fit_block(arg_params):
    net = _FitNet()
    net.initialize(mx.init.Zero())
    net.fc1.weight.set_data(nd.array(arg_params["fc1_weight"].asnumpy()))
    net.fc1.bias.set_data(nd.array(arg_params["fc1_bias"].asnumpy()))
    net.fc2.weight.set_data(nd.array(arg_params["fc2_weight"].asnumpy()))
    net.fc2.bias.set_data(nd.array(arg_params["fc2_bias"].asnumpy()))
    return net


def test_fit_auto_resume_publish_deploy_bitwise(tmp_path):
    ref_args, _ = _run_fit(str(tmp_path / "ref"))

    # the trainer "dies" saving epoch 1 (first file write), restarts, and
    # auto-resumes to completion on the SAME publish prefix
    prefix = str(tmp_path / "pub")
    plan = faults.FaultPlan(3).add("checkpoint.write", kind="crash",
                                   times=1)
    with pytest.raises(faults.SimulatedCrash):
        _run_fit(prefix, crash_plan=plan)
    args, _ = _run_fit(prefix, resume=True)
    for k in ref_args:
        assert np.array_equal(ref_args[k].asnumpy(), args[k].asnumpy()), k

    # the resumed run's final checkpoint is the deployable epoch, and the
    # controller rolls it into a serving fleet whose outputs are bitwise
    # the trained weights'
    epoch = model_mod.latest_complete_checkpoint(prefix)
    assert epoch == 2
    router = FleetRouter(replicas=2, failover_budget=2)
    with router:
        router.load_model("m", _fit_block(ref_args), input_shapes=[(_F,)],
                          replicas=2, max_batch=4, max_queue=16,
                          linger_ms=1.0, warmup=True)
        seen = {}

        def build_model(arg_params, aux_params, generation):
            for k in arg_params:
                seen[k] = arg_params[k].asnumpy()
            return _fit_block(arg_params)

        ctl = deploy.DeploymentController(router, prefix,
                                          models={"m": build_model})
        rep = ctl.poll()
        assert rep["status"] == "deployed" and rep["generation"] == 2
        assert rep["staged_models"], rep
        for k in ref_args:       # the builder was handed the trained
            assert np.array_equal(ref_args[k].asnumpy(), seen[k]), k
        x = np.full((_F,), 0.5, np.float32)
        expected = _fit_block(ref_args)(nd.array(x[None])).asnumpy()[0]
        res = router.predict("m", x, timeout_ms=5000)
        assert res.status == OK
        assert np.array_equal(res.outputs[0], expected), \
            "served output is not bitwise the trained weights'"


# ---------------------------------------------------------------------------
# prune_checkpoints: retention GC that cannot eat the serving generation
# ---------------------------------------------------------------------------

def _save_epoch(prefix, epoch):
    x = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(x, num_hidden=3, name="fc")
    args = {"w": nd.array(np.full((2, 3), float(epoch), np.float32))}
    model_mod.save_checkpoint(prefix, epoch, net, args, {})


def test_prune_keeps_newest_sweeps_superseded_and_debris(tmp_path):
    prefix = str(tmp_path / "ck")
    for epoch in (1, 2, 3, 4):
        _save_epoch(prefix, epoch)
    # write_atomic debris from a "killed" writer
    orphan = "%s-0002.params.tmp-123-456" % prefix
    with open(orphan, "wb") as f:
        f.write(b"dead writer")
    report = model_mod.prune_checkpoints(prefix, keep_last=2)
    assert report["kept"] == [3, 4]
    assert report["pruned"] == [1, 2]
    assert report["removed_tmp"] == [orphan]
    assert not os.path.exists(orphan)
    assert not os.path.exists("%s-0001.params" % prefix)
    assert not os.path.exists("%s-0002.params" % prefix)
    # the shared symbol file every epoch lists survives
    assert model_mod.latest_complete_checkpoint(prefix) == 4
    _, args, _ = model_mod.load_checkpoint(prefix, 4)
    assert float(args["w"].asnumpy()[0, 0]) == 4.0
    _, args, _ = model_mod.load_checkpoint(prefix, 3)
    assert float(args["w"].asnumpy()[0, 0]) == 3.0
    # pruning again is a no-op
    again = model_mod.prune_checkpoints(prefix, keep_last=2)
    assert again["pruned"] == [] and again["removed_files"] == []


def test_prune_never_touches_newest_complete_or_inflight_saves(tmp_path):
    prefix = str(tmp_path / "ck")
    _save_epoch(prefix, 1)
    _save_epoch(prefix, 2)
    # keep_last=0 clamps to 1: the newest complete entry is untouchable
    report = model_mod.prune_checkpoints(prefix, keep_last=0)
    assert report["kept"] == [2]
    assert model_mod.latest_complete_checkpoint(prefix) == 2
    # an entry NEWER than the newest complete epoch that fails the hash
    # check looks exactly like a save in progress: prune must spare it
    _save_epoch(prefix, 3)
    with open("%s-0003.params" % prefix, "r+b") as f:
        f.seek(10)
        f.write(b"\xff\xff\xff\xff")
    report = model_mod.prune_checkpoints(prefix, keep_last=1)
    assert 3 not in report["pruned"]
    assert os.path.exists("%s-0003.params" % prefix)
    assert model_mod.latest_complete_checkpoint(prefix) == 2


# ---------------------------------------------------------------------------
# wait_converged diagnoses a wedged rebalance
# ---------------------------------------------------------------------------

def test_wait_converged_timeout_names_the_deficit():
    built = []
    wedged = threading.Event()      # the replacement copy entered warming
    release = threading.Event()     # ...and stays there until we say so

    def factory(name):
        built.append(name)
        if len(built) > 2:
            wedged.set()
            release.wait(20.0)
        return _baseline_engine(name)

    router = FleetRouter(replicas=2, failover_budget=2)
    with router:
        router.load_decode("lm", factory, replicas=2)
        assert router.wait_converged(timeout_s=10.0) is True
        rid = router.stats()["decode_models"]["lm"]["placement"][0]
        router.kill_replica(rid)
        # add_replica rebalances synchronously, so run it in a thread:
        # the replacement copy wedges inside the factory while the main
        # thread watches the open deficit
        joiner = threading.Thread(target=router.add_replica)
        joiner.start()
        try:
            assert wedged.wait(10.0), "rebalance never reached the factory"
            assert router.wait_converged(timeout_s=0.2) is False
            with pytest.raises(MXNetError,
                               match=r"decode 'lm': 1/2 routable"):
                router.wait_converged(timeout_s=0.2,
                                      reason_on_timeout=True)
        finally:
            release.set()
            joiner.join(20.0)
        # the wedged copy finally warms; convergence closes the deficit
        assert router.wait_converged(timeout_s=10.0) is True


# ---------------------------------------------------------------------------
# observability: profiler counters + stats plumbing
# ---------------------------------------------------------------------------

def test_deploy_counters_in_profiler_dump(tmp_path, refs):
    from mxnet_tpu import profiler
    prefix = str(tmp_path / "ck")
    trace = str(tmp_path / "deploy_profile.json")
    profiler.set_config(filename=trace)
    profiler.set_state("run")
    try:
        router, ctl = _fresh_fleet(prefix)
        with router:
            _save_gen(prefix, 2, _SEED_B)
            assert ctl.poll()["status"] == "deployed"
            _save_gen(prefix, 3, _SEED_A)
            veto = deploy.DeploymentController(
                router, prefix, engines={"lm": _build_engine},
                slo_probe=lambda r: "planted regression")
            assert veto.poll()["status"] == "rolled_back"
    finally:
        profiler.set_state("stop")
        profiler.dump()
    events = json.load(open(trace))["traceEvents"]
    counters = {e["name"] for e in events if e.get("ph") == "C"}
    for name in ("deploy:generation", "deploy:swap_ms",
                 "deploy:rollbacks"):
        assert name in counters, (name, counters)


# ---------------------------------------------------------------------------
# serve_bench deploy profile: registry, scan coverage, smoke, artifact
# ---------------------------------------------------------------------------

def _import_serve_bench():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import serve_bench
    return serve_bench


def test_deploy_profile_registered_and_scan_prefixes_cover_deploy():
    serve_bench = _import_serve_bench()
    assert "deploy" in serve_bench.PROFILES
    assert callable(serve_bench.PROFILES["deploy"]["run"])
    # mxlint --since must trigger both static passes when the deployment
    # controller changes
    from mxnet_tpu.analysis.memory_lint import SCAN_PREFIXES as MEM
    from mxnet_tpu.analysis.sharding_lint import SCAN_PREFIXES as SHARD
    assert "mxnet_tpu/serving/deploy.py" in SHARD
    assert "mxnet_tpu/serving/deploy.py" in MEM


def test_serve_bench_deploy_smoke_artifact(tmp_path):
    serve_bench = _import_serve_bench()
    out = str(tmp_path / "report.json")
    rc = serve_bench.main(["--smoke", "--profile", "deploy",
                           "--out", out])
    assert rc == 0
    report = json.load(open(out))
    assert report["profile"] == "deploy"
    _check_deploy_report(report)


def _check_deploy_report(report):
    wl = report["workload"]
    assert wl["arrivals"] > 0
    assert wl["fired"] == wl["arrivals"]
    # zero dropped streams: every arrival reached OK
    assert report["statuses"] == {"OK": wl["arrivals"]}
    assert report["conserved"] is True
    assert report["pools_whole"] is True
    # single-generation integrity, with the swap really overlapping load
    assert report["torn_streams"] == 0
    assert report["ok_by_generation"]["1"] >= 1
    assert report["ok_by_generation"]["2"] >= 1
    assert report["probes"]["bitwise"] is True
    swap = report["swap"]
    assert swap["status"] == "deployed" and swap["error"] is None
    assert swap["generation"] == 2
    assert swap["streams_during_swap"] >= 1
    # reported for a chip run, never compared with a number here
    assert "ttft_p99_during_swap_ms" in swap and "ttft_p99_steady_ms" in swap
    for rid, snap in report["engines"].items():
        assert snap["generation"] == 2, rid
        assert snap["steady_state_recompiles"] == 0, rid
        assert snap["kv_leaked_blocks"] == 0, rid
    for ename, snap in report["retired_engines"].items():
        assert snap["steady_state_recompiles"] == 0, ename
    assert report["memory"]["balanced"] is True
