"""Fault injection + crash-consistent checkpoint/resume (docs/ROBUSTNESS.md).

Tier-1 gates for the robustness stack:

* ``util.retry`` / ``faults.FaultPlan`` semantics (seeded, bounded,
  transient-only by default);
* ``util.write_atomic`` crash consistency — a save killed at any point
  (including byte-level torn writes) never damages the previous file;
* the checkpoint manifest: ``latest_complete_checkpoint`` skips torn /
  hash-mismatched / uncommitted checkpoints, with a parse-validating
  fallback when the manifest itself is gone;
* **the acceptance sweep**: a Module fit killed at EVERY checkpoint fault
  point resumes via ``fit(auto_resume=True)`` to params bitwise-identical
  to the uninterrupted run (optimizer state included), touching no batch
  twice within an epoch;
* recoverable-site retries: DeviceFeed staging, DataLoader workers,
  kvstore push/pull absorb transient faults and surface persistent ones;
* the serving circuit breaker: opens after K consecutive failures (fast
  retryable UNAVAILABLE), half-open probes, re-closes on recovery;
* the mxstress ``faults`` + ``crash`` scenarios under chaos locks, inside
  a ~5 s smoke budget (the fault-injection twin of the 25-seed
  concurrency smoke).
"""
import os
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faults, io, nd, util
from mxnet_tpu import model as model_mod


# ---------------------------------------------------------------------------
# retry + FaultPlan semantics
# ---------------------------------------------------------------------------

def test_retry_absorbs_transients_and_reraises_at_budget():
    calls = []

    @util.retry(attempts=3, backoff=0.0)
    def flaky(fail_times):
        calls.append(1)
        if len(calls) <= fail_times:
            raise faults.TransientFault("blip")
        return "ok"

    assert flaky(2) == "ok"
    assert len(calls) == 3
    calls.clear()
    with pytest.raises(faults.TransientFault):
        flaky(99)
    assert len(calls) == 3   # attempts exhausted, last failure re-raised


def test_retry_does_not_catch_fatal_or_foreign_errors():
    calls = []

    @util.retry(attempts=3, backoff=0.0)
    def fatal():
        calls.append(1)
        raise faults.FatalFault("dead backend")

    with pytest.raises(faults.FatalFault):
        fatal()
    assert len(calls) == 1   # no retry on non-retryable

    @util.retry(attempts=3, backoff=0.0, retryable=(ValueError,))
    def custom():
        calls.append(1)
        raise ValueError("opted in")

    calls.clear()
    with pytest.raises(ValueError):
        custom()
    assert len(calls) == 3   # explicit opt-in retries real exceptions


def test_fault_plan_is_seeded_and_site_checked():
    def fire_pattern(seed):
        plan = faults.FaultPlan(seed)
        plan.add("kvstore.push", kind="transient", p=0.5)
        fired = []
        with faults.plan(plan):
            for _ in range(32):
                try:
                    faults.fault_point("kvstore.push")
                    fired.append(0)
                except faults.TransientFault:
                    fired.append(1)
        return fired

    assert fire_pattern(7) == fire_pattern(7)       # reproducible
    assert fire_pattern(7) != fire_pattern(8)       # seed-sensitive
    with pytest.raises(ValueError):
        faults.FaultPlan(0).add("no.such.site")
    # a typo'd fault_point fails loudly under an active plan
    with faults.plan(faults.FaultPlan(0)):
        with pytest.raises(ValueError):
            faults.fault_point("checkpoint.wriet")
    # without a plan, fault_point is a no-op regardless of the name
    faults.fault_point("serving.predict")


def test_fault_plan_window_and_times():
    plan = faults.FaultPlan(0)
    plan.add("serving.predict", kind="transient", after=2, times=1)
    outcomes = []
    with faults.plan(plan):
        for _ in range(5):
            try:
                faults.fault_point("serving.predict")
                outcomes.append("ok")
            except faults.TransientFault:
                outcomes.append("fault")
    assert outcomes == ["ok", "ok", "fault", "ok", "ok"]
    assert plan.hit_count("serving.") == 5
    assert plan.fired_count() == 1


# ---------------------------------------------------------------------------
# atomic writes: crash anywhere, old file survives
# ---------------------------------------------------------------------------

def test_write_atomic_crash_never_tears_the_target(tmp_path):
    path = str(tmp_path / "file.bin")
    util.write_atomic(path, b"OLD-CONTENT")
    for site, kind in (("checkpoint.write", "crash"),
                       ("checkpoint.write", "truncate"),
                       ("checkpoint.replace", "crash")):
        plan = faults.FaultPlan(1).add(site, kind=kind, times=1)
        with faults.plan(plan):
            with pytest.raises(faults.SimulatedCrash):
                util.write_atomic(path, b"NEW-CONTENT-MUCH-LONGER")
        with open(path, "rb") as f:
            assert f.read() == b"OLD-CONTENT", (site, kind)
    # crash AFTER the replace: new content is committed
    plan = faults.FaultPlan(1).add("checkpoint.replaced", kind="crash")
    with faults.plan(plan):
        with pytest.raises(faults.SimulatedCrash):
            util.write_atomic(path, b"NEW")
    with open(path, "rb") as f:
        assert f.read() == b"NEW"
    # a clean write succeeds with no tmp leftovers
    util.write_atomic(path, b"FINAL")
    crashed_tmp = [f for f in os.listdir(str(tmp_path)) if ".tmp-" in f]
    util.write_atomic(str(tmp_path / "other.bin"), b"x")
    after = [f for f in os.listdir(str(tmp_path)) if ".tmp-" in f]
    assert after == crashed_tmp   # clean writes leave no new strays


# ---------------------------------------------------------------------------
# manifest + latest-complete-wins
# ---------------------------------------------------------------------------

def _save_epoch(prefix, epoch):
    x = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(x, num_hidden=3, name="fc")
    args = {"w": nd.array(np.full((2, 3), float(epoch), np.float32))}
    model_mod.save_checkpoint(prefix, epoch, net, args, {})


def test_latest_complete_skips_corrupt_checkpoints(tmp_path):
    prefix = str(tmp_path / "ck")
    _save_epoch(prefix, 1)
    _save_epoch(prefix, 2)
    assert model_mod.latest_complete_checkpoint(prefix) == 2
    # corrupt epoch 2's params ON DISK: the hash check must reject it
    with open("%s-0002.params" % prefix, "r+b") as f:
        f.seek(10)
        f.write(b"\xff\xff\xff\xff")
    assert model_mod.latest_complete_checkpoint(prefix) == 1
    _, args, _ = model_mod.load_checkpoint(prefix, 1)
    assert float(args["w"].asnumpy()[0, 0]) == 1.0
    # uncommitted save (params written, manifest crash): still epoch 1
    plan = faults.FaultPlan(0).add("checkpoint.write", kind="crash",
                                   after=2)   # third file = the manifest
    with faults.plan(plan):
        with pytest.raises(faults.SimulatedCrash):
            _save_epoch(prefix, 3)
    assert model_mod.latest_complete_checkpoint(prefix) == 1


def test_latest_complete_fallback_without_manifest(tmp_path):
    prefix = str(tmp_path / "ck")
    _save_epoch(prefix, 1)
    _save_epoch(prefix, 2)
    os.remove("%s-manifest.json" % prefix)
    # no manifest: strictly, nothing is provably complete...
    assert model_mod.latest_complete_checkpoint(prefix) is None
    # ...but the legacy opt-in falls back to parse-validation, newest first
    assert model_mod.latest_complete_checkpoint(
        prefix, allow_unverified=True) == 2
    with open("%s-0002.params" % prefix, "wb") as f:
        f.write(b"torn")   # unparseable: skip to epoch 1
    assert model_mod.latest_complete_checkpoint(
        prefix, allow_unverified=True) == 1
    assert model_mod.latest_complete_checkpoint(
        str(tmp_path / "no"), allow_unverified=True) is None


# ---------------------------------------------------------------------------
# the acceptance sweep: fit killed at every checkpoint fault point,
# auto_resume reaches the uninterrupted run's params BITWISE
# ---------------------------------------------------------------------------

_N, _F = 16, 5


def _fit_data():
    rng = np.random.RandomState(11)
    X = rng.randn(_N, _F).astype(np.float32)
    Y = (rng.rand(_N) > 0.5).astype(np.float32)
    return io.NDArrayIter(X, Y, batch_size=8)


def _make_mod():
    x = mx.sym.Variable("data")
    y = mx.sym.FullyConnected(x, num_hidden=4, name="fc1")
    y = mx.sym.Activation(y, act_type="relu")
    y = mx.sym.FullyConnected(y, num_hidden=2, name="fc2")
    return mx.mod.Module(mx.sym.SoftmaxOutput(y, name="softmax"),
                         context=mx.cpu())


def _run_fit(prefix, resume=False, crash_plan=None, batch_log=None):
    """One deterministic 2-epoch fit with per-epoch checkpoints (params +
    optimizer momentum); returns final (arg_params, aux_params)."""
    mod = _make_mod()
    cbs = [mx.callback.module_checkpoint(mod, prefix,
                                         save_optimizer_states=True)]
    batch_cb = None
    if batch_log is not None:
        batch_cb = lambda p: batch_log.append((p.epoch, p.nbatch))
    mx.random.seed(1234)
    kw = dict(num_epoch=2, optimizer="sgd",
              optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
              initializer=mx.init.Xavier(),
              epoch_end_callback=cbs, batch_end_callback=batch_cb)
    if crash_plan is not None:
        with faults.plan(crash_plan):
            mod.fit(_fit_data(), **kw)
    else:
        mod.fit(_fit_data(), auto_resume=resume, **kw)
    return mod.get_params()


def test_fit_crash_resume_sweep_bitwise(tmp_path):
    ref_args, _ = _run_fit(str(tmp_path / "ref"))

    # enumerate every checkpoint fault point one full fit passes (both
    # epoch-end saves: symbol + params + states + manifest, three sites
    # each) with a rule-less recording plan
    probe = faults.FaultPlan(0)
    _run_fit(str(tmp_path / "probe"), crash_plan=probe)
    points = [(site, i)
              for site in sorted(probe.hits)
              if site.startswith("checkpoint.")
              for i in range(probe.hits[site])]
    assert len(points) >= 12, points   # 2 saves x 4 files x >=1.5 sites

    rng = np.random.RandomState(99)
    for n, (site, i) in enumerate(points):
        prefix = str(tmp_path / ("kill%d" % n))
        kind = "truncate" if rng.rand() < 0.4 else "crash"
        plan = faults.FaultPlan(n).add(site, kind=kind, after=i, times=1)
        with pytest.raises(faults.SimulatedCrash):
            _run_fit(prefix, crash_plan=plan)
        # the process "died"; a fresh run auto-resumes from whatever the
        # newest COMPLETE checkpoint is (possibly none at all) and must
        # land on the uninterrupted run's params exactly
        batch_log = []
        args, _ = _run_fit(prefix, resume=True, batch_log=batch_log)
        for k in ref_args:
            assert np.array_equal(ref_args[k].asnumpy(), args[k].asnumpy()), \
                "param %r diverged after kill@%s#%d(%s)" % (k, site, i, kind)
        # resumed fit touches no batch twice within an epoch
        assert len(batch_log) == len(set(batch_log)), batch_log


def test_fit_resume_from_missing_checkpoint_raises(tmp_path):
    mod = _make_mod()
    with pytest.raises(FileNotFoundError):
        mod.fit(_fit_data(), num_epoch=1,
                resume_from=str(tmp_path / "nothing"))


def test_fit_resume_restores_epoch_and_optimizer_state(tmp_path):
    prefix = str(tmp_path / "ck")
    _run_fit(prefix)   # leaves checkpoints for epochs 1 and 2
    mod = _make_mod()
    epochs_run = []
    mod.fit(_fit_data(), num_epoch=4, resume_from=prefix, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            batch_end_callback=lambda p: epochs_run.append(p.epoch))
    # resumed at epoch 2 (the saved number): epochs 0 and 1 were skipped
    assert min(epochs_run) == 2 and max(epochs_run) == 3


# ---------------------------------------------------------------------------
# recoverable sites: DeviceFeed, DataLoader workers, kvstore
# ---------------------------------------------------------------------------

def test_device_feed_retries_transient_staging_faults():
    from mxnet_tpu.io.device_feed import DeviceFeed

    def source():
        for i in range(6):
            yield np.full((3,), i, np.float32)

    plan = faults.FaultPlan(0).add("device_feed.put", kind="transient",
                                   times=2)
    with faults.plan(plan):
        feed = DeviceFeed(source(), ctx=mx.cpu(0), depth=2)
        got = [np.asarray(b) for b in feed]
    assert [int(b[0]) for b in got] == list(range(6))
    assert plan.fired_count("device_feed.") == 2   # absorbed, not surfaced


def test_device_feed_surfaces_persistent_staging_failure():
    from mxnet_tpu.io.device_feed import DeviceFeed

    def source():
        for i in range(6):
            yield np.full((3,), i, np.float32)

    plan = faults.FaultPlan(0).add("device_feed.put", kind="fatal", after=2)
    with faults.plan(plan):
        feed = DeviceFeed(source(), ctx=mx.cpu(0), depth=1)
        seen = []
        with pytest.raises(faults.FatalFault):
            for b in feed:
                seen.append(int(np.asarray(b)[0]))
    assert seen == [0, 1]   # the good prefix arrived first


class _TinyDataset:
    def __len__(self):
        return 16

    def __getitem__(self, i):
        return np.full((4,), i, np.float32), np.float32(i % 2)


def test_dataloader_resubmits_batch_after_worker_death():
    from mxnet_tpu.gluon.data.dataloader import DataLoader
    plan = faults.FaultPlan(0).add("dataloader.worker", kind="transient",
                                   times=2)
    with faults.plan(plan):
        with DataLoader(_TinyDataset(), batch_size=4, num_workers=2,
                        thread_pool=True) as loader:
            batches = [b for b in loader]
    assert len(batches) == 4
    data = np.concatenate([b[0].asnumpy() for b in batches])
    np.testing.assert_array_equal(np.sort(data[:, 0]), np.arange(16))
    assert plan.fired_count("dataloader.") == 2


def test_dataloader_persistent_worker_failure_surfaces():
    from mxnet_tpu.gluon.data.dataloader import DataLoader
    plan = faults.FaultPlan(0).add("dataloader.worker", kind="fatal")
    with faults.plan(plan):
        with DataLoader(_TinyDataset(), batch_size=4, num_workers=1,
                        thread_pool=True) as loader:
            with pytest.raises(faults.FatalFault):
                list(loader)


def test_kvstore_push_pull_retry_transient_faults():
    kv = mx.kv.create("local")
    kv.init("w", nd.array(np.zeros(4, np.float32)))
    plan = faults.FaultPlan(0)
    plan.add("kvstore.push", kind="transient", times=2)
    plan.add("kvstore.pull", kind="transient", times=2)
    out = nd.array(np.zeros(4, np.float32))
    with faults.plan(plan):
        kv.push("w", nd.array(np.ones(4, np.float32)))
        kv.pull("w", out=out)
    np.testing.assert_array_equal(out.asnumpy(), np.ones(4, np.float32))
    assert plan.fired_count("kvstore.") == 4

    persistent = faults.FaultPlan(0).add("kvstore.push", kind="fatal")
    with faults.plan(persistent):
        with pytest.raises(faults.FatalFault):
            kv.push("w", nd.array(np.ones(4, np.float32)))


# ---------------------------------------------------------------------------
# serving: breaker opens, probes, recovers; closed server is UNAVAILABLE
# ---------------------------------------------------------------------------

def _serving_fixture():
    from mxnet_tpu.gluon import nn
    from mxnet_tpu import serving

    class Net(mx.gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.out = nn.Dense(2, in_units=4)

        def hybrid_forward(self, F, x):
            return self.out(x)

    net = Net()
    net.initialize(mx.init.Xavier())
    server = serving.ModelServer()
    server.load_model("m", net, input_shapes=[(4,)], max_batch=4,
                      warmup=True, breaker_threshold=3,
                      breaker_backoff_ms=30.0)
    return server


def test_breaker_opens_fast_fails_and_recovers():
    from mxnet_tpu import serving
    server = _serving_fixture()
    x = np.ones((4,), np.float32)
    try:
        assert server.predict("m", x, timeout_ms=2000).status == serving.OK
        assert server.health("m") == serving.HEALTHY

        plan = faults.FaultPlan(0).add("serving.predict", kind="fatal")
        with faults.plan(plan):
            statuses = [server.predict("m", x, timeout_ms=2000).status
                        for _ in range(5)]
            fast = server.predict("m", x, timeout_ms=2000)
        # exactly threshold ERRORs, then fast retryable UNAVAILABLE
        assert statuses[:3] == [serving.ERROR] * 3
        assert statuses[3:] == [serving.UNAVAILABLE] * 2
        assert fast.status == serving.UNAVAILABLE
        snap = server.stats()["models"]["m"]
        assert snap["health"] == "UNAVAILABLE"
        assert snap["breaker"]["state"] == "open"
        assert snap["breaker_opens"] == 1
        # breaker rejections never entered the queue: they count in the
        # rejected bucket (like shed), keeping requests == ok+t+e+unavailable
        assert snap["unavailable_rejected"] >= 3
        assert snap["requests"] == (snap["ok"] + snap["timeouts"]
                                    + snap["errors"] + snap["unavailable"])

        # faults cleared: half-open probe re-closes within the backoff
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if server.predict("m", x, timeout_ms=2000).status == serving.OK:
                break
            time.sleep(0.005)
        else:
            pytest.fail("breaker never recovered")
        assert server.health("m") == serving.HEALTHY
        assert server.stats()["models"]["m"]["breaker"]["state"] == "closed"
    finally:
        server.stop()


def test_transient_predict_faults_absorbed_by_retry():
    from mxnet_tpu import serving
    server = _serving_fixture()
    x = np.ones((4,), np.float32)
    try:
        plan = faults.FaultPlan(0).add("serving.predict", kind="transient",
                                       times=2)
        with faults.plan(plan):
            res = server.predict("m", x, timeout_ms=5000)
        assert res.status == serving.OK
        snap = server.stats()["models"]["m"]
        assert snap["retries"] == 2
        assert snap["errors"] == 0
        assert snap["health"] == "HEALTHY"
    finally:
        server.stop()


def test_closed_server_returns_clean_unavailable():
    from mxnet_tpu import serving
    server = _serving_fixture()
    server.stop()
    res = server.predict("m", np.ones((4,), np.float32), timeout_ms=100)
    assert res.status == serving.UNAVAILABLE
    res = server.predict_async("m", np.ones((4,), np.float32))
    assert res.status == serving.UNAVAILABLE


# ---------------------------------------------------------------------------
# sharded checkpoints: async save + latest-complete-wins restore
# ---------------------------------------------------------------------------

def test_sliced_manager_async_save_and_torn_step_fallback(tmp_path):
    import jax.numpy as jnp
    from mxnet_tpu.parallel import SlicedCheckpointManager

    mgr = SlicedCheckpointManager(str(tmp_path / "run"), max_to_keep=4,
                                  async_save=True)
    params = lambda s: {"w": jnp.full((8,), float(s), jnp.float32)}
    mgr.save(1, params(1))
    mgr.save(2, params(2))   # waits step 1 out, overlaps step 2
    mgr.wait_until_finished()
    assert mgr.latest_step() == 2

    # tear the newest step on disk: latest-complete-wins must fall back
    import shutil
    step_dir = tmp_path / "run" / "2"
    assert step_dir.exists()
    shutil.rmtree(str(step_dir / "params"))
    out = mgr.restore(params_template=params(0))
    np.testing.assert_array_equal(np.asarray(out["params"]["w"]),
                                  np.full((8,), 1.0, np.float32))
    mgr.close()


# ---------------------------------------------------------------------------
# the chaos gate: mxstress faults + crash scenarios, ~5 s budget
# ---------------------------------------------------------------------------

def test_mxstress_fault_scenarios_zero_violations():
    from mxnet_tpu.analysis import schedule
    t0 = time.monotonic()
    report = schedule.stress(seeds=schedule.FAULT_SMOKE_SEEDS,
                             scenarios=("faults", "crash"))
    elapsed = time.monotonic() - t0
    flat = ["seed %s [%s] %s" % (seed, scen, v)
            for seed, per_seed in report["seeds"].items()
            for scen, violations in per_seed.items()
            for v in violations]
    assert report["violations"] == 0, "\n".join(flat)
    assert len(report["seeds"]) == len(schedule.FAULT_SMOKE_SEEDS)
    # smoke budget: this is a tier-1 gate, it must stay cheap
    assert elapsed < 15.0, "fault smoke blew its budget: %.1fs" % elapsed
