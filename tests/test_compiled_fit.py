"""Compiled training step: fit(compiled=True) as one CachedOp (ISSUE 6).

Acceptance gates asserted here:
* compiled fit() matches eager fit() params BITWISE on a small convnet
  (same seed, same data, SGD+momentum);
* exactly one compile per signature and ZERO steady-state recompiles
  across >= 2 epochs (cache_stats());
* no host fetch inside the step loop — the only asnumpy() calls the
  compiled path makes are the metric-accumulator syncs at metric_interval
  boundaries / epoch end;
* steps_per_call > 1 (lax.scan window) reaches the same params and the
  same accumulated train metric;
* a compiled fit killed mid-checkpoint resumes via auto_resume to the
  uninterrupted run's params bitwise (the tests/test_faults.py harness,
  compiled flavor);
* unsupported configurations fall back to the eager loop with a warning.
"""
import logging

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import io, sym
from mxnet_tpu import faults
from mxnet_tpu.ndarray import NDArray


def _convnet():
    data = sym.Variable("data")
    net = sym.Convolution(data, kernel=(3, 3), pad=(1, 1), num_filter=4,
                          name="conv1")
    net = sym.Activation(net, act_type="relu")
    net = sym.Pooling(net, global_pool=True, pool_type="avg", kernel=(1, 1))
    net = sym.Flatten(net)
    net = sym.FullyConnected(net, num_hidden=10, name="fc")
    return sym.SoftmaxOutput(net, name="softmax")


_B, _N = 8, 6   # batch size, batches per epoch
_RNG = np.random.RandomState(0)
_DATA = _RNG.uniform(-1, 1, (_B * _N, 3, 8, 8)).astype(np.float32)
_LABELS = _RNG.randint(0, 10, _B * _N).astype(np.float32)


def _fit(compiled, num_epoch=2, eval_metric="acc", opt="sgd",
         opt_params=None, **kw):
    mx.random.seed(77)
    it = io.NDArrayIter(_DATA, _LABELS, batch_size=_B)
    mod = mx.mod.Module(_convnet(), context=mx.cpu())
    mod.fit(it, num_epoch=num_epoch, optimizer=opt,
            optimizer_params=dict(
                opt_params or {"learning_rate": 0.1, "momentum": 0.9}),
            eval_metric=eval_metric, initializer=mx.init.Xavier(),
            compiled=compiled, **kw)
    args, auxs = mod.get_params()
    return mod, {k: v.asnumpy() for k, v in args.items()}


def test_compiled_fit_bitwise_parity_with_eager():
    mod_c, params_c = _fit(True)
    assert mod_c._compiled_step is not None, "compiled path did not engage"
    mod_e, params_e = _fit(False)
    assert mod_e._compiled_step is None
    for name in params_e:
        assert np.array_equal(params_c[name], params_e[name]), \
            "param %r diverged between compiled and eager fit" % name


def test_compiled_fit_zero_steady_state_recompiles():
    recompiles = []
    mx.random.seed(77)
    it = io.NDArrayIter(_DATA, _LABELS, batch_size=_B)
    mod = mx.mod.Module(_convnet(), context=mx.cpu())
    mod.fit(it, num_epoch=3, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            eval_metric="acc", initializer=mx.init.Xavier(),
            epoch_end_callback=lambda *a: recompiles.append(
                mod._compiled_step.cache_stats()["recompiles"]))
    # exactly ONE compile (one signature: steps_per_call=1, fixed shapes)
    stats = mod._compiled_step.cache_stats()
    assert len(stats["signatures"]) == 1, stats
    assert stats["recompiles"] == 1, stats
    # zero steady-state recompiles across epochs 2..3
    assert recompiles[1] == recompiles[0] == recompiles[-1] == 1, recompiles
    # every dispatch after the first was an executable-cache hit
    assert stats["hits"] == 3 * _N - 1, stats


def _counted_fit(counts, compiled, num_epoch, **kw):
    """Run fit() alone (no param fetch) with asnumpy instrumented."""
    orig = NDArray.asnumpy

    def counted(self):
        counts["n"] += 1
        return orig(self)

    mx.random.seed(77)
    it = io.NDArrayIter(_DATA, _LABELS, batch_size=_B)
    mod = mx.mod.Module(_convnet(), context=mx.cpu())
    NDArray.asnumpy = counted
    try:
        mod.fit(it, num_epoch=num_epoch, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                eval_metric="acc", initializer=mx.init.Xavier(),
                compiled=compiled, **kw)
    finally:
        NDArray.asnumpy = orig
    return mod


def test_compiled_fit_no_host_fetch_inside_step_loop():
    counts = {"n": 0}
    mod = _counted_fit(counts, True, 2)
    assert mod._compiled_step is not None
    compiled_fetches = counts["n"]
    counts["n"] = 0
    _counted_fit(counts, False, 2)
    eager_fetches = counts["n"]
    # compiled: ONLY the metric sync at each epoch end (2 scalars/metric)
    assert compiled_fetches == 2 * 2, compiled_fetches
    # eager pays >= one (label, pred) fetch pair per batch
    assert eager_fetches >= 2 * 2 * _N, eager_fetches


def test_compiled_fit_metric_interval_bounds_fetch_cadence():
    counts = {"n": 0}
    mod = _counted_fit(counts, True, 1, metric_interval=2)
    assert mod._compiled_step is not None
    # 6 batches, interval 2 -> syncs after batches 2, 4, 6 (6 == epoch end)
    assert counts["n"] == 3 * 2, counts["n"]


def test_compiled_fit_steps_per_call_window_equivalence():
    mod_1, params_1 = _fit(True, steps_per_call=1)
    mod_4, params_4 = _fit(True, steps_per_call=4)
    # 6 batches -> windows of 4 + 2: exactly two compiled signatures,
    # both stable across epochs
    stats = mod_4._compiled_step.cache_stats()
    assert len(stats["signatures"]) == 2, stats
    assert stats["recompiles"] == 2, stats
    for name in params_1:
        # the scan body is a separate XLA compilation unit from the
        # unrolled single-step program: fusion choices differ at the ULP
        # level (measured max 3e-8 here), so equivalence is tight-allclose,
        # not bitwise — bitwise is the compiled-vs-eager gate at W=1
        np.testing.assert_allclose(
            params_1[name], params_4[name], rtol=1e-5, atol=1e-7,
            err_msg="param %r diverged between steps_per_call=1 and 4"
                    % name)


def test_compiled_fit_train_metric_matches_eager():
    got = {}
    for compiled in (True, False):
        mx.random.seed(77)
        it = io.NDArrayIter(_DATA, _LABELS, batch_size=_B)
        mod = mx.mod.Module(_convnet(), context=mx.cpu())
        seen = []
        mod.fit(it, num_epoch=2, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                eval_metric="acc", initializer=mx.init.Xavier(),
                compiled=compiled,
                batch_end_callback=lambda p: seen.append(
                    (p.epoch, p.nbatch, p.eval_metric.get()[1],
                     p.eval_metric.num_inst)))
        got[compiled] = seen
    # same number of batch callbacks, and the epoch-end metric (the last
    # callback of each epoch, after the device sync) agrees exactly —
    # accuracy is an integer count, so equality is exact
    assert len(got[True]) == len(got[False])
    for epoch in (0, 1):
        last_c = [s for s in got[True] if s[0] == epoch][-1]
        last_e = [s for s in got[False] if s[0] == epoch][-1]
        assert last_c[3] == last_e[3] == _B * _N
        assert last_c[2] == pytest.approx(last_e[2], abs=0)


def test_compiled_fit_adam_and_scheduler_match_eager_closely():
    sched = mx.lr_scheduler.FactorScheduler(step=4, factor=0.5)
    _, params_c = _fit(True, opt="adam",
                       opt_params={"learning_rate": 0.01,
                                   "lr_scheduler": sched})
    sched2 = mx.lr_scheduler.FactorScheduler(step=4, factor=0.5)
    _, params_e = _fit(False, opt="adam",
                       opt_params={"learning_rate": 0.01,
                                   "lr_scheduler": sched2})
    for name in params_e:
        # Adam's bias correction runs in f64 on the eager host path and in
        # traced f32 under capture: allclose, not bitwise (docs/PERF.md)
        np.testing.assert_allclose(params_c[name], params_e[name],
                                   rtol=3e-5, atol=3e-6)


def test_compiled_fit_falls_back_with_warning_for_unsupported(caplog):
    with caplog.at_level(logging.WARNING):
        mod, _ = _fit(True, opt="nadam", opt_params={"learning_rate": 0.01})
    assert mod._compiled_step is None
    assert any("falling back to the eager loop" in r.getMessage()
               for r in caplog.records)


def test_compiled_fit_falls_back_for_undeviceable_metric(caplog):
    # F1 has no traced_update twin -> eager loop, one-line warning
    labels2 = (_LABELS % 2).astype(np.float32)
    mx.random.seed(77)
    it = io.NDArrayIter(_DATA, labels2, batch_size=_B)
    mod = mx.mod.Module(_convnet(), context=mx.cpu())
    with caplog.at_level(logging.WARNING):
        mod.fit(it, num_epoch=1, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1},
                eval_metric="f1", initializer=mx.init.Xavier())
    assert mod._compiled_step is None


def test_compiled_fit_composite_metric_accumulates_on_device():
    metric = mx.metric.CompositeEvalMetric(metrics=["acc", "ce"])
    mod, _ = _fit(True, eval_metric=metric)
    assert mod._compiled_step is not None
    values = dict(zip(*metric.get()))
    assert 0.0 <= values["accuracy"] <= 1.0
    assert values["cross-entropy"] > 0.0


def test_compiled_step_donate_flag_roundtrip():
    # donate='auto' resolves False on CPU; forcing True must still train
    # correctly (CPU XLA ignores unusable donations with a warning)
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        _, params_d = _fit(True, donate=True)
    _, params_ref = _fit(True, donate=False)
    for name in params_ref:
        assert np.array_equal(params_d[name], params_ref[name])


def test_compiled_fit_binds_inputs_by_provide_order():
    """Two same-shaped data inputs whose iterator provide_data order differs
    from the module's data_names order: the compiled step must bind each
    array to its NAME (the eager scatter matches against the bound
    data_shapes, i.e. provide order) — positional binding by data_names
    would silently train on swapped inputs."""
    a = sym.Variable("a")
    b = sym.Variable("b")
    # net consumes ONLY input 'a'; 'b' is pure decoy of the same shape
    net = sym.FullyConnected(a + 0 * b, num_hidden=10, name="fc")
    net = sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(4)
    xa = rng.randn(32, 6).astype(np.float32)
    xb = np.zeros((32, 6), np.float32) + 99.0   # poison if bound as 'a'
    y = rng.randint(0, 10, 32).astype(np.float32)

    def run(compiled):
        mx.random.seed(9)
        # NDArrayIter sorts dict keys -> provide order ('a','b'); flip the
        # module's declared order so name-vs-position disagree
        it = io.NDArrayIter({"a": xa, "b": xb}, y, batch_size=16)
        mod = mx.mod.Module(net, data_names=("b", "a"), context=mx.cpu())
        mod.fit(it, num_epoch=1, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1},
                eval_metric="acc", initializer=mx.init.Xavier(),
                compiled=compiled)
        args, _ = mod.get_params()
        return mod, {k: v.asnumpy() for k, v in args.items()}

    mod_c, params_c = run(True)
    assert mod_c._compiled_step is not None
    _, params_e = run(False)
    for name in params_e:
        assert np.array_equal(params_c[name], params_e[name]), name


# ---------------------------------------------------------------------------
# the benchmark's two networks through the path its cells run
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("network", ["resnet50_v1", "mobilenetv2_1.0"])
def test_zoo_network_trains_through_compiled_fit_with_the_feed(network):
    """The zoo network at its published depth and width, traced on a
    symbol as the benchmark's ``module_fit`` entry traces it, through
    ``Module.fit(prefetch_to_device=...)``: what tells a PR here, on the
    CPU, that it broke a cell's path before the chip is asked."""
    from mxnet_tpu import profiler
    from mxnet_tpu.gluon.model_zoo import vision
    # 64x64, not 32x32: the last stage is then 2x2, so a batch of 2 still
    # gives BatchNorm eight values a channel (with two the loss is NaN by
    # the third step)
    batch, steps = 2, 3
    rng = np.random.RandomState(0)
    it = io.NDArrayIter(
        rng.uniform(-1, 1, (batch * steps, 3, 64, 64)).astype(np.float32),
        rng.randint(0, 10, batch * steps).astype(np.float32),
        batch_size=batch)
    mx.random.seed(11)
    net = vision.get_model(network, classes=10)
    symbol = mx.sym.SoftmaxOutput(net(mx.sym.var("data")), name="softmax")
    mod = mx.mod.Module(symbol, context=mx.cpu())
    recompiles, after_first_epoch = [], {}

    def epoch_end(epoch, *_):
        recompiles.append(mod._compiled_step.cache_stats()["recompiles"])
        if epoch == 0:
            after_first_epoch.update(
                (k, v.asnumpy().copy())
                for k, v in mod.get_params()[0].items())

    profiler.reset_spans()
    metric = mx.metric.create("ce")
    mod.fit(it, num_epoch=2, optimizer="sgd",
            optimizer_params={"learning_rate": 0.01, "momentum": 0.9},
            eval_metric=metric, initializer=mx.init.Xavier(),
            epoch_end_callback=epoch_end, prefetch_to_device=mx.cpu())
    totals = profiler.totals()
    assert "fit.eager_fallback" not in totals
    assert totals["feed.batches"]["count"] == 2 * steps
    stats = mod._compiled_step.cache_stats()
    assert len(stats["signatures"]) == 1 and stats["recompiles"] == 1, stats
    assert recompiles == [1, 1]            # none in the second epoch
    assert np.isfinite(metric.get()[1])
    after = mod.get_params()[0]
    assert len(after) > 100                # published depth, not a stub
    for name, before in after_first_epoch.items():
        assert not np.array_equal(after[name].asnumpy(), before), name


# ---------------------------------------------------------------------------
# crash/resume under the compiled path (tests/test_faults.py harness)
# ---------------------------------------------------------------------------

def _fit_ckpt(prefix, resume=False, crash_plan=None):
    mx.random.seed(1234)
    it = io.NDArrayIter(_DATA, _LABELS, batch_size=_B)
    mod = mx.mod.Module(_convnet(), context=mx.cpu())
    cbs = [mx.callback.module_checkpoint(mod, prefix,
                                         save_optimizer_states=True)]
    kw = dict(num_epoch=2, optimizer="sgd",
              optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
              initializer=mx.init.Xavier(), epoch_end_callback=cbs)
    if crash_plan is not None:
        with faults.plan(crash_plan):
            mod.fit(it, **kw)
    else:
        mod.fit(it, auto_resume=resume, **kw)
    assert mod._compiled_step is not None
    args, _ = mod.get_params()
    return {k: v.asnumpy() for k, v in args.items()}


def test_compiled_fit_killed_mid_epoch_resumes_bitwise(tmp_path):
    ref = _fit_ckpt(str(tmp_path / "ref"))
    # kill the epoch-0 checkpoint mid-write (params file replace), then
    # again mid-manifest-commit of epoch 1 — one pre-commit, one post-params
    for n, (site, after) in enumerate([("checkpoint.replace", 1),
                                       ("checkpoint.write", 3)]):
        prefix = str(tmp_path / ("kill%d" % n))
        plan = faults.FaultPlan(n).add(site, kind="crash", after=after,
                                       times=1)
        with pytest.raises(faults.SimulatedCrash):
            _fit_ckpt(prefix, crash_plan=plan)
        resumed = _fit_ckpt(prefix, resume=True)
        for k in ref:
            assert np.array_equal(ref[k], resumed[k]), \
                "param %r diverged after kill@%s#%d" % (k, site, after)
