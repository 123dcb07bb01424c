"""ZeRO sharded weight update in the compiled fit path (ISSUE 10).

Acceptance gates asserted here:
* fit(shard_update=True) engages the sharded compiled step (no fallback)
  and matches the replicated compiled fit tightly for SGD/momentum and
  Adam.  The fit-level comparison is tight-allclose, not bitwise: the
  sharded program is a different XLA module and LLVM's FMA contraction
  picks different mul/add pairs per module (docs/PERF.md "Why the fit
  gate is allclose"); the step-level bitwise gate lives in
  tests/test_multichip_topologies.py where both modules share one mesh.
* per-replica optimizer-state bytes are ~1/N of the replicated footprint
  (measured via addressable_shards);
* zero steady-state recompiles across epochs (cache_stats), including
  steps_per_call > 1 scan windows;
* the 2-bit wire format trains, and its error-feedback residual lives in
  the module-owned ResidualStore shared with the kvstore path, carrying
  across steps;
* fit(shard_update=True) + auto_resume resumes bitwise from a kill
  mid-checkpoint with sharded optimizer state;
* unsupported configurations fail loudly (eager + shard_update,
  wire without shard) or fall back with a warning (non-elementwise
  optimizer).
"""
import logging
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import io, sym
from mxnet_tpu import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _convnet():
    data = sym.Variable("data")
    net = sym.Convolution(data, kernel=(3, 3), pad=(1, 1), num_filter=4,
                          name="conv1")
    net = sym.Activation(net, act_type="relu")
    net = sym.Pooling(net, global_pool=True, pool_type="avg", kernel=(1, 1))
    net = sym.Flatten(net)
    net = sym.FullyConnected(net, num_hidden=10, name="fc")
    return sym.SoftmaxOutput(net, name="softmax")


_B, _N = 8, 6
_RNG = np.random.RandomState(0)
_DATA = _RNG.uniform(-1, 1, (_B * _N, 3, 8, 8)).astype(np.float32)
_LABELS = _RNG.randint(0, 10, _B * _N).astype(np.float32)


def _fit(num_epoch=2, opt="sgd", opt_params=None, **kw):
    mx.random.seed(77)
    it = io.NDArrayIter(_DATA, _LABELS, batch_size=_B)
    mod = mx.mod.Module(_convnet(), context=mx.cpu())
    mod.fit(it, num_epoch=num_epoch, optimizer=opt,
            optimizer_params=dict(
                opt_params or {"learning_rate": 0.1, "momentum": 0.9}),
            eval_metric="acc", initializer=mx.init.Xavier(),
            compiled=True, **kw)
    args, _ = mod.get_params()
    return mod, {k: v.asnumpy() for k, v in args.items()}


def _assert_sharded(mod):
    step = mod._compiled_step
    assert step is not None, "compiled path did not engage"
    assert step._shard is not None, "shard_update path did not engage"
    return step


# ---------------------------------------------------------------------------
# parity
# ---------------------------------------------------------------------------

def test_fit_shard_update_sgd_momentum_parity():
    """12 steps of SGD+momentum: sharded vs replicated compiled fit.

    Tight-allclose, not bitwise: measured drift here is ~1 ulp/step (max
    9e-8 after 12 steps) caused purely by LLVM contracting a different
    multiply of ``momentum*m - lr*g`` into an FMA in the sharded module
    (docs/PERF.md).  Gradients themselves are pinned bitwise-identical by
    the replicated sharding constraint ahead of the shard_map region —
    asserted indirectly by the Adam test below coming out bitwise."""
    mod_s, params_s = _fit(shard_update=True)
    _assert_sharded(mod_s)
    mod_r, params_r = _fit()
    assert mod_r._compiled_step._shard is None
    for name in params_r:
        np.testing.assert_allclose(
            params_s[name], params_r[name], rtol=1e-5, atol=5e-7,
            err_msg="param %r diverged between sharded and replicated fit"
                    % name)


def test_fit_shard_update_adam_parity():
    """Adam-family gate: allclose per the acceptance criteria (and in
    practice bitwise on this workload, which pins the gradient path)."""
    kw = dict(opt="adam", opt_params={"learning_rate": 0.01})
    mod_s, params_s = _fit(shard_update=True, **kw)
    _assert_sharded(mod_s)
    _, params_r = _fit(**kw)
    for name in params_r:
        np.testing.assert_allclose(
            params_s[name], params_r[name], rtol=1e-6, atol=1e-7,
            err_msg="param %r diverged (adam, sharded vs replicated)" % name)


def test_fit_shard_update_steps_per_call_window():
    """The scan window composes with the sharded update: same params as
    the single-step window within the PR-6 scan tolerance, no extra
    signatures beyond the 4+2 window split."""
    _, params_1 = _fit(shard_update=True, steps_per_call=1)
    mod_4, params_4 = _fit(shard_update=True, steps_per_call=4)
    stats = mod_4._compiled_step.cache_stats()
    assert len(stats["signatures"]) == 2, stats
    assert stats["recompiles"] == 2, stats
    for name in params_1:
        np.testing.assert_allclose(
            params_1[name], params_4[name], rtol=1e-5, atol=1e-6,
            err_msg="param %r diverged between shard windows 1 and 4" % name)


# ---------------------------------------------------------------------------
# memory + recompiles
# ---------------------------------------------------------------------------

def test_fit_shard_update_zero_steady_state_recompiles():
    mod, _ = _fit(num_epoch=3, shard_update=True)
    stats = _assert_sharded(mod).cache_stats()
    assert len(stats["signatures"]) == 1, stats
    assert stats["recompiles"] == 1, stats
    assert stats["hits"] == 3 * _N - 1, stats


def test_fit_shard_update_optimizer_state_bytes_one_over_n():
    """The ZeRO-1/2 claim, measured: every non-scalar optimizer-state leaf
    is a flat padded vector whose per-replica shard holds 1/8 of its
    elements, while parameters stay fully replicated on every device."""
    import jax
    n_dev = len(jax.devices())
    mod, _ = _fit(shard_update=True)
    step = _assert_sharded(mod)
    o_keys = [k for k in step.state if k.startswith("o:")]
    assert o_keys, "no optimizer-state entries found"
    for k in o_keys:
        arr = step.state[k]._data
        if arr.ndim == 0:
            continue
        local = arr.addressable_shards[0].data.size
        assert local * n_dev == arr.size, \
            "%s: local shard %d of %d is not 1/%d" % (
                k, local, arr.size, n_dev)
    for k in step.state:
        if k.startswith("p:"):
            arr = step.state[k]._data
            assert arr.addressable_shards[0].data.size == arr.size, \
                "param %s should be replicated" % k


# ---------------------------------------------------------------------------
# 2-bit wire format + shared ResidualStore
# ---------------------------------------------------------------------------

def test_fit_wire_2bit_trains_within_envelope():
    """EF-quantized wire: params track the fp32 sharded run within the
    documented short-horizon envelope (docs/PERF.md: drift is bounded by
    the carried residual, <= threshold per element per step window)."""
    mod_w, params_w = _fit(shard_update=True, wire_format="2bit",
                           wire_threshold=0.5)
    step = _assert_sharded(mod_w)
    assert step._shard.wire == pytest.approx(0.5)
    _, params_f = _fit(shard_update=True)
    for name in params_f:
        drift = np.abs(params_w[name] - params_f[name]).max()
        assert np.isfinite(params_w[name]).all()
        assert drift < 0.5, "EF drift %g exceeds threshold envelope" % drift


def test_fit_wire_2bit_residual_store_is_module_owned_and_carries():
    """Satellite: ONE ResidualStore class serves both the kvstore
    compressed allreduce and the compiled wire format.  With a huge
    threshold nothing ever fires on the wire, so (wd=0) the weights stay
    at their init values while the residual accumulates the full gradient
    signal — proof the error feedback carries across steps instead of
    being dropped."""
    from mxnet_tpu.gradient_compression import ResidualStore
    mx.random.seed(77)
    it = io.NDArrayIter(_DATA, _LABELS, batch_size=_B)
    mod = mx.mod.Module(_convnet(), context=mx.cpu())
    store = mod.gradient_residual_store()
    assert isinstance(store, ResidualStore) and len(store) == 0
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                              "wd": 0.0},
            eval_metric="acc", initializer=mx.init.Xavier(),
            compiled=True, shard_update=True, wire_format="2bit",
            wire_threshold=1e6)
    _assert_sharded(mod)
    # same store object, now populated with one residual row set per param
    assert store is mod.gradient_residual_store()
    assert len(store) > 0
    args, _ = mod.get_params()
    for name, weight in args.items():
        res = store.get(name)
        assert res is not None, "no residual for %r" % name
        r = np.asarray(res._data)
        assert r.ndim == 2, "residual must be the [dp, padded] row matrix"
        # every step's full gradient went into the residual, none reached
        # the weights
        assert np.abs(r).max() > 0, "residual never accumulated for %r" % name
    init_mod = mx.mod.Module(_convnet(), context=mx.cpu())
    init_mod.bind(data_shapes=[("data", (_B, 3, 8, 8))],
                  label_shapes=[("softmax_label", (_B,))])
    mx.random.seed(77)
    init_mod.init_params(mx.init.Xavier())
    init_args, _ = init_mod.get_params()
    for name in args:
        assert np.array_equal(args[name].asnumpy(),
                              init_args[name].asnumpy()), \
            "weights moved though no quantized code ever fired (%r)" % name


def test_residual_store_shared_get_set_semantics():
    from mxnet_tpu.gradient_compression import ResidualStore
    store = ResidualStore()
    assert store.get("k") is None
    made = store.get_or_create("k", lambda: np.zeros(3))
    assert store.get_or_create("k", lambda: np.ones(3)) is made
    store.set("k2", np.ones(2))
    assert "k2" in store and len(store) == 2
    assert sorted(store.keys()) == ["k", "k2"]
    store.clear()
    assert len(store) == 0


def test_kvstore_residuals_use_shared_store_class():
    """The kvstore path keys its error feedback in the same ResidualStore
    (satellite: one auditable residual home, not two ad-hoc dicts)."""
    from mxnet_tpu.gradient_compression import ResidualStore
    kv = mx.kvstore.create("dist_sync")
    assert kv.residual_store is None
    kv.set_gradient_compression({"type": "2bit", "threshold": 2.0})
    assert isinstance(kv.residual_store, ResidualStore)
    kv.init("g", mx.nd.zeros((4,)))
    kv.push("g", mx.nd.ones((4,)))   # below threshold -> all into residual
    np.testing.assert_allclose(
        np.asarray(kv.residual_store.get("g")), 1.0)
    kv.push("g", mx.nd.ones((4,)))   # 1+1 fires; residual drops to 0
    np.testing.assert_allclose(
        np.asarray(kv.residual_store.get("g")), 0.0)


# ---------------------------------------------------------------------------
# crash / resume with sharded state
# ---------------------------------------------------------------------------

def _fit_ckpt(prefix, resume=False, crash_plan=None):
    mx.random.seed(1234)
    it = io.NDArrayIter(_DATA, _LABELS, batch_size=_B)
    mod = mx.mod.Module(_convnet(), context=mx.cpu())
    cbs = [mx.callback.module_checkpoint(mod, prefix,
                                         save_optimizer_states=True)]
    kw = dict(num_epoch=2, optimizer="sgd",
              optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
              initializer=mx.init.Xavier(), epoch_end_callback=cbs,
              compiled=True, shard_update=True)
    if crash_plan is not None:
        with faults.plan(crash_plan):
            mod.fit(it, **kw)
    else:
        mod.fit(it, auto_resume=resume, **kw)
    _assert_sharded(mod)
    args, _ = mod.get_params()
    return {k: v.asnumpy() for k, v in args.items()}


def test_fit_shard_update_killed_mid_checkpoint_resumes_bitwise(tmp_path):
    """auto_resume restores the flat dp-sharded optimizer-state vectors
    bitwise (check_flat_state recognizes the padded layout on load)."""
    ref = _fit_ckpt(str(tmp_path / "ref"))
    prefix = str(tmp_path / "kill")
    plan = faults.FaultPlan(0).add("checkpoint.replace", kind="crash",
                                   after=1, times=1)
    with pytest.raises(faults.SimulatedCrash):
        _fit_ckpt(prefix, crash_plan=plan)
    resumed = _fit_ckpt(prefix, resume=True)
    for k in ref:
        assert np.array_equal(ref[k], resumed[k]), \
            "param %r diverged after kill mid-checkpoint" % k


# ---------------------------------------------------------------------------
# guard rails
# ---------------------------------------------------------------------------

def test_fit_shard_update_requires_compiled():
    mx.random.seed(77)
    it = io.NDArrayIter(_DATA, _LABELS, batch_size=_B)
    mod = mx.mod.Module(_convnet(), context=mx.cpu())
    with pytest.raises(ValueError, match="shard_update"):
        mod.fit(it, num_epoch=1, optimizer="sgd",
                initializer=mx.init.Xavier(), compiled=False,
                shard_update=True)


def test_fit_wire_format_requires_shard_update():
    mx.random.seed(77)
    it = io.NDArrayIter(_DATA, _LABELS, batch_size=_B)
    mod = mx.mod.Module(_convnet(), context=mx.cpu())
    with pytest.raises(ValueError, match="wire_format"):
        mod.fit(it, num_epoch=1, optimizer="sgd",
                initializer=mx.init.Xavier(), compiled=True,
                wire_format="2bit")


def test_fit_shard_update_non_elementwise_falls_back(caplog):
    """LBSGD's LARS layer-norm scaling couples elements, so the sharded
    elementwise update would change the math: fit warns and trains
    replicated via the eager loop."""
    with caplog.at_level(logging.WARNING):
        mod, params = _fit(num_epoch=1, opt="lbsgd",
                           opt_params={"learning_rate": 0.1},
                           shard_update=True)
    assert mod._compiled_step is None
    assert any("REPLICATED" in r.getMessage() for r in caplog.records), \
        [r.getMessage() for r in caplog.records]
    assert all(np.isfinite(v).all() for v in params.values())


# ---------------------------------------------------------------------------
# bandwidth tool modes + the committed accuracy-vs-bandwidth artifact
# ---------------------------------------------------------------------------

def _run_bandwidth(extra_args):
    import subprocess
    import sys
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bandwidth.py"),
         "--smoke"] + extra_args,
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-3000:]
    import json
    return json.loads(next(l for l in res.stdout.splitlines()
                           if l.startswith("{")))


def test_bandwidth_tool_collective_smoke_schema():
    rec = _run_bandwidth(["--collective", "reduce_scatter"])
    assert rec["metric"] == "mesh_reduce_scatter"
    assert rec["devices"] == 8
    assert "value" in rec and rec["unit"] == "GB/s"


def test_bandwidth_tool_wire_2bit_smoke_schema():
    rec = _run_bandwidth(["--wire", "2bit"])
    assert rec["metric"] == "gradient_reduce_wire_2bit"
    assert rec["wire_reduction_x"] >= 3.0
    assert rec["wire_bytes_per_step"] * 4 == rec["fp32_bytes_per_step"]
    assert rec["accuracy_delta"] >= 0 and np.isfinite(rec["accuracy_delta"])
    assert "value" in rec
