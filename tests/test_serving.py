"""mxnet_tpu.serving: dynamic-batching inference server (docs/SERVING.md).

Covers the serving acceptance gates: concurrent same-shape requests coalesce
into shared batches, deadlines expire as TIMEOUT statuses, a full admission
queue sheds with OVERLOADED instead of growing, and — the big one — a
mixed-shape concurrent workload after warmup completes with ZERO new XLA
compiles (CachedOp.cache_stats() recompile delta == 0) while every request's
output matches its unbatched reference.
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, serving
from mxnet_tpu.gluon import nn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class PoolMLP(mx.gluon.HybridBlock):
    """(B, L, F) -> mean over L -> MLP: one model, many sequence lengths."""

    def __init__(self, feat=8, hidden=16, classes=4, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.h = nn.Dense(hidden, activation="relu", in_units=feat)
            self.out = nn.Dense(classes, in_units=hidden)

    def hybrid_forward(self, F, x):
        return self.out(self.h(F.mean(x, axis=1)))


def _make_net(feat=8):
    net = PoolMLP(feat=feat)
    net.initialize(mx.init.Xavier())
    return net


def _reference(net, x):
    """Unbatched eager forward for one request."""
    return net(nd.array(x[None])).asnumpy()[0]


# ---------------------------------------------------------------------------
# coalescing
# ---------------------------------------------------------------------------

def test_concurrent_clients_coalesce_into_shared_batches():
    net = _make_net()
    server = serving.ModelServer()
    server.load_model("m", net, input_shapes=[(4, 8)], max_batch=8,
                      batch_ladder=[1, 8], linger_ms=60.0, warmup=True)
    rng = np.random.RandomState(0)
    xs = [rng.randn(4, 8).astype(np.float32) for _ in range(8)]
    results = [None] * len(xs)
    barrier = threading.Barrier(len(xs))

    def client(i):
        barrier.wait()
        results[i] = server.predict("m", xs[i], timeout_ms=5000)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(xs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    snap = server.stats()["models"]["m"]
    server.stop()
    for i, res in enumerate(results):
        assert res.status == serving.OK, res
        np.testing.assert_allclose(res.output, _reference(net, xs[i]),
                                   rtol=1e-5, atol=1e-5)
    # 8 simultaneous same-shape requests under a generous linger must share
    # batches: strictly fewer dispatches than requests
    assert 1 <= snap["batches"] < len(xs)
    assert snap["avg_batch"] > 1.0


# ---------------------------------------------------------------------------
# the acceptance gate: mixed shapes, many threads, zero recompiles
# ---------------------------------------------------------------------------

def test_mixed_shape_workload_zero_recompiles_after_warmup():
    shapes = [(2, 8), (4, 8), (8, 8), (16, 8)]     # >= 4 distinct shapes
    net = _make_net()
    server = serving.ModelServer()
    model = server.load_model("m", net, input_shapes=shapes, max_batch=4,
                              batch_ladder=[1, 4], linger_ms=5.0,
                              max_queue=256, warmup=True)
    warm = model.warmup_report
    assert warm["signatures"] == len(shapes) * 2       # ladder 1/4
    assert warm["compiles"] == warm["signatures"]
    miss_after_warmup = model.cache_stats()["misses"]

    n_threads, per_thread = 4, 9                       # 36 requests >= 32
    rng = np.random.RandomState(1)
    payloads = {s: [rng.randn(*s).astype(np.float32) for _ in range(per_thread)]
                for s in shapes}
    results = {}
    lock = threading.Lock()
    barrier = threading.Barrier(n_threads)

    def client(tid):
        barrier.wait()
        for i in range(per_thread):
            shape = shapes[(tid + i) % len(shapes)]
            x = payloads[shape][i]
            res = server.predict("m", x, timeout_ms=10000)
            with lock:
                results[(tid, i)] = (x, res)

    threads = [threading.Thread(target=client, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    cache = model.cache_stats()
    snap = server.stats()["models"]["m"]
    server.stop()

    assert len(results) == n_threads * per_thread
    for (tid, i), (x, res) in results.items():
        assert res.status == serving.OK, (tid, i, res)
        np.testing.assert_allclose(res.output, _reference(net, x),
                                   rtol=1e-5, atol=1e-5)
    # ZERO new XLA compiles in steady state — the whole point of the ladder
    assert cache["misses"] == miss_after_warmup
    assert snap["cache"]["recompiles"] == warm["cache"]["misses"]
    assert snap["ok"] == n_threads * per_thread


# ---------------------------------------------------------------------------
# deadlines and shedding
# ---------------------------------------------------------------------------

def test_deadline_expiry_returns_timeout_status():
    net = _make_net()
    server = serving.ModelServer()
    server.load_model("m", net, input_shapes=[(4, 8)], max_batch=2,
                      linger_ms=1.0, warmup=False)
    server.pause("m")                       # worker idles; request ages out
    res = server.predict("m", np.zeros((4, 8), np.float32), timeout_ms=30)
    server.resume("m")
    snap = server.stats()["models"]["m"]
    server.stop()
    assert res.status == serving.TIMEOUT
    assert res.outputs is None
    assert snap["timeouts"] == 1
    assert snap["ok"] == 0


def test_overload_sheds_instead_of_queueing_unboundedly():
    net = _make_net()
    server = serving.ModelServer()
    server.load_model("m", net, input_shapes=[(4, 8)], max_batch=2,
                      linger_ms=1.0, max_queue=4, warmup=False)
    server.pause("m")
    x = np.zeros((4, 8), np.float32)
    handles = [server.predict_async("m", x) for _ in range(4)]
    assert all(isinstance(h, serving.Request) for h in handles)
    # queue is at the bound: admission now sheds immediately, with a status
    shed = server.predict("m", x)
    assert shed.status == serving.OVERLOADED
    assert server.stats()["models"]["m"]["shed"] == 1
    server.resume("m")
    results = [server.result("m", h) for h in handles]
    snap = server.stats()["models"]["m"]
    server.stop()
    assert all(r.status == serving.OK for r in results)
    assert snap["ok"] == 4 and snap["shed"] == 1
    assert snap["queue_depth"] == 0


def test_unlisted_shape_rejected_before_it_can_compile():
    net = _make_net()
    server = serving.ModelServer()
    model = server.load_model("m", net, input_shapes=[(4, 8)], max_batch=2,
                              warmup=False)
    misses = model.cache_stats()["misses"]
    res = server.predict("m", np.zeros((5, 8), np.float32))
    snap = server.stats()["models"]["m"]
    server.stop()
    assert res.status == serving.INVALID_INPUT
    assert "bucket menu" in res.error
    assert snap["invalid"] == 1
    assert model.cache_stats()["misses"] == misses     # nothing compiled


def test_duplicate_load_fails_fast_and_keeps_original_serving():
    net = _make_net()
    server = serving.ModelServer()
    server.load_model("m", net, input_shapes=[(4, 8)], max_batch=2,
                      warmup=False)
    with pytest.raises(mx.MXNetError, match="already loaded"):
        server.load_model("m", _make_net(), input_shapes=[(4, 8)],
                          max_batch=2, warmup=False)
    # the original model must be untouched by the failed load
    res = server.predict("m", np.zeros((4, 8), np.float32), timeout_ms=5000)
    server.stop()
    assert res.status == serving.OK


def test_malformed_payload_is_a_status_not_an_exception():
    net = _make_net()
    server = serving.ModelServer()
    server.load_model("m", net, input_shapes=[(4, 8)], max_batch=2,
                      warmup=False)
    # wrong input count for a 1-input model: status, not ValueError
    res = server.predict("m", (np.zeros((4, 8), np.float32),) * 2)
    snap = server.stats()["models"]["m"]
    server.stop()
    assert res.status == serving.INVALID_INPUT
    assert "input" in res.error
    assert snap["invalid"] == 1


# ---------------------------------------------------------------------------
# cache_stats as a public debugging aid
# ---------------------------------------------------------------------------

def test_cached_op_cache_stats_counts_signatures():
    net = _make_net()
    net.hybridize()
    net(nd.zeros((1, 4, 8)))                  # build + first compile
    cop = net._cached_op
    base = cop.cache_stats()
    assert base["misses"] == 1 and base["recompiles"] == 1
    net(nd.zeros((1, 4, 8)))                  # same signature: hit
    net(nd.zeros((2, 4, 8)))                  # new signature: miss
    stats = cop.cache_stats()
    assert stats["hits"] == base["hits"] + 1
    assert stats["misses"] == 2
    assert len(stats["signatures"]) == 2
    for rec in stats["signatures"].values():
        assert set(rec) == {"hits", "misses"}
    assert any(s.startswith("infer|") for s in stats["signatures"])
    cop.reset_cache_stats()
    assert cop.cache_stats()["misses"] == 0


# ---------------------------------------------------------------------------
# exported-artifact serving path
# ---------------------------------------------------------------------------

def test_exported_model_serves_and_matches(tmp_path):
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(8, activation="relu", in_units=6),
                nn.Dense(3, in_units=8))
    net.initialize()
    prefix = str(tmp_path / "m")
    net.export(prefix)

    server = serving.ModelServer()
    server.load_exported("m", prefix, input_shapes=[(6,)], max_batch=2,
                         warmup=True)
    x = np.random.RandomState(3).randn(6).astype(np.float32)
    res = server.predict("m", x, timeout_ms=5000)
    snap = server.stats()["models"]["m"]
    server.stop()
    assert res.status == serving.OK
    np.testing.assert_allclose(res.output, _reference(net, x),
                               rtol=1e-5, atol=1e-5)
    assert snap["cache"]["recompiles"] == snap["warmup"]["cache"]["misses"]


# ---------------------------------------------------------------------------
# profiler integration
# ---------------------------------------------------------------------------

def test_serving_counters_land_in_profiler_dump(tmp_path):
    from mxnet_tpu import profiler
    net = _make_net()
    server = serving.ModelServer()
    server.load_model("m", net, input_shapes=[(4, 8)], max_batch=2,
                      linger_ms=1.0, warmup=False)
    trace = str(tmp_path / "profile.json")
    profiler.set_config(filename=trace)
    profiler.set_state("run")
    try:
        for _ in range(3):
            res = server.predict("m", np.ones((4, 8), np.float32),
                                 timeout_ms=5000)
            assert res.status == serving.OK
    finally:
        profiler.set_state("stop")
        profiler.dump()
        server.stop()
    events = json.load(open(trace))["traceEvents"]
    counters = {e["name"] for e in events if e.get("ph") == "C"}
    assert "m:queue_depth" in counters
    assert "m:batch_ms" in counters
    batch_vals = [e["args"]["value"] for e in events
                  if e.get("ph") == "C" and e["name"] == "m:batch_ms"]
    assert batch_vals and all(v >= 0 for v in batch_vals)


# ---------------------------------------------------------------------------
# bucket ladder unit behavior
# ---------------------------------------------------------------------------

def test_bucket_ladder_rungs_and_lookup():
    ladder = serving.BucketLadder(max_batch=8)
    assert list(ladder) == [1, 2, 4, 8]
    assert [ladder.bucket(n) for n in (1, 2, 3, 5, 8)] == [1, 2, 4, 8, 8]
    custom = serving.BucketLadder(max_batch=6, sizes=[1, 3, 6])
    assert list(custom) == [1, 3, 6] and custom.bucket(4) == 6
    with pytest.raises(ValueError):
        serving.BucketLadder(sizes=[0, 2])


def test_multi_input_model_batches_all_inputs():
    class TwoIn(mx.gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.d = nn.Dense(3, in_units=5)

        def hybrid_forward(self, F, x, scale):
            return self.d(x) * F.reshape(scale, (-1, 1))

    net = TwoIn()
    net.initialize()
    server = serving.ModelServer()
    server.load_model("m", net, input_shapes=[((5,), ())], max_batch=2,
                      linger_ms=1.0, warmup=False)
    x = np.arange(5, dtype=np.float32)
    res = server.predict("m", (x, np.float32(2.0)), timeout_ms=5000)
    server.stop()
    assert res.status == serving.OK
    ref = (net(nd.array(x[None]), nd.array(np.array([2.0], np.float32)))
           .asnumpy()[0])
    np.testing.assert_allclose(res.output, ref, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# lifecycle: shutdown with requests in flight (docs/ROBUSTNESS.md)
# ---------------------------------------------------------------------------

def test_shutdown_during_inflight_requests_is_clean_unavailable():
    """Requests caught by server.stop() terminate with the retryable
    UNAVAILABLE status — nobody hangs on a dead batcher queue, nothing
    raises KeyError, and post-stop calls get the same clean status."""
    net = _make_net()
    server = serving.ModelServer()
    model = server.load_model("m", net, input_shapes=[(4, 8)], max_batch=4,
                              max_queue=64, linger_ms=1.0, warmup=True)
    # pause dispatch so submitted requests are guaranteed still queued
    # when stop() lands
    server.pause("m")
    x = np.ones((4, 8), np.float32)
    handles = [server.predict_async("m", x) for _ in range(6)]
    assert all(not isinstance(h, serving.InferenceResult) for h in handles)

    resolved = {}
    threads = []

    def waiter(i, h):
        resolved[i] = server.result("m", h)

    for i, h in enumerate(handles[:3]):   # some clients already waiting...
        t = threading.Thread(target=waiter, args=(i, h))
        t.start()
        threads.append(t)
    time.sleep(0.05)
    server.stop()                          # ...when the server goes down
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive(), "result() hung across shutdown"
    for i, h in enumerate(handles[3:], start=3):   # ...and some after
        resolved[i] = server.result("m", h)
    assert len(resolved) == len(handles)
    for i, res in resolved.items():
        assert res.status == serving.UNAVAILABLE, (i, res)
        assert res.outputs is None
    # post-stop predict: clean terminal status, not an exception
    res = server.predict("m", x, timeout_ms=50)
    assert res.status == serving.UNAVAILABLE
    # teardown accounting conserves: every ADMITTED request reached exactly
    # one terminal counter — the drained ones land in `unavailable`, so
    # requests == ok + timeouts + errors + unavailable holds across stop()
    snap = model.stats.snapshot()
    assert snap["requests"] == len(handles)
    assert snap["unavailable"] == len(handles)
    assert snap["requests"] == (snap["ok"] + snap["timeouts"]
                                + snap["errors"] + snap["unavailable"])


def test_result_with_never_loaded_name_raises_not_clobbers():
    """A typo'd model name in result() must raise the unknown-model error —
    not silently claim a live request UNAVAILABLE on a healthy server."""
    net = _make_net()
    server = serving.ModelServer()
    server.load_model("m", net, input_shapes=[(4, 8)], max_batch=4,
                      linger_ms=1.0, warmup=True)
    try:
        handle = server.predict_async("m", np.ones((4, 8), np.float32))
        with pytest.raises(mx.MXNetError):
            server.result("nope", handle)
        # the request itself is untouched and resolves normally
        res = server.result("m", handle)
        assert res.status == serving.OK
    finally:
        server.stop()


def test_stopped_server_refuses_new_loads():
    server = serving.ModelServer()
    server.stop()
    with pytest.raises(mx.MXNetError):
        server.load_model("m", _make_net(), input_shapes=[(4, 8)])


# ---------------------------------------------------------------------------
# serve_bench smoke (the tier-1 wiring for tools/serve_bench.py)
# ---------------------------------------------------------------------------

def test_serve_bench_smoke_artifact(tmp_path):
    # in-process (not a subprocess): tier-1 runs on a 1-core box and a
    # fresh interpreter + jax import would cost ~15s for no extra coverage
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import serve_bench
    out = str(tmp_path / "report.json")
    rc = serve_bench.main(["--smoke", "--out", out])
    assert rc == 0
    report = json.load(open(out))
    assert report["steady_state_recompiles"] == 0
    assert report["statuses"].get("OK") == report["workload"]["total_requests"]
    assert set(report["latency_ms"]) == {"p50", "p95", "p99"}
    assert "throughput_rps" in report   # reported, never gated here


# ---------------------------------------------------------------------------
# decode-engine observability surface (attach_engine / stats / health)
# ---------------------------------------------------------------------------

def _tiny_engine(name):
    from mxnet_tpu.serving.decode import DecodeEngine, TinyCausalLM
    model = TinyCausalLM(vocab_size=16, hidden=8, num_layers=1,
                        num_heads=1, max_len=32, seed=3)
    return DecodeEngine(model, name=name, max_slots=2, block_size=4,
                        max_prompt_len=8, max_new_tokens=8, max_queue=16)


def test_attached_engine_reports_through_server_stats_and_health():
    server = serving.ModelServer()
    eng = _tiny_engine("lm")
    try:
        server.attach_engine(eng)
        assert server.engines() == ["lm"]
        stream = eng.generate([1, 2, 3], max_new_tokens=4, timeout_ms=30000)
        assert stream.status == serving.OK
        # DecodeStats surfaces through the SAME stats()/health() the fleet
        # router reads for batched models
        snap = server.stats()["engines"]["lm"]
        assert snap["ok"] >= 1
        assert snap["health"] == "HEALTHY"
        assert {"kv", "cache", "breaker"} <= set(snap)
        assert server.health("lm") == "HEALTHY"
    finally:
        server.stop()
    # server.stop() tears the attached engine down with it
    refused = eng.generate([1], max_new_tokens=1, timeout_ms=5000)
    assert refused.status == serving.UNAVAILABLE


def test_engine_and_model_names_are_one_namespace():
    server = serving.ModelServer()
    eng = _tiny_engine("m")
    clash = _tiny_engine("m")
    try:
        server.load_model("m", _make_net(), input_shapes=[(4, 8)])
        with pytest.raises(mx.MXNetError, match="already a loaded model"):
            server.attach_engine(clash)
        server.unload("m")
        server.attach_engine(eng)
        with pytest.raises(mx.MXNetError, match="already attached"):
            server.attach_engine(clash)
        with pytest.raises(mx.MXNetError, match="already an attached"):
            server.load_model("m", _make_net(), input_shapes=[(4, 8)])
        with pytest.raises(mx.MXNetError, match="no engine 'ghost'"):
            server.detach_engine("ghost")
    finally:
        server.stop()
        clash.stop()


def test_detach_engine_returns_it_running():
    server = serving.ModelServer()
    eng = _tiny_engine("lm")
    try:
        server.attach_engine(eng)
        got = server.detach_engine("lm")
        assert got is eng
        assert server.engines() == []
        # detaching is an ownership transfer, not a teardown
        stream = eng.generate([1, 2], max_new_tokens=2, timeout_ms=30000)
        assert stream.status == serving.OK
        with pytest.raises(mx.MXNetError):
            server.health("lm")
    finally:
        server.stop()
        eng.stop()
