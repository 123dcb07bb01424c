"""Async input pipeline (tier-1): DeviceFeed, DataLoader lifecycle, the
device-placement consumers and the mxstress feed scenario.

Covers this PR's contracts end to end:
* ``io.DeviceFeed`` — order/conservation, staging, stats, worker-error
  propagation, deterministic close (idempotent, mid-epoch safe);
* ``DataLoader`` — honored ``pin_memory``, ``prefetch_to_device``,
  persistent-pool ``close()`` (drains in-flight work; a mid-epoch worker
  exception can't strand the pool), repeated + concurrent ``__iter__``;
* consumers — ``PrefetchingIter(ctx=...)`` and ``BaseModule.fit(
  prefetch_to_device=...)`` train correctly on staged batches, and a
  ``DataLoader`` feed drives ``CompiledTrainStep.from_block`` to the
  synchronous loader's parameters with zero steady-state recompiles
  (``fit()``'s own feed counts are in tests/test_tracing.py);
* the seeded ``feed`` chaos scenario stays violation-free.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, io, nd
from mxnet_tpu.io import DeviceFeed


# ---------------------------------------------------------------------------
# DeviceFeed semantics
# ---------------------------------------------------------------------------

def test_device_feed_order_and_staging():
    src = [np.full((4,), i, np.float32) for i in range(10)]
    with DeviceFeed(src, ctx=mx.cpu(0), depth=2) as feed:
        out = [np.asarray(x) for x in feed]
    assert len(out) == 10
    for i, b in enumerate(out):
        np.testing.assert_array_equal(b, np.full((4,), i, np.float32))
    stats = feed.stats()
    assert stats["batches"] == 10
    assert stats["max_queue_depth"] >= 1
    assert "h2d_ms" in stats


def test_device_feed_structure_preserving():
    batch = (nd.array(np.ones((2, 3), np.float32)),
             np.arange(2, dtype=np.float32))
    feed = DeviceFeed([batch], ctx=mx.cpu(0))
    (a, b), = list(feed)
    assert isinstance(a, nd.NDArray) and a.context == mx.cpu(0)
    np.testing.assert_array_equal(np.asarray(b), [0.0, 1.0])
    # DataBatch staging keeps meta and re-wraps data/label as NDArrays
    db = io.DataBatch(data=[nd.ones((2, 2))], label=[nd.zeros((2,))], pad=1)
    staged, = list(DeviceFeed([db], ctx=mx.cpu(0)))
    assert staged.pad == 1
    assert isinstance(staged.data[0], nd.NDArray)


def test_device_feed_snapshots_callers_context_scope():
    """With ctx omitted, the feed must honor the CALLER's `with Context:`
    scope — the worker thread's own thread-local stack is a fresh cpu
    default and must not win."""
    import mxnet_tpu as mx
    pinned = mx.Context("cpu_pinned", 0)
    with pinned:
        feed = DeviceFeed([nd.ones((2, 2))])
    staged, = list(feed)
    assert staged.context == pinned


def test_device_feed_transform_runs_before_staging():
    feed = DeviceFeed([1, 2, 3], ctx=mx.cpu(0),
                      transform=lambda i: np.full((2,), i * 10, np.float32))
    out = [np.asarray(x)[0] for x in feed]
    assert out == [10.0, 20.0, 30.0]


def test_device_feed_error_propagates_after_good_prefix():
    def src():
        yield np.zeros((2,), np.float32)
        yield np.ones((2,), np.float32)
        raise ValueError("decode exploded")

    feed = DeviceFeed(src(), ctx=mx.cpu(0))
    it = iter(feed)
    next(it)
    next(it)
    with pytest.raises(ValueError, match="decode exploded"):
        next(it)
    # worker joined; the error is sticky — a consumer that catches the
    # first raise and retries must NOT see a clean StopIteration (an epoch
    # that died at batch k would be indistinguishable from a completed one)
    with pytest.raises(ValueError, match="decode exploded"):
        next(it)


def test_device_feed_close_mid_epoch_is_deterministic():
    feed = DeviceFeed((np.zeros((2,), np.float32) for _ in range(1000)),
                      ctx=mx.cpu(0), depth=1)
    it = iter(feed)
    next(it)
    feed.close()
    feed.close()    # idempotent
    assert not feed._thread.is_alive()
    with pytest.raises((StopIteration, RuntimeError)):
        next(it)


def test_abandoned_feed_iterator_is_collectable_and_stops_worker():
    """An epoch abandoned mid-stream (``break`` out of a feed-backed loop)
    must not leak its worker: the thread targets a module function over a
    separate state object, so the dropped DeviceFeed stays collectable and
    __del__ -> close() stops the worker."""
    import gc
    import weakref

    ds, _, _ = _dataset(100)
    loader = gluon.data.DataLoader(ds, batch_size=2,
                                   prefetch_to_device=mx.cpu(0))
    it = iter(loader)
    next(it)
    thread = it._thread
    ref = weakref.ref(it)
    del it          # the consumer walks away mid-epoch
    gc.collect()
    assert ref() is None, "worker kept the abandoned feed alive"
    thread.join(5.0)
    assert not thread.is_alive(), "abandoned feed leaked its worker thread"
    loader.close()


def test_device_feed_rejects_bad_depth():
    with pytest.raises(ValueError):
        DeviceFeed([], depth=0)


def test_device_feed_mesh_shards_over_dp():
    # multi-chip staging: leaves arrive dp-sharded over the virtual mesh
    from mxnet_tpu.parallel import make_mesh
    mesh = make_mesh()
    n_dev = mesh.devices.size
    src = [np.arange(n_dev * 2 * 3, dtype=np.float32).reshape(n_dev * 2, 3)]
    staged, = list(DeviceFeed(src, mesh=mesh))
    assert len(staged.sharding.device_set) == n_dev
    np.testing.assert_array_equal(np.asarray(staged), src[0])


# ---------------------------------------------------------------------------
# DataLoader: feed paths + lifecycle
# ---------------------------------------------------------------------------

def _dataset(n=20):
    X = np.random.uniform(size=(n, 3)).astype(np.float32)
    Y = np.arange(n, dtype=np.float32)
    return gluon.data.ArrayDataset(X, Y), X, Y


def test_dataloader_pin_memory_honored_not_ignored():
    ds, X, Y = _dataset()
    with gluon.data.DataLoader(ds, batch_size=5, pin_memory=True) as loader:
        batches = list(loader)
    assert len(batches) == 4
    xb, yb = batches[0]
    # pinned batches live in committed host-side buffers (kCPUPinned analog)
    assert xb.context.device_type == "cpu_pinned"
    np.testing.assert_allclose(xb.asnumpy(), X[:5])
    np.testing.assert_allclose(yb.asnumpy(), Y[:5])


def test_dataloader_prefetch_to_device_matches_sync_path():
    ds, X, Y = _dataset()
    sync = [b[1].asnumpy() for b in gluon.data.DataLoader(ds, batch_size=5)]
    with gluon.data.DataLoader(ds, batch_size=5,
                               prefetch_to_device=mx.cpu(0)) as loader:
        it = iter(loader)          # the DeviceFeed itself
        fed = [b[1].asnumpy() for b in it]
        assert it.stats()["batches"] == 4
    np.testing.assert_allclose(np.concatenate(fed), np.concatenate(sync))


def test_dataloader_prefetch_to_device_type_checked():
    ds, _, _ = _dataset()
    with pytest.raises(TypeError):
        gluon.data.DataLoader(ds, batch_size=5, prefetch_to_device="tpu")


def test_dataloader_close_idempotent_and_blocks_new_epochs():
    ds, _, _ = _dataset()
    loader = gluon.data.DataLoader(ds, batch_size=5, num_workers=2,
                                   thread_pool=True)
    assert len(list(loader)) == 4
    loader.close()
    loader.close()
    with pytest.raises(RuntimeError, match="closed"):
        iter(loader)


def test_dataloader_close_mid_epoch_drains_in_flight():
    ds, _, _ = _dataset(40)
    loader = gluon.data.DataLoader(ds, batch_size=4, num_workers=2,
                                   thread_pool=True)
    it = iter(loader)
    next(it)   # leave the rest of the prefetch window in flight
    loader.close()   # must drain + join, not hang or leak workers
    assert loader._pool is None and not loader._in_flight


def test_dataloader_repeated_and_concurrent_iter():
    ds, _, Y = _dataset()
    loader = gluon.data.DataLoader(ds, batch_size=5, num_workers=2,
                                   thread_pool=True)
    with loader:
        a, b = iter(loader), iter(loader)
        # interleave two concurrent epochs over the one persistent pool
        ra = [x[1].asnumpy() for x in a]
        rb = [x[1].asnumpy() for x in b]
        rc = [x[1].asnumpy() for x in loader]   # and a repeated epoch
    for r in (ra, rb, rc):
        np.testing.assert_allclose(np.concatenate(r), Y)


class _FailingDataset:
    def __len__(self):
        return 12

    def __getitem__(self, i):
        if i == 9:
            raise ValueError("bad sample 9")
        return np.zeros((2,), np.float32)


def test_dataloader_worker_exception_does_not_strand_pool():
    loader = gluon.data.DataLoader(_FailingDataset(), batch_size=2,
                                   num_workers=2, thread_pool=True)
    with pytest.raises(ValueError, match="bad sample 9"):
        list(loader)
    # pool survives the failed epoch: a fresh epoch reaches the same point
    n = 0
    with pytest.raises(ValueError):
        for _ in loader:
            n += 1
    assert n == 4   # batches [0..7] precede the poisoned one
    loader.close()


def test_dataloader_prefetch_knob_validated():
    ds, _, _ = _dataset()
    with pytest.raises(ValueError):
        gluon.data.DataLoader(ds, batch_size=5, prefetch=0)
    loader = gluon.data.DataLoader(ds, batch_size=5, num_workers=1,
                                   thread_pool=True, prefetch=2)
    assert len(list(loader)) == 4
    loader.close()


# ---------------------------------------------------------------------------
# consumers: PrefetchingIter ctx + Module.fit prefetch_to_device
# ---------------------------------------------------------------------------

def test_prefetching_iter_ctx_stages_batches():
    X = np.random.uniform(size=(12, 4)).astype(np.float32)
    Y = np.arange(12, dtype=np.float32)
    pf = io.PrefetchingIter(io.NDArrayIter(X, Y, batch_size=4), ctx=mx.cpu(0))
    seen = 0
    for batch in pf:
        assert batch.data[0].context == mx.cpu(0)
        seen += 1
    pf.reset()
    assert sum(1 for _ in pf) == seen == 3


def test_prefetching_iter_abandoned_is_collectable():
    """Dropping a PrefetchingIter mid-epoch must free it (the feed source
    generator may not close over the iterator) so the DeviceFeed GC
    backstop stops the worker."""
    import gc
    import weakref

    X = np.random.uniform(size=(40, 4)).astype(np.float32)
    Y = np.arange(40, dtype=np.float32)
    pf = io.PrefetchingIter(io.NDArrayIter(X, Y, batch_size=2), ctx=mx.cpu(0))
    pf.next()
    thread = pf._feed._thread
    ref = weakref.ref(pf)
    del pf
    gc.collect()
    assert ref() is None, "worker kept the abandoned PrefetchingIter alive"
    thread.join(5.0)
    assert not thread.is_alive(), "abandoned prefetcher leaked its worker"


def test_prefetching_iter_worker_error_reaches_consumer():
    """A staging/source failure in the prefetch worker must surface in
    next(), not kill the thread silently and hang the consumer."""

    class _Poisoned(io.DataIter):
        def __init__(self):
            super().__init__(batch_size=2)
            self.provide_data = [io.DataDesc("data", (2, 3))]
            self.provide_label = []
            self._n = 0

        def next(self):
            self._n += 1
            if self._n == 2:
                raise RuntimeError("decode blew up")
            return io.DataBatch(data=[nd.zeros((2, 3))], label=[], pad=0)

        def reset(self):
            self._n = 0

    pf = io.PrefetchingIter(_Poisoned())
    assert next(pf).data[0].shape == (2, 3)
    with pytest.raises(RuntimeError, match="decode blew up"):
        next(pf)


def test_module_fit_with_device_feed_converges():
    from tests.test_module import _make_mlp, _synthetic_blobs
    data, labels = _synthetic_blobs(256)
    train_iter = io.NDArrayIter(data, labels, batch_size=32, shuffle=True)
    mod = mx.mod.Module(_make_mlp(), context=mx.cpu())
    mod.fit(train_iter, num_epoch=4, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            initializer=mx.init.Xavier(),
            prefetch_to_device=mx.cpu(0))
    train_iter.reset()
    score = mod.score(train_iter, "acc")
    assert score[0][1] > 0.9, "accuracy %s too low through the feed" % (
        score[0][1],)


def test_dataloader_feed_drives_block_step_like_the_sync_loader():
    """A Gluon loop over ``DataLoader(prefetch_to_device=...)`` into
    ``CompiledTrainStep.from_block``: the feed changes where a batch is
    staged, not what is trained.  Counts only: one compile, every later
    step a cache hit, as many batches through the feed as steps taken,
    and the synchronous loader's parameters to the bit."""
    from mxnet_tpu.module.compiled_step import CompiledTrainStep
    _, X, Y = _dataset()
    ds = gluon.data.ArrayDataset(X, Y % 2)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def train(**loader_kw):
        mx.random.seed(3)
        net = gluon.nn.Dense(2, in_units=3)
        net.initialize(mx.init.Xavier())
        cstep = CompiledTrainStep.from_block(
            net, lambda outs, y: loss_fn(outs[0], y).mean(),
            mx.optimizer.SGD(learning_rate=0.1, momentum=0.9))
        fed = 0
        with gluon.data.DataLoader(ds, batch_size=5, **loader_kw) as loader:
            for _ in range(2):
                it = iter(loader)
                for xb, yb in it:
                    cstep.step(xb, yb)
                if loader_kw:
                    fed += it.stats()["batches"]
        return net.weight.data().asnumpy(), cstep.cached_op.cache_stats(), fed

    w_sync, _, _ = train()
    w_feed, cache, fed = train(prefetch_to_device=mx.cpu(0))
    assert cache["recompiles"] == 1 and cache["hits"] == 7, cache
    assert fed == 8
    assert np.array_equal(w_feed, w_sync)


# ---------------------------------------------------------------------------
# observability: the feed counters land in profiler dumps
# ---------------------------------------------------------------------------

def test_feed_counters_land_in_profiler_trace(tmp_path):
    from mxnet_tpu import profiler
    trace = tmp_path / "feed_trace.json"
    profiler.set_config(filename=str(trace))
    profiler.set_state("run")
    try:
        src = [np.full((4,), i, np.float32) for i in range(6)]
        with DeviceFeed(src, ctx=mx.cpu(0)) as feed:
            list(feed)
    finally:
        profiler.set_state("stop")
    profiler.dump()
    import json
    events = json.load(open(trace))["traceEvents"]
    names = {e["name"] for e in events if e.get("ph") == "C"}
    assert "feed:queue_depth" in names
    assert "feed:h2d_ms" in names


# ---------------------------------------------------------------------------
# chaos: the mxstress feed scenario (full smoke runs in test_concurrency)
# ---------------------------------------------------------------------------

def test_mxstress_feed_scenario_seeded():
    from mxnet_tpu.analysis import schedule
    assert "feed" in schedule.SCENARIOS
    report = schedule.stress(seeds=range(5), scenarios=("feed",))
    flat = ["seed %s %s" % (seed, v)
            for seed, per_seed in report["seeds"].items()
            for vs in per_seed.values() for v in vs]
    assert report["violations"] == 0, "\n".join(flat)
    assert report["preemptions"] > 0
