"""DeviceFeed: the asynchronous device-feed stage of the input pipeline.

Reference: ``src/io/iter_prefetcher.h`` keeps decoded batches one step ahead
of the consumer; both the MXNet paper (arXiv 1512.01274 §4) and TensorFlow's
(arXiv 1605.08695) name overlapping input preprocessing/transfer with compute
as a first-class throughput lever.  The compute side of this repro is one
fused XLA module per step (module/compiled_step.py); this module is the matching
host side: without it every training loop pays decode + batchify + host→
device transfer *inside* the step and is data-bound no matter how fast the
chip is.

``DeviceFeed`` wraps any batch iterable (a gluon ``DataLoader`` base
iterator, a ``DataIter``, a generator of numpy arrays) with a bounded-queue
background thread that runs one-to-two batches ahead of the consumer:

* an optional ``transform`` (e.g. the DataLoader's batchify) runs on the
  feed thread, off the consumer's critical path;
* each item is then **staged**: leaves move to the target device via
  ``jax.device_put`` (or sharded over a mesh via
  ``parallel.shard_batch``) and the worker blocks until the transfer has
  landed, so by the time the consumer sees a batch it is device-resident;
* the queue is bounded (``depth``), so the producer can never run away
  from the consumer and host memory stays flat.

Lifecycle is deterministic: ``close()`` is idempotent, unblocks a producer
stuck on a full queue, joins the thread, and is also invoked by ``__exit__``
and ``__del__``; a worker exception is re-raised in the consumer (not
swallowed on a dead thread).  One ``DeviceFeed`` is one pass over
``source`` — build a fresh feed per epoch (``DataLoader.__iter__`` and
``BaseModule.fit`` do).  The worker thread deliberately holds NO reference
to the ``DeviceFeed`` itself (its target is a module function over a
separate state object): an iterator abandoned mid-epoch stays collectable,
so the ``__del__`` backstop can run and stop the worker instead of leaking
it for the life of the process.

Observability: the worker's stages are ``profiler.span``s (``feed.source``,
``feed.transform``, ``feed.h2d``, ``feed.put_wait``, numbered by batch, with
the thread's CPU time), the consumer's blocking ``get`` is ``feed.wait``, and
``feed.batches`` counts into ``profiler.totals()``, which outlives the feed.
``stats()`` returns this feed's own totals (batches, h2d time, consumer
starvation, peak depth) that the pipeline bench reports, and, as the serving
counters do (serving/stats.py), a ``feed`` profiler Domain carries
``<name>:queue_depth`` / ``<name>:h2d_ms`` / ``<name>:starved_ms`` Counters
while a profiler session runs.
"""
from __future__ import annotations

import queue as _queue
import threading

from .. import profiler
from .. import util as _util

__all__ = ["DeviceFeed", "stage_batch"]

# worker -> consumer sentinels (identity-compared)
_END = object()

_JOIN_TIMEOUT_S = 10.0
# producer re-checks the stop flag at this period while the queue is full
_PUT_POLL_S = 0.05


class _WorkerError:
    __slots__ = ("exc",)

    def __init__(self, exc):
        self.exc = exc


def _resolve_device(ctx):
    """Context (or None) -> concrete jax.Device for staging."""
    if ctx is None:
        from ..context import current_context
        ctx = current_context()
    return ctx.jax_device(), ctx


def stage_batch(item, ctx=None, mesh=None):
    """Place one batch item on device, preserving its structure.

    The ``device_feed.put`` fault point sits at the top: an injected
    transient transfer failure is absorbed by the worker's retry envelope
    (``_stage_with_retry``); a persistent one propagates to the consumer
    like any other worker error.

    Handles the shapes that flow through this framework's input paths:
    ``DataBatch`` (data/label NDArray lists), lists/tuples/dicts of leaves,
    and leaves themselves.  Leaf rule: ``NDArray`` in, ``NDArray`` out
    (re-contexted); numpy / jax array in, committed jax array out.  With a
    ``mesh``, leaves are sharded over the ``dp`` axis via
    ``parallel.shard_batch`` instead of placed whole.

    The call BLOCKS until the transfer has landed (``block_until_ready``),
    so a staged batch handed to the consumer costs no hidden transfer wait
    inside the step.
    """
    import jax

    from ..faults import fault_point
    from ..ndarray import NDArray, _wrap

    fault_point("device_feed.put")
    if mesh is not None:
        from ..parallel import shard_batch

        def put(x):
            out = shard_batch(mesh, x._data if isinstance(x, NDArray) else x)
            return _wrap(out, ctx=ctx) if isinstance(x, NDArray) else out
    else:
        device, ndctx = _resolve_device(ctx)

        def put(x):
            if isinstance(x, NDArray):
                return _wrap(jax.device_put(x._data, device), ctx=ndctx)
            return jax.device_put(x, device)

    def walk(obj):
        from .io import DataBatch
        if isinstance(obj, DataBatch):
            staged = DataBatch(
                data=None if obj.data is None else [walk(d) for d in obj.data],
                label=None if obj.label is None else
                [walk(l) for l in obj.label],
                pad=obj.pad, index=obj.index, bucket_key=obj.bucket_key,
                provide_data=obj.provide_data,
                provide_label=obj.provide_label)
            return staged
        if isinstance(obj, (list, tuple)):
            return type(obj)(walk(o) for o in obj)
        if isinstance(obj, dict):
            return {k: walk(v) for k, v in obj.items()}
        if hasattr(obj, "shape"):
            return put(obj)
        return obj   # scalars / metadata pass through

    staged = walk(item)

    def sync(obj):
        from .io import DataBatch
        if isinstance(obj, DataBatch):
            sync(obj.data)
            sync(obj.label)
        elif isinstance(obj, (list, tuple)):
            for o in obj:
                sync(o)
        elif isinstance(obj, dict):
            for o in obj.values():
                sync(o)
        elif isinstance(obj, NDArray):
            obj.wait_to_read()  # mxflow: sync-ok(staging contract: stage_batch returns only after the transfer lands)
        elif hasattr(obj, "block_until_ready"):
            obj.block_until_ready()  # mxflow: sync-ok(staging contract: stage_batch returns only after the transfer lands)
    sync(staged)
    return staged


class _FeedState:
    """Everything the worker thread touches.  Split from ``DeviceFeed`` so
    the thread's target closes over THIS object only — an abandoned feed
    is then garbage-collectable while its worker still runs, letting
    ``DeviceFeed.__del__`` stop the worker (no thread leak)."""

    def __init__(self, source, ctx, mesh, transform, depth, name, stage):
        self.source = source
        self.ctx = ctx
        self.mesh = mesh
        self.transform = transform
        self.stage = stage
        self.queue = _queue.Queue(maxsize=int(depth))
        self.stop = threading.Event()
        self.lock = threading.Lock()
        # guarded by lock: stats (worker-written, consumer-read)
        self.batches = 0
        self.h2d_ms = 0.0
        self.starved_ms = 0.0
        self.max_depth = 0
        domain = profiler.Domain("feed")
        self.c_depth = domain.new_counter("%s:queue_depth" % name)
        self.c_h2d = domain.new_counter("%s:h2d_ms" % name)
        self.c_starved = domain.new_counter("%s:starved_ms" % name)

    def put(self, item):
        """Bounded put that honors stop; False if stopped while full."""
        while not self.stop.is_set():
            try:
                self.queue.put(item, timeout=_PUT_POLL_S)
                return True
            except _queue.Full:
                continue
        return False


# retry envelope for the staging transfer (docs/ROBUSTNESS.md): a
# transient device_put failure re-stages the same item (device_put is
# idempotent) instead of killing the epoch
_stage_with_retry = _util.retry(attempts=3, backoff=0.002)(stage_batch)


def _feed_worker(state):  # mxflow: hot (device feed staging worker)
    try:
        it = iter(state.source)
        number = 0                   # batch k here is step k of the loop
        while not state.stop.is_set():
            try:
                with profiler.span("feed.source", seq=number, cpu=True):
                    item = next(it)
            except StopIteration:
                state.put(_END)
                return
            if state.transform is not None:
                with profiler.span("feed.transform", seq=number, cpu=True):
                    item = state.transform(item)
            staged = item
            if state.stage:
                with profiler.span("feed.h2d", seq=number, cpu=True) as h2d:
                    staged = _stage_with_retry(item, ctx=state.ctx,
                                               mesh=state.mesh)
            with profiler.span("feed.put_wait", seq=number, cpu=True):
                if not state.put(staged):
                    return
            number += 1
            profiler.count("feed.batches")
            depth = state.queue.qsize()
            h2d_ms = h2d.wall_ns / 1e6 if state.stage else 0.0
            with state.lock:
                state.batches += 1
                state.h2d_ms += h2d_ms
                if depth > state.max_depth:
                    state.max_depth = depth
            if profiler.profiling_active():
                state.c_h2d.set_value(h2d_ms)
                state.c_depth.set_value(depth)
    except BaseException as exc:  # propagate to the consumer, not stderr
        state.put(_WorkerError(exc))


class DeviceFeed:
    """Bounded background thread that keeps staged batches ahead of compute.

    Parameters
    ----------
    source : iterable
        Batch source; iterated exactly once, on the feed thread.
    ctx : Context, optional
        Target device context (default: the current context).
    mesh : jax.sharding.Mesh, optional
        When given, leaves are dp-sharded via ``parallel.shard_batch``
        instead of placed on one device (multi-chip feed).
    depth : int
        Queue capacity — how many staged batches the feed runs ahead
        (the reference prefetcher uses 1; 2 absorbs decode jitter).
    transform : callable, optional
        Applied to each raw item on the feed thread BEFORE staging
        (DataLoader routes batchify here, off the consumer thread).
    name : str
        Counter prefix; the defaults produce the documented
        ``feed:queue_depth`` / ``feed:h2d_ms`` / ``feed:starved_ms``.
    stage : bool
        ``False`` turns device placement off — transform/prefetch only
        (``PrefetchingIter`` without a ctx uses this to reuse the worker/
        queue/lifecycle machinery while handing batches through untouched).
    """

    def __init__(self, source, ctx=None, mesh=None, depth=2, transform=None,
                 name="feed", stage=True):
        if depth < 1:
            raise ValueError("DeviceFeed depth must be >= 1, got %r" % depth)
        if stage and ctx is None and mesh is None:
            # snapshot the CALLER's context scope here: a `with ctx:`
            # block belongs to the thread that entered it, so resolving on
            # the worker thread would silently ignore `with mx.tpu(0):`
            from ..context import current_context
            ctx = current_context()
        self._state = _FeedState(source, ctx, mesh, transform, depth, name,
                                 stage)
        self._lock = self._state.lock
        # guarded by _lock: consumer-side lifecycle
        self._thread = None
        self._closed = False
        self._exhausted = False
        self._error = None

    def _ensure_started(self):
        with self._lock:
            if self._closed:
                raise RuntimeError("DeviceFeed is closed")
            if self._thread is None:
                self._thread = threading.Thread(
                    target=_feed_worker, args=(self._state,),
                    name="DeviceFeed", daemon=True)
                self._thread.start()

    # -- consumer side --------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        self._ensure_started()
        with self._lock:
            if self._exhausted:
                if self._error is not None:
                    raise self._error
                raise StopIteration
        state = self._state
        try:
            item = state.queue.get_nowait()
            starved_ms = 0.0
        except _queue.Empty:
            with profiler.span("feed.wait") as wait:
                item = state.queue.get()
            starved_ms = wait.wall_ns / 1e6
        if starved_ms:
            with self._lock:
                state.starved_ms += starved_ms
            if profiler.profiling_active():
                state.c_starved.set_value(starved_ms)
        if profiler.profiling_active():
            state.c_depth.set_value(state.queue.qsize())
        if item is _END:
            with self._lock:
                self._exhausted = True
            self._join()
            raise StopIteration
        if isinstance(item, _WorkerError):
            with self._lock:
                self._exhausted = True
                self._error = item.exc
            self._join()
            raise item.exc
        return item

    def next(self):
        return self.__next__()

    # -- lifecycle ------------------------------------------------------
    def _join(self):
        with self._lock:
            thread = self._thread
        if thread is not None and thread is not threading.current_thread():
            thread.join(_JOIN_TIMEOUT_S)

    def close(self):
        """Stop the feed deterministically.  Idempotent and safe mid-epoch:
        unblocks a producer waiting on the full queue, joins the thread,
        and drops any staged-but-unconsumed batches."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._exhausted = True
        state = self._state
        state.stop.set()
        # drain so a put()-blocked worker wakes even with _PUT_POLL_S jitter
        while True:
            try:
                state.queue.get_nowait()
            except _queue.Empty:
                break
        self._join()
        # a consumer blocked in get() while we closed must not hang forever;
        # if the worker's final put landed after the drain the queue may be
        # full again — that item wakes the getter instead, so never block here
        try:
            state.queue.put_nowait(_END)
        except _queue.Full:
            pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        # reachable even while the worker runs: the thread references only
        # _FeedState, so dropping the last DeviceFeed ref triggers this
        try:
            self.close()
        except Exception:
            pass  # interpreter teardown: modules may already be gone

    # -- observability --------------------------------------------------
    def stats(self):
        """Always-on totals: ``{"batches", "h2d_ms", "starved_ms",
        "max_queue_depth", "avg_h2d_ms"}`` (the profiler Counters carry the
        same signals as trace events when profiling is active)."""
        state = self._state
        with self._lock:
            batches = state.batches
            return {"batches": batches,
                    "h2d_ms": state.h2d_ms,
                    "starved_ms": state.starved_ms,
                    "max_queue_depth": state.max_depth,
                    "avg_h2d_ms": state.h2d_ms / batches if batches else 0.0}
