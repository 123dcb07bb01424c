"""Seeded adversarial-schedule stress harness for the threaded runtime.

The static half of the concurrency story (`concurrency_lint`, the ``concur``
mxlint pass) proves lock *discipline*; this module attacks lock
*sufficiency*: it runs the real threaded subsystems — the serving
admission/coalescing path, registry load/unload churn, the CachedOp
compile-cache counters, and ``engine.bulk`` scoping — under seeded
adversarial preemption and asserts runtime invariants.

How preemption is injected
--------------------------
``chaos(sched)`` monkeypatches ``threading.Lock`` and ``threading.RLock``
so every lock *created inside the scope* is wrapped: each ``acquire()``
(and each release) first consults a seeded RNG and, with probability
``p_preempt``, sleeps 0..``max_sleep_ms`` — stretching critical sections
and shifting thread interleavings at exactly the points where races
surface.  ``threading.Condition`` and ``threading.Event`` pick the wrapped
primitives up automatically (their internals call the patched factories),
so the batcher's condition variable and every Request's completion event
are perturbed without touching library code.  Seeds diversify the
perturbation pattern; runs are adversarial and reproducible in
distribution, not bit-identical replays (the OS still schedules).

Invariants asserted (per seed)
------------------------------
* **no lost requests** — every submitted request reaches exactly one
  terminal status, and the per-model counters conserve:
  ``requests == ok + timeouts + errors``, shed/invalid tallies match the
  client-observed rejections.
* **no torn results** — an OK result carries outputs that match the
  eager reference for *that client's* input (catches batch-row mixups);
  a TIMEOUT result never carries outputs (the Request completion race
  regression).
* **monotonic counters** — a monitor thread snapshots stats during the
  storm; no counter ever decreases, and the compile cache records ZERO
  new misses after warmup (the zero-steady-state-recompile serving gate,
  now asserted under contention).
* **no deadlock** — every worker/client joins within a timeout.
* **registry churn safety** — concurrent load/unload/duplicate-load only
  ever fail with MXNetError, and the registry ends in the expected state.
* **bulk scoping** — ``engine.bulk`` scopes stay per-thread.
* **feed pipeline** — the ``DeviceFeed`` input stage conserves batches in
  order (no torn rows), shuts down cleanly mid-epoch, and propagates
  source errors (see ``feed_pipeline``).
* **fault storm** (``faults``) — a serving storm under a seeded
  ``mxnet_tpu.faults`` plan: transient predict faults are absorbed by the
  retry envelope, request counts conserve INCLUDING ``UNAVAILABLE``
  outcomes, nothing raises unhandled, and the circuit breaker demonstrably
  opens after K consecutive failures and re-closes via half-open probing
  once the faults clear (see ``fault_storm``).
* **crash sweep** (``crash``) — kills a checkpoint save at EVERY injected
  fault point (each write chunk, pre-replace, post-replace, manifest
  commit; seed-chosen kinds mix plain crash and byte-level torn-write).
  Invariant: after every kill, ``model.latest_complete_checkpoint`` still
  returns a checkpoint whose files load bit-exact (see ``crash_sweep``;
  the fit-level twin — resume to the uninterrupted run's exact params —
  lives in tests/test_faults.py).
* **decode streams** (``decode``) — continuous-batching token streams
  through the DecodeEngine under chaos: stream-count conservation, OK
  streams bitwise-equal to their own greedy reference (partial streams a
  strict prefix — no torn or cross-contaminated token streams), KV block
  accounting whole after the drain (allocated == freed), zero
  steady-state recompiles, no deadlock (see ``decode_storm``).
* **elastic fleet** (``fleet``) — a replica is killed (SimulatedCrash at
  the ``fleet.replica`` fault point) under storm load through the
  FleetRouter: zero dropped requests (fleet conservation across
  failovers), no torn results, bounded tail latency, the background
  rebalance restores the replication factor (re-warm before cutover), and
  the router re-converges HEALTHY (see ``fleet_storm``).
* **stateful decode fleet** (``decode_fleet``) — a multi-tenant token-
  stream storm through ``FleetRouter.submit_stream`` while one replica is
  drained (fenced KV handoff to a survivor) AND a different one is
  killed: zero dropped streams (router decode conservation), OK and
  handed-off streams bitwise-equal to the greedy reference, partial
  streams strict prefixes (no torn or cross-contaminated handoffs), KV
  pools whole on every survivor, per-tenant admission conservation with
  no starvation, zero steady-state recompiles on engines that lived the
  whole seed (see ``decode_fleet_storm``).
* **shared-prefix decode storm** (``decode_prefix``) — greedy and seeded
  sampled streams over prompts sharing a common prefix hit the copy-on-
  write prefix cache on chunked + speculative engines while one replica
  drains mid-run: OK streams bitwise-equal their greedy or sampled
  reference ACROSS the handoff (migrated streams carry refcounted shared
  pages + sampler state), KV pools drain whole with zero leaks, the
  prefix-hit / CoW-fork / speculation counters demonstrably advance, and
  nothing recompiles (see ``decode_prefix_storm``).
* **sharded decode storm** (``sharded_decode``) — greedy and seeded
  sampled streams over tensor-parallel mesh-backed engines
  (``ShardedDecodeModel(tp=2)``, head-sharded K/V pools, gather-free
  compute-parallel kernels) while one replica drains mid-run: the
  sharded→sharded handoff keeps OK token streams identical to the
  SINGLE-DEVICE reference (logits are allclose under the Megatron
  psums; the token claim is exact), every engine's pool
  drains whole on every shard (host accounting + tp_degree signals),
  router/engine conservation holds, and the warmed shard_map signatures
  never recompile (see ``sharded_decode_storm``).
* **disaggregated tier storm** (``disagg``) — greedy and seeded sampled
  streams through a ``DisaggRouter`` (prefill-only tier handing off at
  first token to a decode tier) while one PREFILL replica is killed and
  one DECODE replica is drained mid-run: cross-tier conservation settles
  on the prefill router's single ledger, OK streams stay bitwise-equal
  to the colocated reference across the handoff, killed streams leave
  strict prefixes that RE-ADMIT and continue the greedy path bitwise,
  KV pools drain whole on both tiers, and surviving engines never
  recompile (see ``disagg_storm``).
* **memory-pressure storm** (``mem``) — concurrent sequence lifecycles
  drive a tiny paged KV pool to near-exhaustion (admission sheds, LRU
  eviction, prefix re-admission, copy-on-write forks): the pool's
  attachment ledger conserves (``allocated_total == freed_total``), the
  byte accountant (``mxnet_tpu.memory_accounting`` — the runtime twin of
  the mem lint pass) mirrors it exactly in bytes, its region peak stays
  under the declared admission worst case, and ``peak_used`` never
  exceeds physical capacity (see ``mem_storm``).
* **rolling-deployment storm** (``deploy``) — each seed publishes the
  next checkpoint epoch with DIFFERENT weights and either rolls it
  across the live fleet under client streams (sometimes racing a
  replica kill) or crashes the DeploymentController at a seeded
  ``deploy.*`` fault point: a killed controller always leaves the
  fleet HEALTHY on the OLD generation, every stream finishes against
  exactly one weight generation (bitwise vs that flavor's reference),
  the ledger conserves, KV pools drain whole, and post-swap probes
  never recompile (see ``deploy_storm``).

``tools/mxstress.py`` is the CLI front end; ``tests/test_concurrency.py``
wires the smoke configuration (25 fixed seeds, bounded sizes) into tier-1
and ``tests/test_faults.py``/``tests/test_fleet.py``/
``tests/test_decode_fleet.py``/``tests/test_decode_prefix.py``/
``tests/test_sharded_decode.py``/``tests/test_disagg.py``/
``tests/test_deploy.py`` gate the fault-driven scenarios (``faults``,
``crash``, ``fleet``, ``decode_fleet``, ``decode_prefix``,
``sharded_decode``, ``disagg``, ``deploy``) on the smaller
``FAULT_SMOKE_SEEDS`` set.
"""
from __future__ import annotations

import contextlib
import random
import threading
import time

__all__ = ["ChaosScheduler", "chaos", "stress", "SMOKE_SEEDS", "SCENARIOS",
           "FAULT_SMOKE_SEEDS"]

# real primitives captured at import time: the wrappers and the scheduler
# must keep working while threading.Lock/RLock point at the factories
_REAL_LOCK = threading.Lock
_REAL_RLOCK = threading.RLock

SMOKE_SEEDS = tuple(range(25))
# the fault scenarios run real save/restore + breaker recovery cycles per
# seed, so their tier-1 gate (tests/test_faults.py) uses a smaller fixed
# set to stay inside its ~5 s smoke budget
FAULT_SMOKE_SEEDS = tuple(range(5))
_JOIN_TIMEOUT_S = 20.0


class ChaosScheduler(object):
    """Seeded preemption source shared by every wrapped lock."""

    def __init__(self, seed=0, p_preempt=0.25, max_sleep_ms=0.5):
        self._rng = random.Random(seed)
        self.p_preempt = float(p_preempt)
        self.max_sleep_s = float(max_sleep_ms) / 1e3
        self.enabled = True
        self.preemptions = 0

    def reseed(self, seed):
        self._rng.seed(seed)

    def maybe_preempt(self):
        # No lock is held here, by design: the collector can run a
        # finalizer on this thread between two bytecodes, and a finalizer
        # that takes a wrapped lock (DeviceFeed.__del__ -> close())
        # re-enters this method.  Under a lock of the scheduler's own that
        # re-entry deadlocked the thread against itself and, behind it,
        # every thread of the run.  A draw and a reseed are each atomic
        # under the interpreter lock; ``preemptions`` is a statistic (the
        # gates ask that it is well above zero), so an increment lost
        # between two threads only undercounts.
        if not self.enabled:
            return
        if self._rng.random() < self.p_preempt:
            dur = self._rng.random() * self.max_sleep_s
            self.preemptions += 1
            time.sleep(dur)   # dur==0 still yields the GIL


class _ChaosLock(object):
    """``threading.Lock`` wrapper that preempts at acquire/release edges."""

    _factory = staticmethod(_REAL_LOCK)

    def __init__(self, sched):
        self._sched = sched
        self._inner = self._factory()

    def acquire(self, blocking=True, timeout=-1):
        self._sched.maybe_preempt()
        return self._inner.acquire(blocking, timeout)

    def release(self):
        self._inner.release()
        self._sched.maybe_preempt()

    def locked(self):
        return self._inner.locked()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        # route through release() so `with lock:` — the dominant pattern in
        # the code under test — gets the release-edge preemption too
        self.release()

    def __getattr__(self, name):
        # Condition binds _release_save/_acquire_restore/_is_owned straight
        # off the lock when present (RLock); delegate honestly so a plain
        # Lock still raises AttributeError and Condition uses its fallbacks
        return getattr(self._inner, name)


class _ChaosRLock(_ChaosLock):
    _factory = staticmethod(_REAL_RLOCK)


@contextlib.contextmanager
def chaos(sched):
    """Patch the lock factories so locks created inside are chaos-wrapped.

    Objects built in the scope keep their wrapped locks after exit; set
    ``sched.enabled = False`` to stop perturbing them (each acquire then
    costs one attribute check)."""
    real = (threading.Lock, threading.RLock)
    threading.Lock = lambda: _ChaosLock(sched)
    threading.RLock = lambda: _ChaosRLock(sched)
    try:
        yield sched
    finally:
        threading.Lock, threading.RLock = real


# ---------------------------------------------------------------------------
# fixture: one tiny servable model + eager references
# ---------------------------------------------------------------------------

_FEAT = 6
_CLASSES = 3


def _build_fixture(n_clients, max_queue):
    """-> (server, model_name, net, client_inputs, client_expected)."""
    import numpy as np
    from .. import gluon, init
    from ..gluon import nn
    from .. import ndarray as nd
    from .. import serving

    class _Net(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.out = nn.Dense(_CLASSES, in_units=_FEAT)

        def hybrid_forward(self, F, x):
            return self.out(x)

    net = _Net()
    net.initialize(init.Xavier())
    server = serving.ModelServer()
    # tight breaker backoff so the faults scenario's open -> half-open ->
    # closed recovery cycle fits the smoke budget
    server.load_model("stable", net, input_shapes=[(_FEAT,)], max_batch=4,
                      max_queue=max_queue, linger_ms=1.0, warmup=True,
                      breaker_threshold=4, breaker_backoff_ms=15.0)
    inputs, expected = [], []
    for i in range(n_clients):
        x = np.full((_FEAT,), 0.25 * (i + 1), np.float32)
        inputs.append(x)
        expected.append(net(nd.array(x[None])).asnumpy()[0])
    return server, "stable", net, inputs, expected


def _spawn(fns):
    """Run thunks on threads; -> (violations from joins, exceptions list)."""
    errors = []
    threads = []

    def runner(fn):
        try:
            fn()
        except Exception as exc:   # an invariant harness must not die silently
            errors.append("unexpected exception: %r" % (exc,))

    for fn in fns:
        t = threading.Thread(target=runner, args=(fn,), daemon=True)
        threads.append(t)
        t.start()
    violations = []
    for t in threads:
        t.join(_JOIN_TIMEOUT_S)
        if t.is_alive():
            violations.append("deadlock: thread %s did not join within %ss"
                              % (t.name, _JOIN_TIMEOUT_S))
    violations.extend(errors)
    return violations


# ---------------------------------------------------------------------------
# shared invariant: request-count conservation (serving + fault storms)
# ---------------------------------------------------------------------------

def _settle_and_check(server, name, before, tally, label):
    """Settle, then assert the conservation identity and per-status match.

    A request's completion event fires BEFORE the worker's stats bump
    (complete() then on_result()), and the chaos locks stretch exactly that
    edge — so the counters get a bounded window to conserve before an
    imbalance is treated as a lost request.  The identity includes
    UNAVAILABLE on both sides: admitted requests drained at teardown land
    in ``unavailable``; fast rejections (breaker open / shutting down) land
    in ``unavailable_rejected`` and — like shed — never enter ``requests``.
    Returns (violations, after_snapshot)."""
    violations = []
    keys = ("requests", "ok", "timeouts", "shed", "invalid", "errors",
            "unavailable", "unavailable_rejected")
    settle_until = time.monotonic() + 5.0
    while True:
        after = server.stats()["models"][name]
        d = {k: after[k] - before[k] for k in keys}
        terminal_sum = (d["ok"] + d["timeouts"] + d["errors"]
                        + d["unavailable"])
        if d["requests"] == terminal_sum or time.monotonic() >= settle_until:
            break
        time.sleep(0.005)
    if d["requests"] != tally["admitted"]:
        violations.append("%s: admission mismatch: server %d vs clients %d"
                          % (label, d["requests"], tally["admitted"]))
    if d["requests"] != terminal_sum:
        violations.append(
            "%s: lost requests: admitted %d but only %d reached a terminal "
            "counter" % (label, d["requests"], terminal_sum))
    for client_key, server_key in (("OK", "ok"), ("TIMEOUT", "timeouts"),
                                   ("OVERLOADED", "shed"),
                                   ("INVALID_INPUT", "invalid"),
                                   ("ERROR", "errors")):
        if d[server_key] != tally[client_key]:
            violations.append(
                "%s: %s count mismatch: server %d vs clients %d"
                % (label, server_key, d[server_key], tally[client_key]))
    # clients cannot distinguish drained-vs-rejected UNAVAILABLE, so the
    # client tally must equal the two server buckets combined
    if d["unavailable"] + d["unavailable_rejected"] != tally["UNAVAILABLE"]:
        violations.append(
            "%s: unavailable count mismatch: server %d+%d vs clients %d"
            % (label, d["unavailable"], d["unavailable_rejected"],
               tally["UNAVAILABLE"]))
    return violations, after


# ---------------------------------------------------------------------------
# scenario 1: serving storm
# ---------------------------------------------------------------------------

def serving_storm(server, name, inputs, expected, seed, per_client=3):
    """Concurrent mixed-deadline predicts; full invariant suite."""
    import numpy as np
    from ..serving import server as srv

    terminal = {srv.OK, srv.TIMEOUT, srv.OVERLOADED, srv.INVALID_INPUT,
                srv.ERROR, srv.UNAVAILABLE}
    rng = random.Random(seed ^ 0xC0FFEE)
    n_clients = len(inputs)
    before = server.stats()["models"][name]
    results = [[] for _ in range(n_clients)]
    plans = []
    for c in range(n_clients):
        plan = []
        for r in range(per_client):
            roll = rng.random()
            if roll < 0.2:
                plan.append(("tiny", rng.uniform(0.2, 2.0)))   # likely TIMEOUT
            elif roll < 0.3:
                plan.append(("invalid", None))                 # wrong shape
            elif roll < 0.5:
                plan.append(("none", None))                    # no deadline
            else:
                plan.append(("ok", rng.uniform(150.0, 400.0)))
        plans.append(plan)

    def client(c):
        for kind, tmo in plans[c]:
            if kind == "invalid":
                data = np.zeros((_FEAT + 1,), np.float32)
            else:
                data = inputs[c]
            res = server.predict(name, data, timeout_ms=tmo)
            results[c].append(res)

    # monitor: counters must never go backwards mid-storm
    stop = threading.Event()
    monitor_violations = []

    def monitor():
        keys = ("requests", "ok", "timeouts", "shed", "invalid", "errors",
                "batches")
        prev = None
        while not stop.is_set():
            snap = server.stats()["models"][name]
            cache = snap["cache"]
            cur = tuple(snap[k] for k in keys) + (
                cache["hits"] + cache["misses"],)
            if prev is not None:
                for k, p, c in zip(keys + ("cache_total",), prev, cur):
                    if c < p:
                        monitor_violations.append(
                            "counter %r went backwards: %s -> %s" % (k, p, c))
            prev = cur
            time.sleep(0.002)

    mon = threading.Thread(target=monitor, daemon=True)
    mon.start()
    violations = _spawn([lambda c=c: client(c) for c in range(n_clients)])
    stop.set()
    mon.join(_JOIN_TIMEOUT_S)
    violations.extend(monitor_violations)

    tally = {"admitted": 0, "OK": 0, "TIMEOUT": 0, "OVERLOADED": 0,
             "INVALID_INPUT": 0, "ERROR": 0, "UNAVAILABLE": 0}
    for c in range(n_clients):
        if len(results[c]) != len(plans[c]):
            violations.append("client %d lost results: %d of %d"
                              % (c, len(results[c]), len(plans[c])))
        for (kind, _), res in zip(plans[c], results[c]):
            if res is None or res.status not in terminal:
                violations.append("client %d got non-terminal result %r"
                                  % (c, res))
                continue
            tally[res.status] += 1
            if res.status not in (srv.OVERLOADED, srv.INVALID_INPUT,
                                  srv.UNAVAILABLE):
                tally["admitted"] += 1
            if res.status == srv.OK:
                if res.outputs is None:
                    violations.append("torn result: OK with outputs=None")
                elif not np.allclose(res.outputs[0], expected[c],
                                     rtol=1e-4, atol=1e-5):
                    violations.append(
                        "row mixup: client %d OK output does not match its "
                        "reference" % c)
            elif res.status == srv.TIMEOUT and res.outputs is not None:
                violations.append(
                    "torn result: TIMEOUT carrying outputs (completion race)")
            if kind == "invalid" and res.status != srv.INVALID_INPUT:
                violations.append("wrong-shape request got %s" % res.status)

    conserve, after = _settle_and_check(server, name, before, tally,
                                        "serving storm")
    violations.extend(conserve)
    cache_before, cache_after = before["cache"], after["cache"]
    if cache_after["recompiles"] != cache_before["recompiles"]:
        violations.append(
            "steady-state recompile under contention: %d -> %d"
            % (cache_before["recompiles"], cache_after["recompiles"]))
    return violations


# ---------------------------------------------------------------------------
# scenario 2: registry load/unload churn
# ---------------------------------------------------------------------------

def registry_churn(server, name, net, inputs, seed, n_churners=2, rounds=2):
    from ..base import MXNetError
    from ..serving import server as srv

    terminal = {srv.OK, srv.TIMEOUT, srv.OVERLOADED, srv.INVALID_INPUT,
                srv.ERROR, srv.UNAVAILABLE}
    violations = []
    dup_wins = []

    def churner(tid):
        for r in range(rounds):
            cname = "churn-%d-%d" % (tid, r)
            server.load_model(cname, net, input_shapes=[(_FEAT,)],
                              max_batch=2, warmup=False)
            server.unload(cname)

    def dup_loader():
        # both race to load the same name: exactly one may win
        try:
            server.load_model("dup", net, input_shapes=[(_FEAT,)],
                              max_batch=2, warmup=False)
            dup_wins.append(1)
        except MXNetError:
            pass

    def predictor():
        for _ in range(3):
            res = server.predict(name, inputs[0], timeout_ms=300.0)
            if res.status not in terminal:
                violations.append("predict during churn: non-terminal %r"
                                  % (res,))

    fns = [lambda t=t: churner(t) for t in range(n_churners)]
    fns += [dup_loader, dup_loader, predictor]
    violations.extend(_spawn(fns))
    if len(dup_wins) != 1:
        violations.append("duplicate load: %d winners (want exactly 1)"
                          % len(dup_wins))
    # clean up unconditionally so one violated seed cannot poison the rest
    if "dup" in server.models():
        server.unload("dup")
    models = server.models()
    if models != [name]:
        violations.append("registry left dirty after churn: %s" % models)
    return violations


# ---------------------------------------------------------------------------
# scenario 3: CachedOp cache-stats hammer
# ---------------------------------------------------------------------------

def cache_stats_hammer(server, name, seed, n_threads=2, execs_per_thread=6):
    import numpy as np

    model = server._registry.get(name)
    before = model.cache_stats()
    calls = [0] * n_threads

    def hammer(tid):
        rng = random.Random(seed * 31 + tid)
        for _ in range(execs_per_thread):
            rung = rng.choice([1, 2, 4])          # all warmed signatures
            arrays = [np.zeros((rung, _FEAT), np.float32)]
            outs = model.execute(arrays)
            calls[tid] += 1
            if outs[0].shape != (rung, _CLASSES):
                raise AssertionError("bad output shape %s"
                                     % (outs[0].shape,))

    def reader():
        for _ in range(40):
            s = model.cache_stats()
            hits = sum(r["hits"] for r in s["signatures"].values())
            misses = sum(r["misses"] for r in s["signatures"].values())
            if hits != s["hits"] or misses != s["misses"]:
                raise AssertionError("inconsistent cache_stats snapshot")
            time.sleep(0.001)

    violations = _spawn([lambda t=t: hammer(t) for t in range(n_threads)]
                        + [reader])
    after = model.cache_stats()
    if after["misses"] != before["misses"]:
        violations.append("cache hammer caused recompiles: %d -> %d"
                          % (before["misses"], after["misses"]))
    expected_hits = before["hits"] + sum(calls)
    if after["hits"] != expected_hits:
        violations.append(
            "lost cache-stat updates: %d dispatches but hits %d -> %d"
            % (sum(calls), before["hits"], after["hits"]))
    return violations


# ---------------------------------------------------------------------------
# scenario 4: engine.bulk thread scoping
# ---------------------------------------------------------------------------

def bulk_scopes(seed, n_threads=3):
    from .. import engine

    violations = []

    def scoped(tid):
        want = 100 + tid
        with engine.bulk(want):
            time.sleep(0.001 * (seed % 3))
            if engine.bulk_size() != want:
                violations.append(
                    "bulk scope stomped: thread %d saw %d (want %d)"
                    % (tid, engine.bulk_size(), want))
            with engine.bulk(want * 10):
                if engine.bulk_size() != want * 10:
                    violations.append("nested bulk scope broken in %d" % tid)
            if engine.bulk_size() != want:
                violations.append("bulk scope not restored in thread %d"
                                  % tid)
        if engine.bulk_size() != 15:
            violations.append("thread %d default bulk size polluted: %d"
                              % (tid, engine.bulk_size()))

    violations.extend(_spawn([lambda t=t: scoped(t)
                              for t in range(n_threads)]))
    return violations


# ---------------------------------------------------------------------------
# scenario 5: DeviceFeed pipeline (the async input feed)
# ---------------------------------------------------------------------------

def feed_pipeline(seed, n_batches=16, depth=2):
    """DeviceFeed under chaos: conservation, order, shutdown, errors.

    Invariants:
    * **batch conservation + order** — a full consume sees exactly
      ``n_batches`` batches, in source order, each row un-torn (every
      element of batch i equals i — a mixed/partial buffer fails);
    * **clean shutdown mid-epoch** — ``close()`` after a partial consume
      returns with the worker joined, repeated close is a no-op, and a
      closed feed refuses iteration;
    * **error propagation** — a source exception surfaces in the consumer
      after the good prefix, and the worker joins;
    * **no deadlock** — every consumer thread joins in time (stalls at the
      bounded queue's put/get edges are where the chaos locks bite).
    """
    import numpy as np
    from ..context import cpu
    from ..io.device_feed import DeviceFeed

    violations = []
    rng = random.Random(seed ^ 0xFEED)

    def source(n, fail_at=None):
        for i in range(n):
            if fail_at is not None and i == fail_at:
                raise RuntimeError("planted decode failure")
            yield np.full((3,), i, np.float32)

    # full-epoch consume on a separate thread (deadlock-checked by _spawn)
    feed = DeviceFeed(source(n_batches), ctx=cpu(0), depth=depth,
                      name="stress-feed")
    got = []

    def consume():
        for batch in feed:
            got.append(np.asarray(batch))
    violations.extend(_spawn([consume]))
    if len(got) != n_batches:
        violations.append("lost batches: %d of %d" % (len(got), n_batches))
    for i, b in enumerate(got):
        if not np.all(b == i):
            violations.append(
                "torn/reordered batch at %d: %s" % (i, b.tolist()))
    stats = feed.stats()
    if stats["batches"] != len(got):
        violations.append("feed stats disagree: staged %d, consumed %d"
                          % (stats["batches"], len(got)))

    # mid-epoch shutdown at a seed-dependent point (consumed via _spawn so
    # a deadlocked feed is REPORTED as a violation, not hung on — the
    # whole point of the scenario's no-deadlock invariant)
    feed2 = DeviceFeed(source(n_batches), ctx=cpu(0), depth=1,
                       name="stress-feed")
    stop_after = rng.randrange(1, max(2, n_batches // 2))
    it = iter(feed2)

    def partial_consume():
        for _ in range(stop_after):
            next(it)
    violations.extend(_spawn([partial_consume]))
    feed2.close()
    feed2.close()    # idempotent
    if feed2._thread is not None and feed2._thread.is_alive():
        violations.append("close() left the feed worker running")
    try:
        next(it)
        violations.append("closed feed kept yielding")
    except (StopIteration, RuntimeError):
        pass

    # worker-error propagation after a good prefix
    fail_at = rng.randrange(1, n_batches)
    feed3 = DeviceFeed(source(n_batches, fail_at=fail_at), ctx=cpu(0),
                       depth=depth, name="stress-feed")
    seen = [0]

    def consume_until_error():
        try:
            for _ in feed3:
                seen[0] += 1
            violations.append("source failure swallowed by the feed")
        except RuntimeError:
            if seen[0] != fail_at:
                violations.append(
                    "error surfaced after %d batches (want %d)"
                    % (seen[0], fail_at))
    violations.extend(_spawn([consume_until_error]))
    if feed3._thread is not None:
        feed3._thread.join(_JOIN_TIMEOUT_S)
        if feed3._thread.is_alive():
            violations.append("worker did not join after error")
    return violations


# ---------------------------------------------------------------------------
# scenario 6: serving storm under a seeded fault plan (+ breaker cycle)
# ---------------------------------------------------------------------------

def fault_storm(server, name, inputs, expected, seed, per_client=3):
    """Serving under injected predict faults (the ``faults`` scenario).

    Phase 1 — storm under a seeded transient-fault plan: the retry
    envelope absorbs most faults (OK), a burst that outlasts the budget
    fails its batch (ERROR); invariants: every request reaches a terminal
    status, nothing raises unhandled, and the counters conserve INCLUDING
    ``UNAVAILABLE``: ``requests == ok + timeouts + errors + unavailable``
    with every per-status server delta matching the client tally.

    Phase 2 — deterministic breaker cycle under a persistent-failure
    plan: exactly K consecutive failures must OPEN the breaker (fast
    UNAVAILABLE, no execution), and once the faults clear, the half-open
    probe must re-CLOSE it and traffic returns to OK."""
    import numpy as np
    from .. import faults
    from ..serving import server as srv

    terminal = {srv.OK, srv.TIMEOUT, srv.OVERLOADED, srv.INVALID_INPUT,
                srv.ERROR, srv.UNAVAILABLE}
    violations = []
    n_clients = len(inputs)
    before = server.stats()["models"][name]

    # -- phase 1: transient-fault storm ---------------------------------
    plan = faults.FaultPlan(seed ^ 0xFA17)
    plan.add("serving.predict", kind="transient", p=0.3,
             times=2 * n_clients * per_client)
    results = [[] for _ in range(n_clients)]

    def client(c):
        for _ in range(per_client):
            res = server.predict(name, inputs[c], timeout_ms=2000.0)
            results[c].append(res)

    with faults.plan(plan):
        violations.extend(_spawn([lambda c=c: client(c)
                                  for c in range(n_clients)]))

    tally = {"admitted": 0, "OK": 0, "TIMEOUT": 0, "OVERLOADED": 0,
             "INVALID_INPUT": 0, "ERROR": 0, "UNAVAILABLE": 0}
    for c in range(n_clients):
        if len(results[c]) != per_client:
            violations.append("fault storm: client %d lost results: %d of %d"
                              % (c, len(results[c]), per_client))
        for res in results[c]:
            if res is None or res.status not in terminal:
                violations.append("fault storm: non-terminal result %r"
                                  % (res,))
                continue
            tally[res.status] += 1
            if res.status not in (srv.OVERLOADED, srv.INVALID_INPUT,
                                  srv.UNAVAILABLE):
                tally["admitted"] += 1
            if res.status == srv.OK and not np.allclose(
                    res.outputs[0], expected[c], rtol=1e-4, atol=1e-5):
                violations.append("fault storm: row mixup for client %d" % c)

    conserve, _ = _settle_and_check(server, name, before, tally,
                                    "fault storm")
    violations.extend(conserve)

    # -- phase 2: breaker opens, then recovers --------------------------
    snap = server.stats()["models"][name]["breaker"]
    threshold = snap["failure_threshold"]
    opens_before = server.stats()["models"][name]["breaker_opens"]
    # drain any residual failure streak from phase 1 so the count is exact
    res = server.predict(name, inputs[0], timeout_ms=2000.0)
    if res.status != srv.OK:
        violations.append("breaker phase: warm predict not OK: %r" % (res,))
    persistent = faults.FaultPlan(seed).add("serving.predict", kind="fatal")
    with faults.plan(persistent):
        statuses = [server.predict(name, inputs[0], timeout_ms=2000.0).status
                    for _ in range(threshold + 2)]
        if statuses[:threshold] != [srv.ERROR] * threshold:
            violations.append("breaker phase: first %d statuses %s (want "
                              "all ERROR)" % (threshold, statuses[:threshold]))
        if srv.UNAVAILABLE not in statuses[threshold:]:
            violations.append("breaker did not open: tail statuses %s"
                              % statuses[threshold:])
        after_open = server.stats()["models"][name]
        if after_open["breaker_opens"] <= opens_before:
            violations.append("breaker_opens counter did not advance")
        if after_open["health"] != "UNAVAILABLE":
            violations.append("open breaker reports health %r"
                              % after_open["health"])
    # faults cleared: wait out the backoff, then the half-open probe must
    # succeed and re-close the breaker
    deadline = time.monotonic() + 5.0
    recovered = False
    while time.monotonic() < deadline:
        res = server.predict(name, inputs[0], timeout_ms=2000.0)
        if res.status == srv.OK:
            recovered = True
            break
        time.sleep(0.005)
    if not recovered:
        violations.append("breaker never recovered after faults cleared")
    final = server.stats()["models"][name]
    if final["breaker"]["state"] != "closed":
        violations.append("breaker state %r after recovery (want closed)"
                          % final["breaker"]["state"])
    if final["health"] != "HEALTHY":
        violations.append("health %r after recovery (want HEALTHY)"
                          % final["health"])
    return violations


# ---------------------------------------------------------------------------
# scenario 7: checkpoint crash sweep
# ---------------------------------------------------------------------------

def crash_sweep(seed):
    """Kill a checkpoint save at every fault point (the ``crash`` scenario).

    Enumerate every ``checkpoint.*`` fault point one save passes (per-chunk
    writes, pre-replace, post-replace — for the symbol, params, and
    manifest files), then for each point k run — against a FRESH prefix
    holding only a committed epoch-1 checkpoint — a save of epoch 2 killed
    exactly there (kind alternating crash / torn-write-truncate by seed).
    The invariant is exact, not just "something restores": epoch 2 may be
    the latest COMPLETE checkpoint only when the kill fired after the
    manifest's own ``os.replace`` (the commit point); at every earlier kill
    the restore must fall back to epoch 1.  Either way the winning epoch's
    params must load bit-exact.  Finally a clean save must win."""
    import os
    import shutil
    import tempfile

    import numpy as np
    from .. import faults
    from .. import model as model_mod
    from .. import ndarray as nd
    from .. import symbol as sym_mod

    violations = []
    rng = random.Random(seed ^ 0xC4A5)
    tmpdir = tempfile.mkdtemp(prefix="mxstress-crash-")

    def params_for(epoch):
        base = np.arange(8, dtype=np.float32).reshape(2, 4)
        return {"w": nd.array(base + epoch), "b": nd.array(
            np.full((4,), float(epoch), np.float32))}

    x = sym_mod.Variable("data")
    net = sym_mod.FullyConnected(x, num_hidden=4, name="fc")

    def save(prefix, epoch, fault_plan=None):
        if fault_plan is None:
            model_mod.save_checkpoint(prefix, epoch, net,
                                      params_for(epoch), {})
            return
        with faults.plan(fault_plan):
            model_mod.save_checkpoint(prefix, epoch, net,
                                      params_for(epoch), {})

    def check(prefix, want_epoch, where):
        latest = model_mod.latest_complete_checkpoint(prefix)
        if latest != want_epoch:
            violations.append("%s: latest complete is %r (want %r)"
                              % (where, latest, want_epoch))
        if latest is None:
            return
        try:
            _, args, _ = model_mod.load_checkpoint(prefix, latest)
        except Exception as exc:
            violations.append("%s: latest_complete epoch %d failed to "
                              "load: %r" % (where, latest, exc))
            return
        want = params_for(latest)
        for k in want:
            if not np.array_equal(args[k].asnumpy(), want[k].asnumpy()):
                violations.append("%s: epoch %d param %r not bit-exact"
                                  % (where, latest, k))

    try:
        # enumerate every (site, per-site hit index) fault point one save
        # passes — an empty plan records hits without injecting anything —
        # against a throwaway prefix so nothing real gets committed
        probe = faults.FaultPlan(0)
        save(os.path.join(tmpdir, "probe"), 2, probe)
        points = [(site, i)
                  for site in sorted(probe.hits)
                  if site.startswith("checkpoint.")
                  for i in range(probe.hits[site])]
        if len(points) < 6:
            violations.append("crash sweep: only %d checkpoint fault "
                              "points (atomic writer shrank?)"
                              % len(points))
        # the save is committed exactly when the LAST file's (the
        # manifest's) os.replace has happened: the final "replaced" hit
        n_files = probe.hits.get("checkpoint.replaced", 0)
        committed_at = ("checkpoint.replaced", n_files - 1)
        for n, (site, i) in enumerate(points):
            prefix = os.path.join(tmpdir, "k%d" % n, "ck")
            os.makedirs(os.path.dirname(prefix))
            save(prefix, 1)   # must survive the killed save of epoch 2
            kind = "truncate" if rng.random() < 0.5 else "crash"
            plan_k = faults.FaultPlan(seed * 131 + n)
            plan_k.add(site, kind=kind, after=i, times=1)
            try:
                save(prefix, 2, plan_k)
                violations.append("crash sweep: kill point %s#%d never "
                                  "fired" % (site, i))
            except faults.SimulatedCrash:
                pass
            want = 2 if (site, i) == committed_at else 1
            check(prefix, want, "kill@%s#%d(%s)" % (site, i, kind))
        prefix = os.path.join(tmpdir, "clean")
        save(prefix, 1)
        save(prefix, 2)   # clean save: newest-complete must be 2
        check(prefix, 2, "after clean save")
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    return violations


# ---------------------------------------------------------------------------
# scenario 8: continuous-batching decode engine storm
# ---------------------------------------------------------------------------

# decode engines compile a prefill+width signature menu at load, so the
# fixture is built once (lazily) and shared across seeds like the server
_DECODE_PROMPTS = ((3,), (1, 2), (5, 4, 3, 2), (7, 6, 5, 4, 3, 2, 1),
                   (2, 2, 2), (9, 8))
_DECODE_MAX_NEW = 6


def _build_decode_fixture():
    """-> (engine, prompts, per-prompt greedy reference token lists)."""
    from ..serving.decode import DecodeEngine, TinyCausalLM

    model = TinyCausalLM(vocab_size=24, hidden=16, num_layers=1,
                         num_heads=2, max_len=32, seed=11)
    # deliberately tight: 3 slots, a 2-deep queue and a 7-block pool so
    # seeded storms actually exercise OVERLOADED shedding and join-time
    # block reservation, not just the happy path
    engine = DecodeEngine(model, name="stress-decode", max_slots=3,
                          block_size=4, num_blocks=8, max_prompt_len=8,
                          max_new_tokens=_DECODE_MAX_NEW, max_queue=2,
                          breaker_threshold=4, breaker_backoff_ms=15.0)
    refs = [engine.generate_reference(p, _DECODE_MAX_NEW).tolist()
            for p in _DECODE_PROMPTS]
    return engine, list(_DECODE_PROMPTS), refs


def decode_storm(engine, prompts, refs, seed, n_clients=4, per_client=2):
    """Concurrent token streams under chaos (the ``decode`` scenario).

    Invariants:
    * **stream conservation** — every submitted stream reaches exactly one
      terminal status from {OK, TIMEOUT, OVERLOADED, INVALID_INPUT,
      UNAVAILABLE} (ERROR would mean the engine failed a batch with no
      faults injected), and the engine's counters conserve:
      ``requests == ok + timeouts + errors + unavailable`` with every
      per-status delta matching the client tally;
    * **no torn/cross-contaminated streams** — an OK stream's tokens equal
      the greedy reference for ITS OWN prompt bitwise; a TIMEOUT or
      UNAVAILABLE stream's partial tokens are a strict prefix of that
      reference (iteration-level join/leave must never leak another
      slot's tokens or KV pages into a stream);
    * **KV block accounting** — after the drain the pool is whole again:
      ``used == 0``, ``reserved == 0`` and ``allocated_total ==
      freed_total`` (leaked pages would starve future admissions);
    * **no deadlock** — every client joins in time; every stream's wait()
      resolves.
    """
    from ..serving import server as srv

    terminal = {srv.OK, srv.TIMEOUT, srv.OVERLOADED, srv.INVALID_INPUT,
                srv.UNAVAILABLE}
    rng = random.Random(seed ^ 0xDEC0DE)
    violations = []
    before = engine.stats_snapshot()
    plans = []
    for c in range(n_clients):
        plan = []
        for _ in range(per_client):
            roll = rng.random()
            if roll < 0.15:
                plan.append(("invalid", None))              # bad token ids
            elif roll < 0.35:
                plan.append(("tiny", rng.uniform(0.2, 2.0)))  # likely TIMEOUT
            else:
                plan.append(("ok", None))                   # no deadline
            plan[-1] = plan[-1] + (rng.randrange(len(prompts)),)
        plans.append(plan)
    results = [[] for _ in range(n_clients)]

    def client(c):
        for kind, tmo, pi in plans[c]:
            if kind == "invalid":
                prompt = [999, -3]                          # outside vocab
            else:
                prompt = list(prompts[pi])
            stream = engine.submit(prompt, max_new_tokens=_DECODE_MAX_NEW,
                                   timeout_ms=tmo)
            if not stream.wait(_JOIN_TIMEOUT_S):
                violations.append("stream of client %d never terminated" % c)
            results[c].append((kind, pi, stream))

    violations.extend(_spawn([lambda c=c: client(c)
                              for c in range(n_clients)]))

    tally = {"admitted": 0, "OK": 0, "TIMEOUT": 0, "OVERLOADED": 0,
             "INVALID_INPUT": 0, "ERROR": 0, "UNAVAILABLE": 0}
    for c in range(n_clients):
        for kind, pi, stream in results[c]:
            status, tokens, _, _, _ = stream.snapshot()
            if status not in terminal:
                violations.append("client %d stream ended %r (kind %s)"
                                  % (c, status, kind))
                continue
            tally[status] = tally.get(status, 0) + 1
            if stream.admitted:
                tally["admitted"] += 1
            if kind == "invalid":
                if status != srv.INVALID_INPUT:
                    violations.append("invalid prompt got %s" % status)
                continue
            ref = refs[pi]
            if status == srv.OK and list(tokens) != ref:
                violations.append(
                    "torn stream: client %d OK tokens %s != reference %s"
                    % (c, list(tokens), ref))
            elif status in (srv.TIMEOUT, srv.UNAVAILABLE) and \
                    list(tokens) != ref[:len(tokens)]:
                violations.append(
                    "contaminated partial stream: client %d %s tokens %s "
                    "not a prefix of %s" % (c, status, list(tokens), ref))

    # conservation: same settle discipline as _settle_and_check (the
    # completion event fires before the stats bump under chaos locks)
    keys = ("requests", "ok", "timeouts", "errors", "unavailable", "shed",
            "invalid", "unavailable_rejected")
    settle_until = time.monotonic() + 5.0
    while True:
        after = engine.stats_snapshot()
        d = {k: after[k] - before[k] for k in keys}
        terminal_sum = (d["ok"] + d["timeouts"] + d["errors"]
                        + d["unavailable"])
        if d["requests"] == terminal_sum or time.monotonic() >= settle_until:
            break
        time.sleep(0.005)
    if d["requests"] != tally["admitted"]:
        violations.append("decode: admission mismatch: engine %d vs "
                          "clients %d" % (d["requests"], tally["admitted"]))
    if d["requests"] != terminal_sum:
        violations.append("decode: lost streams: %d admitted, %d terminal"
                          % (d["requests"], terminal_sum))
    if d["ok"] != tally["OK"]:
        violations.append("decode: ok mismatch: engine %d vs clients %d"
                          % (d["ok"], tally["OK"]))
    if d["timeouts"] != tally["TIMEOUT"]:
        violations.append("decode: timeout mismatch: engine %d vs clients %d"
                          % (d["timeouts"], tally["TIMEOUT"]))
    if d["shed"] != tally["OVERLOADED"]:
        violations.append("decode: shed mismatch: engine %d vs clients %d"
                          % (d["shed"], tally["OVERLOADED"]))
    if d["invalid"] != tally["INVALID_INPUT"]:
        violations.append("decode: invalid mismatch: engine %d vs clients %d"
                          % (d["invalid"], tally["INVALID_INPUT"]))
    if d["unavailable"] + d["unavailable_rejected"] != tally["UNAVAILABLE"]:
        violations.append("decode: unavailable mismatch: engine %d+%d vs "
                          "clients %d" % (d["unavailable"],
                                          d["unavailable_rejected"],
                                          tally["UNAVAILABLE"]))
    if d["errors"] or tally["ERROR"]:
        violations.append("decode: ERROR with no faults injected "
                          "(engine %d, clients %d)"
                          % (d["errors"], tally["ERROR"]))

    # KV block accounting: the pool must be whole after the drain
    deadline = time.monotonic() + 5.0
    while True:
        kv = engine.kv_stats()
        if (kv["used"] == 0 and kv["reserved"] == 0
                and kv["live_sequences"] == 0) \
                or time.monotonic() >= deadline:
            break
        time.sleep(0.005)
    if kv["used"] != 0 or kv["reserved"] != 0 or kv["live_sequences"] != 0:
        violations.append("decode: KV pool not whole after drain: %r" % kv)
    if kv["allocated_total"] != kv["freed_total"]:
        violations.append("decode: KV leak: allocated %d != freed %d"
                          % (kv["allocated_total"], kv["freed_total"]))
    # zero steady-state recompiles under contention
    cb, ca = before["cache"], after["cache"]
    if ca["recompiles"] != cb["recompiles"]:
        violations.append("decode: steady-state recompile under chaos: "
                          "%d -> %d" % (cb["recompiles"], ca["recompiles"]))
    return violations


# ---------------------------------------------------------------------------
# scenario 9: elastic fleet — replica death under storm load
# ---------------------------------------------------------------------------

def _build_fleet_fixture(n_clients):
    """-> (router, model_name, inputs, expected).

    Three replicas, the model placed (and warmed) on two of them: a seeded
    kill always leaves one warm copy to fail over to, and the idle third
    replica is where the background rebalance restores the replication
    factor — re-warming BEFORE the placement cutover, so the scenario's
    recompile-free failover claim is actually exercised."""
    import numpy as np
    from .. import gluon, init
    from ..gluon import nn
    from .. import ndarray as nd
    from ..serving.fleet import FleetRouter

    class _Net(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.out = nn.Dense(_CLASSES, in_units=_FEAT)

        def hybrid_forward(self, F, x):
            return self.out(x)

    net = _Net()
    net.initialize(init.Xavier())
    router = FleetRouter(replicas=3, failover_budget=2,
                         breaker_threshold=2, breaker_backoff_ms=10.0)
    router.load_model("elastic", net, input_shapes=[(_FEAT,)], replicas=2,
                      max_batch=4, max_queue=8, linger_ms=1.0, warmup=True,
                      breaker_threshold=4, breaker_backoff_ms=15.0)
    inputs, expected = [], []
    for i in range(n_clients):
        x = np.full((_FEAT,), 0.25 * (i + 1), np.float32)
        inputs.append(x)
        expected.append(net(nd.array(x[None])).asnumpy()[0])
    return router, "elastic", inputs, expected


def fleet_storm(router, name, inputs, expected, seed, per_client=3):
    """Kill a replica under storm load (the ``fleet`` scenario).

    A seeded SimulatedCrash at the ``fleet.replica`` fault point models one
    replica dying mid-request while concurrent clients stream predicts
    through the FleetRouter.  Invariants:

    * **zero dropped requests** — every client call reaches exactly one
      terminal status, and the fleet counters conserve ACROSS FAILOVERS:
      ``requests == ok + timeouts + errors + unavailable`` with every
      per-status delta matching the client tally;
    * **no torn results** — an OK result matches the eager reference for
      that client's own input even when the request was failed over; a
      TIMEOUT never carries outputs;
    * **the death is observed** — exactly one replica death, at least one
      failover, and the killed replica is off every placement;
    * **bounded tail latency** — no request outlives the 10 s bound (a
      dying replica must fail over, not wedge its callers);
    * **re-convergence** — the background rebalance restores the
      replication factor on the idle replica (warm before cutover) and the
      router reports HEALTHY again.

    Each seed ends with a repair step (``add_replica``) so the next seed
    again has three live replicas."""
    import numpy as np
    from .. import faults
    from ..serving import server as srv

    terminal = {srv.OK, srv.TIMEOUT, srv.OVERLOADED, srv.INVALID_INPUT,
                srv.ERROR, srv.UNAVAILABLE}
    _TAIL_BOUND_MS = 10_000.0
    violations = []
    rng = random.Random(seed ^ 0xF1EE7)
    n_clients = len(inputs)
    total = n_clients * per_client
    before = router.stats()

    plans = []
    for c in range(n_clients):
        plan = []
        for _ in range(per_client):
            if rng.random() < 0.2:
                plan.append(rng.uniform(0.2, 2.0))     # likely TIMEOUT
            else:
                plan.append(2000.0)
        plans.append(plan)
    # the kill fires on a seeded routed attempt in the first half of the
    # storm, so surviving traffic still exercises the failed-over path
    kill_after = rng.randrange(0, max(1, total // 2))
    kill_plan = faults.FaultPlan(seed ^ 0x51E7)
    kill_plan.add("fleet.replica", kind="crash", after=kill_after, times=1)

    results = [[] for _ in range(n_clients)]

    def client(c):
        for tmo in plans[c]:
            results[c].append(router.predict(name, inputs[c],
                                             timeout_ms=tmo))

    with faults.plan(kill_plan):
        violations.extend(_spawn([lambda c=c: client(c)
                                  for c in range(n_clients)]))
    after = router.stats()

    if kill_plan.fired_count("fleet.replica") != 1:
        violations.append("fleet: replica kill fired %d time(s) (want 1; "
                          "after=%d of %d attempts)"
                          % (kill_plan.fired_count("fleet.replica"),
                             kill_after, kill_plan.hit_count("fleet.replica")))

    tally = {"OK": 0, "TIMEOUT": 0, "OVERLOADED": 0, "INVALID_INPUT": 0,
             "ERROR": 0, "UNAVAILABLE": 0}
    for c in range(n_clients):
        if len(results[c]) != per_client:
            violations.append("fleet: client %d lost results: %d of %d"
                              % (c, len(results[c]), per_client))
        for res in results[c]:
            if res is None or res.status not in terminal:
                violations.append("fleet: non-terminal result %r" % (res,))
                continue
            tally[res.status] += 1
            if res.latency_ms is not None and res.latency_ms > _TAIL_BOUND_MS:
                violations.append("fleet: tail latency %0.f ms > %.0f ms "
                                  "bound (%s)" % (res.latency_ms,
                                                  _TAIL_BOUND_MS, res.status))
            if res.status == srv.OK:
                if res.outputs is None:
                    violations.append("fleet: torn result: OK with "
                                      "outputs=None")
                elif not np.allclose(res.outputs[0], expected[c],
                                     rtol=1e-4, atol=1e-5):
                    violations.append("fleet: row mixup: client %d OK output "
                                      "does not match its reference" % c)
            elif res.status == srv.TIMEOUT and res.outputs is not None:
                violations.append("fleet: torn result: TIMEOUT carrying "
                                  "outputs")

    # fleet-level conservation across failovers (counters bump before
    # predict() returns, so the deltas are final once the clients join)
    keys = ("requests", "ok", "timeouts", "errors", "unavailable", "shed",
            "invalid", "failovers", "replica_deaths")
    d = {k: after[k] - before[k] for k in keys}
    routed = (tally["OK"] + tally["TIMEOUT"] + tally["ERROR"]
              + tally["UNAVAILABLE"])
    if d["requests"] != routed:
        violations.append("fleet: dropped requests: router %d vs clients %d"
                          % (d["requests"], routed))
    if d["requests"] != d["ok"] + d["timeouts"] + d["errors"] \
            + d["unavailable"]:
        violations.append(
            "fleet: conservation broken: requests %d != ok %d + timeouts %d "
            "+ errors %d + unavailable %d"
            % (d["requests"], d["ok"], d["timeouts"], d["errors"],
               d["unavailable"]))
    for client_key, fleet_key in (("OK", "ok"), ("TIMEOUT", "timeouts"),
                                  ("ERROR", "errors"),
                                  ("UNAVAILABLE", "unavailable"),
                                  ("OVERLOADED", "shed"),
                                  ("INVALID_INPUT", "invalid")):
        if d[fleet_key] != tally[client_key]:
            violations.append("fleet: %s mismatch: router %d vs clients %d"
                              % (fleet_key, d[fleet_key], tally[client_key]))
    if d["replica_deaths"] != 1:
        violations.append("fleet: %d replica death(s) recorded (want 1)"
                          % d["replica_deaths"])
    if d["failovers"] < 1:
        violations.append("fleet: kill fired but zero failovers recorded")
    dead = [rid for rid, state in router.replicas().items()
            if state == "DEAD"]
    for m in after["models"].values():
        for rid in dead:
            if rid in m["placement"]:
                violations.append("fleet: dead replica %s still placed" % rid)

    # re-convergence: the background rebalance re-warms the model on the
    # idle replica, then routing health must return to HEALTHY
    if not router.wait_converged(timeout_s=10.0):
        violations.append("fleet: placement never re-converged after the "
                          "death: %r" % router.stats()["models"])
    deadline = time.monotonic() + 10.0
    healthy = False
    while time.monotonic() < deadline:
        res = router.predict(name, inputs[0], timeout_ms=2000.0)
        if res.status == srv.OK and router.health(name) == "HEALTHY":
            healthy = True
            break
        time.sleep(0.005)
    if not healthy:
        violations.append("fleet: router did not re-converge HEALTHY "
                          "(health %r)" % router.health(name))

    # repair for the next seed: rejoin a replica (synchronous rebalance —
    # nothing to place if the factor is already restored)
    router.add_replica()
    live = [rid for rid, state in router.replicas().items()
            if state == "LIVE"]
    if len(live) != 3:
        violations.append("fleet: repair left %d live replica(s) (want 3)"
                          % len(live))
    return violations


# ---------------------------------------------------------------------------
# scenario 10: stateful decode fleet — drain + kill under multi-tenant storm
# ---------------------------------------------------------------------------

_DFLEET_PROMPTS = ((3,), (1, 2), (5, 4, 3, 2), (2, 2, 2))
_DFLEET_MAX_NEW = 5


def _build_decode_fleet_fixture():
    """-> (router, engine_name, prompts, references).

    Three replicas each hosting one decode engine built from the same
    seeded TinyCausalLM (identical params per factory call — the handoff
    bitwise-equality claim depends on it).  Pools are deliberately tight
    (8 allocatable blocks, 2 slots) so the seeded storm exercises QoS
    shedding and import-time headroom refusals, not just the happy path."""
    from ..serving.decode import DecodeEngine, TinyCausalLM
    from ..serving.fleet import FleetRouter

    def factory(name):
        model = TinyCausalLM(vocab_size=20, hidden=16, num_layers=1,
                             num_heads=2, max_len=24, seed=13)
        return DecodeEngine(model, name=name, max_slots=2, block_size=4,
                            num_blocks=9, max_prompt_len=4,
                            max_new_tokens=_DFLEET_MAX_NEW, max_queue=6,
                            width_blocks=[4], breaker_threshold=4,
                            breaker_backoff_ms=15.0)

    router = FleetRouter(replicas=3, failover_budget=2,
                         breaker_threshold=3, breaker_backoff_ms=10.0)
    router.load_decode("lm", factory, replicas=3)
    # token budget ~2 concurrent hot streams; calm is uncapped but lighter
    router.set_tenant("hot", weight=1.0, token_budget=18)
    router.set_tenant("calm", weight=2.0)
    rid0 = router.stats()["decode_models"]["lm"]["placement"][0]
    refs = [router.engine("lm", rid0)
            .generate_reference(p, _DFLEET_MAX_NEW).tolist()
            for p in _DFLEET_PROMPTS]
    return router, "lm", list(_DFLEET_PROMPTS), refs


def decode_fleet_storm(router, name, prompts, refs, seed):
    """Drain AND kill replicas under a multi-tenant token-stream storm
    (the ``decode_fleet`` scenario).

    A seeded disruptor waits for streams to be in flight, then **drains**
    one LIVE replica (its engines quiesce, every live stream's prefix +
    KV pages export and resume on a survivor behind a bumped lease
    generation) and **kills** a different LIVE one (its streams terminate
    UNAVAILABLE with their prefixes — no snapshot exists in a crash).
    Invariants:

    * **zero dropped streams** — every submitted stream reaches exactly
      one terminal status within the join bound, and the router's decode
      counters conserve ACROSS HANDOFFS:
      ``requests == ok + timeouts + errors + unavailable`` with the
      client tally matching per status;
    * **no torn or cross-contaminated streams** — an OK stream's tokens
      (handed off or not) equal the greedy reference for ITS OWN prompt
      bitwise; TIMEOUT/UNAVAILABLE partials are strict prefixes; an
      OVERLOADED (QoS-shed) stream carries zero tokens;
    * **per-tenant conservation** — every admitted stream of every tenant
      completes; the over-budget tenant sheds while the calm one flows;
    * **KV pools whole on survivors** — every engine on a non-DEAD
      replica drains back to used == reserved == live_sequences == 0 and
      the per-engine conservation ``requests + imported ==
      ok + timeouts + errors + unavailable + handed_off`` holds;
    * **zero steady-state recompiles** — engines that lived the whole
      seed compiled nothing new (handoff rides the warmed menu);
    * **repair + no starvation** — after enable()/add_replica() the
      placement re-converges and one sequential probe stream per tenant
      reaches OK.
    """
    from ..serving import server as srv

    violations = []
    rng = random.Random(seed ^ 0xDF1EE7)
    n_hot, per_hot = 2, 3
    n_calm, per_calm = 2, 2
    before = router.decode_stats.snapshot()
    before_eng = {(n, rid): snap
                  for n, per in router.stats()["engines"].items()
                  for rid, snap in per.items()}
    before_tenants = router.tenant_snapshot()

    plans = []   # (tenant, [(timeout_ms or None, prompt_idx), ...])
    for c in range(n_hot):
        plans.append(("hot", [(rng.uniform(200.0, 2000.0)
                               if rng.random() < 0.2 else None,
                               rng.randrange(len(prompts)))
                              for _ in range(per_hot)]))
    for c in range(n_calm):
        plans.append(("calm", [(None, rng.randrange(len(prompts)))
                               for _ in range(per_calm)]))
    results = [[] for _ in plans]

    def client(c):
        tenant, plan = plans[c]
        for tmo, pi in plan:
            stream = router.submit_stream(name, list(prompts[pi]),
                                          max_new_tokens=_DFLEET_MAX_NEW,
                                          timeout_ms=tmo, tenant=tenant)
            if not stream.wait(_JOIN_TIMEOUT_S):
                violations.append("decode_fleet: stream of client %d never "
                                  "terminated" % c)
            results[c].append((pi, stream))

    drained = []

    def disruptor():
        # wait until the storm is actually in flight (bounded)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            d = router.decode_stats.snapshot()
            if d["requests"] - before["requests"] >= 2:
                break
            time.sleep(0.002)
        live = [rid for rid, state in sorted(router.replicas().items())
                if state == "LIVE"]
        if len(live) < 2:
            violations.append("decode_fleet: %d live replica(s) before the "
                              "disruption (want >= 2)" % len(live))
            return
        rid_d = live[rng.randrange(len(live))]
        rid_k = rng.choice([r for r in live if r != rid_d])
        router.drain(rid_d)      # fenced handoff to survivors
        drained.append(rid_d)
        router.kill_replica(rid_k)

    workers = [lambda c=c: client(c) for c in range(len(plans))]
    workers.append(disruptor)
    violations.extend(_spawn(workers))

    # client-side status checks
    tally = {"admitted": 0, "OK": 0, "TIMEOUT": 0, "ERROR": 0,
             "UNAVAILABLE": 0, "shed": 0, "rejected": 0}
    for c, (tenant, _plan) in enumerate(plans):
        for pi, stream in results[c]:
            status, tokens, _, latency, err = stream.snapshot()
            if status is None:
                violations.append("decode_fleet: client %d stream has no "
                                  "terminal status" % c)
                continue
            if latency is not None and latency > _JOIN_TIMEOUT_S * 1e3:
                violations.append("decode_fleet: stream latency %.0f ms "
                                  "over the %.0f s bound"
                                  % (latency, _JOIN_TIMEOUT_S))
            if stream.admitted:
                tally["admitted"] += 1
                if status not in (srv.OK, srv.TIMEOUT, srv.ERROR,
                                  srv.UNAVAILABLE):
                    violations.append("decode_fleet: admitted stream ended "
                                      "%r" % status)
                    continue
                tally[status] += 1
            elif status == srv.OVERLOADED:
                tally["shed"] += 1
            elif status == srv.UNAVAILABLE:
                tally["rejected"] += 1
            else:
                violations.append("decode_fleet: rejected stream ended %r"
                                  % status)
                continue
            ref = refs[pi]
            toks = list(tokens)
            if status == srv.OK and toks != ref:
                violations.append(
                    "decode_fleet: torn stream: client %d OK tokens %s != "
                    "reference %s" % (c, toks, ref))
            elif status in (srv.TIMEOUT, srv.UNAVAILABLE) and \
                    toks != ref[:len(toks)]:
                violations.append(
                    "decode_fleet: contaminated partial: client %d %s "
                    "tokens %s not a prefix of %s" % (c, status, toks, ref))
            elif status == srv.OVERLOADED and toks:
                violations.append("decode_fleet: QoS-shed stream carries "
                                  "%d token(s)" % len(toks))

    # router-level conservation (terminal hooks fire just after complete —
    # settle briefly, same discipline as the engine scenarios)
    keys = ("requests", "ok", "timeouts", "errors", "unavailable", "shed",
            "invalid", "unavailable_rejected")
    settle_until = time.monotonic() + 5.0
    while True:
        after = router.decode_stats.snapshot()
        d = {k: after[k] - before[k] for k in keys}
        terminal_sum = (d["ok"] + d["timeouts"] + d["errors"]
                        + d["unavailable"])
        if d["requests"] == terminal_sum or time.monotonic() >= settle_until:
            break
        time.sleep(0.005)
    if d["requests"] != terminal_sum:
        violations.append("decode_fleet: lost streams: %d admitted, %d "
                          "terminal" % (d["requests"], terminal_sum))
    if d["requests"] != tally["admitted"]:
        violations.append("decode_fleet: admission mismatch: router %d vs "
                          "clients %d" % (d["requests"], tally["admitted"]))
    for client_key, fleet_key in (("OK", "ok"), ("TIMEOUT", "timeouts"),
                                  ("ERROR", "errors"),
                                  ("UNAVAILABLE", "unavailable"),
                                  ("shed", "shed"),
                                  ("rejected", "unavailable_rejected")):
        if d[fleet_key] != tally[client_key]:
            violations.append("decode_fleet: %s mismatch: router %d vs "
                              "clients %d"
                              % (fleet_key, d[fleet_key], tally[client_key]))
    if d["errors"]:
        violations.append("decode_fleet: %d ERROR stream(s) with no faults "
                          "injected" % d["errors"])

    # per-tenant conservation: every admitted stream settled its tokens
    for tname, snap in router.tenant_snapshot().items():
        prev = before_tenants.get(tname, {"admitted": 0, "completed": 0})
        if snap["inflight_tokens"] != 0:
            violations.append("decode_fleet: tenant %r still holds %d "
                              "in-flight token(s) after the storm"
                              % (tname, snap["inflight_tokens"]))
        if snap["admitted"] - prev["admitted"] != \
                snap["completed"] - prev["completed"]:
            violations.append("decode_fleet: tenant %r admitted %d but "
                              "completed %d"
                              % (tname, snap["admitted"] - prev["admitted"],
                                 snap["completed"] - prev["completed"]))

    # KV pools whole + per-engine conservation on every survivor
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        engines = router.stats()["engines"].get(name, {})
        if all(s["kv"]["used"] == 0 and s["kv"]["reserved"] == 0
               and s["kv"]["live_sequences"] == 0
               for s in engines.values()):
            break
        time.sleep(0.005)
    engines = router.stats()["engines"].get(name, {})
    for rid, s in engines.items():
        kv = s["kv"]
        if kv["used"] != 0 or kv["reserved"] != 0 \
                or kv["live_sequences"] != 0:
            violations.append("decode_fleet: KV pool not whole on survivor "
                              "%s: %r" % (rid, kv))
        if kv["allocated_total"] != kv["freed_total"]:
            violations.append("decode_fleet: KV leak on %s: allocated %d != "
                              "freed %d" % (rid, kv["allocated_total"],
                                            kv["freed_total"]))
        if s["requests"] + s["imported"] != (
                s["ok"] + s["timeouts"] + s["errors"] + s["unavailable"]
                + s["handed_off"]):
            violations.append("decode_fleet: engine conservation broken on "
                              "%s: req %d + imported %d != ok %d + to %d + "
                              "err %d + unavail %d + handed %d"
                              % (rid, s["requests"], s["imported"], s["ok"],
                                 s["timeouts"], s["errors"],
                                 s["unavailable"], s["handed_off"]))
        # zero steady-state recompiles on engines alive the whole seed
        prev = before_eng.get((name, rid))
        if prev is not None and \
                s["cache"]["recompiles"] != prev["cache"]["recompiles"]:
            violations.append("decode_fleet: steady-state recompile on %s: "
                              "%d -> %d" % (rid,
                                            prev["cache"]["recompiles"],
                                            s["cache"]["recompiles"]))

    # repair for the next seed, then structural fairness: one sequential
    # probe per tenant must reach OK (no tenant starves post-disruption)
    for rid in drained:
        if router.replicas().get(rid) == "DRAINING":
            router.enable(rid)
    router.add_replica()
    if not router.wait_converged(timeout_s=10.0):
        violations.append("decode_fleet: placement never re-converged: %r"
                          % router.stats()["decode_models"])
    for tenant in ("hot", "calm"):
        probe = router.submit_stream(name, list(prompts[0]),
                                     max_new_tokens=_DFLEET_MAX_NEW,
                                     tenant=tenant)
        probe.wait(_JOIN_TIMEOUT_S)
        status, tokens, _, _, err = probe.snapshot()
        if status != srv.OK or list(tokens) != refs[0]:
            violations.append("decode_fleet: post-repair probe for tenant "
                              "%r ended %r (%r)" % (tenant, status, err))
    # leave the fixture settled: the terminal hook fires off-lock after
    # complete(), so a probe's counter bump may land after its wait() —
    # don't let it straddle the next seed's `before` snapshot
    settle_until = time.monotonic() + 5.0
    while time.monotonic() < settle_until:
        s = router.decode_stats.snapshot()
        if s["requests"] == (s["ok"] + s["timeouts"] + s["errors"]
                             + s["unavailable"]):
            break
        time.sleep(0.002)
    return violations


# ---------------------------------------------------------------------------
# scenario: shared-prefix decode storm (decode_prefix)
# ---------------------------------------------------------------------------

_DPREFIX_SHARED = (5, 3, 7, 1, 2, 6, 4, 8)      # two full prefill chunks
_DPREFIX_PROMPTS = (
    _DPREFIX_SHARED,                             # donor: exact duplicates
    _DPREFIX_SHARED + (9, 2),                    # of this one force CoW
    _DPREFIX_SHARED + (11, 3, 5, 7),
    _DPREFIX_SHARED + (2,),
    _DPREFIX_SHARED + (10, 1, 12, 4, 6, 2),
)
_DPREFIX_MAX_NEW = 6
_DPREFIX_TEMP = 0.8
_DPREFIX_TOPK = 6
_DPREFIX_SEED0 = 9000   # sampled stream of prompt i uses seed 9000 + i


def _build_decode_prefix_fixture():
    """-> (router, engine_name, prompts, greedy_refs, sampled_refs).

    Three replicas, each hosting a chunked + prefix-cached + speculative
    decode engine built from the same seeded TinyCausalLM (identical
    params per factory call — the handoff bitwise claim depends on it).
    The draft IS the target model (self-draft): acceptance is high while
    every emitted token still comes from a verify row, so a cold draft
    after an import only lowers the acceptance rate, never the output.
    The prompt set shares an 8-token prefix so cross-request caching,
    CoW forks on the recomputed tail chunk, and refcounted shared-page
    handoffs all fire under the storm."""
    from ..serving.decode import DecodeEngine, TinyCausalLM
    from ..serving.fleet import FleetRouter

    def factory(name):
        model = TinyCausalLM(vocab_size=24, hidden=16, num_layers=1,
                             num_heads=2, max_len=24, seed=17)
        # max_new_tokens leaves headroom over the storm's request size so
        # the donor pass can run one LONGER holder stream (see the
        # deterministic CoW pair in decode_prefix_storm)
        return DecodeEngine(model, name=name, max_slots=2, block_size=4,
                            num_blocks=20, max_prompt_len=14,
                            max_new_tokens=_DPREFIX_MAX_NEW + 2,
                            max_queue=8,
                            prefill_chunk=4, prefix_cache=True,
                            spec_k=2, draft_model=model,
                            breaker_threshold=4, breaker_backoff_ms=15.0)

    router = FleetRouter(replicas=3, failover_budget=2,
                         breaker_threshold=3, breaker_backoff_ms=10.0)
    router.load_decode("pxlm", factory, replicas=3)
    rid0 = router.stats()["decode_models"]["pxlm"]["placement"][0]
    eng = router.engine("pxlm", rid0)
    refs = [eng.generate_reference(p, _DPREFIX_MAX_NEW).tolist()
            for p in _DPREFIX_PROMPTS]
    sam_refs = [eng.generate_reference(
                    p, _DPREFIX_MAX_NEW, temperature=_DPREFIX_TEMP,
                    top_k=_DPREFIX_TOPK, seed=_DPREFIX_SEED0 + i).tolist()
                for i, p in enumerate(_DPREFIX_PROMPTS)]
    return router, "pxlm", list(_DPREFIX_PROMPTS), refs, sam_refs


def decode_prefix_storm(router, name, prompts, refs, sam_refs, seed):
    """Shared-prefix storm with a mid-run replica drain (the
    ``decode_prefix`` scenario).

    A donor pass first runs the bare shared-prefix prompt on EVERY placed
    engine so each replica's prefix registry holds the shared chunks;
    the seeded storm then mixes greedy and explicitly-seeded sampled
    streams over prompts that extend (or exactly duplicate) that prefix
    while a disruptor drains one LIVE replica — migrated streams carry
    refcounted shared pages and in-flight sampler state to a survivor.
    Invariants:

    * **no torn streams** — an OK greedy stream's tokens equal the greedy
      reference for its own prompt bitwise; an OK sampled stream equals
      the sampled reference for its (prompt, seed) pair (same-seed
      replay holds across the handoff); TIMEOUT/UNAVAILABLE partials are
      strict prefixes; a shed stream carries zero tokens;
    * **conservation across handoffs** — router decode counters satisfy
      ``requests == ok + timeouts + errors + unavailable`` and match the
      client tally per status, with zero ERROR streams (no faults are
      injected here);
    * **shared pages stay refcounted** — after the drain every engine's
      KV pool is whole: used == reserved == live_sequences == 0 (shared
      pages retire to the reusable cache, counted once) and
      ``allocated_total == freed_total``; per-engine conservation
      ``requests + imported == ok+to+err+unavail+handed_off`` holds;
    * **the multipliers actually fired** — fleet-wide prefix_hits,
      cow_forks and spec_proposed all advanced (the duplicate-prompt
      stream guarantees a full-hit CoW fork on the recomputed tail
      chunk);
    * **zero steady-state recompiles** — prefix attach, CoW forks,
      sampling and the handoff all ride the warmed chunk/verify/draft
      signatures;
    * **repair + replay** — after enable() the placement re-converges
      and one greedy plus one sampled probe reach OK bitwise-equal to
      their references.
    """
    from ..serving import server as srv

    violations = []
    rng = random.Random(seed ^ 0x9EF1)
    before = router.decode_stats.snapshot()
    stats0 = router.stats()
    before_eng = dict(stats0["engines"].get(name, {}))
    before_roll = stats0["decode"]["prefix_spec"]

    # donor pass: seed every replica's prefix registry (direct engine
    # submits — deliberately outside the router's counters)
    placement = stats0["decode_models"][name]["placement"]
    for rid in placement:
        donor = router.engine(name, rid).submit(list(prompts[0]),
                                                _DPREFIX_MAX_NEW)
        donor.wait(_JOIN_TIMEOUT_S)
        status, tokens, _, _, err = donor.snapshot()
        if status != srv.OK or list(tokens) != refs[0]:
            violations.append("decode_prefix: donor on %s ended %r (%r)"
                              % (rid, status, err))
    # deterministic CoW pair on one engine: a LONGER-lived holder
    # duplicate attaches the registered pages and holds their refcount
    # while a second duplicate attaches behind it — whichever recomputes
    # its tail chunk while the page is shared (refcount > 1) must fork,
    # independent of the chaos schedule.  (Greedy decode is positionwise
    # deterministic, so the holder's extra tokens extend refs[0].)
    eng0 = router.engine(name, placement[0])
    holder = eng0.submit(list(prompts[0]), _DPREFIX_MAX_NEW + 2)
    dup = eng0.submit(list(prompts[0]), _DPREFIX_MAX_NEW)
    for label, stream, want in (("holder", holder, None),
                                ("dup", dup, refs[0])):
        stream.wait(_JOIN_TIMEOUT_S)
        status, tokens, _, _, err = stream.snapshot()
        toks = list(tokens)
        good = status == srv.OK and (
            toks == want if want is not None
            else toks[:len(refs[0])] == refs[0])
        if not good:
            violations.append("decode_prefix: CoW-pair %s stream ended %r "
                              "(%r)" % (label, status, err))

    n_clients, per_client = 3, 3
    plans = []   # [(timeout_ms or None, prompt_idx, sampled), ...]
    for c in range(n_clients):
        plan = []
        for s in range(per_client):
            if c == 0 and s == 0:
                # pinned: a greedy exact duplicate of the donor prompt —
                # the guaranteed full-hit + CoW-fork + speculation stream
                plan.append((None, 0, False))
                continue
            tmo = rng.uniform(200.0, 1500.0) if rng.random() < 0.15 \
                else None
            plan.append((tmo, rng.randrange(len(prompts)),
                         rng.random() < 0.35))
        plans.append(plan)
    results = [[] for _ in plans]

    def client(c):
        for tmo, pi, sampled in plans[c]:
            if sampled:
                stream = router.submit_stream(
                    name, list(prompts[pi]),
                    max_new_tokens=_DPREFIX_MAX_NEW, timeout_ms=tmo,
                    temperature=_DPREFIX_TEMP, top_k=_DPREFIX_TOPK,
                    seed=_DPREFIX_SEED0 + pi)
            else:
                stream = router.submit_stream(
                    name, list(prompts[pi]),
                    max_new_tokens=_DPREFIX_MAX_NEW, timeout_ms=tmo)
            if not stream.wait(_JOIN_TIMEOUT_S):
                violations.append("decode_prefix: stream of client %d "
                                  "never terminated" % c)
            results[c].append((pi, sampled, stream))

    drained = []

    def disruptor():
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            d = router.decode_stats.snapshot()
            if d["requests"] - before["requests"] >= 2:
                break
            time.sleep(0.002)
        live = [rid for rid, state in sorted(router.replicas().items())
                if state == "LIVE"]
        if len(live) < 2:
            violations.append("decode_prefix: %d live replica(s) before "
                              "the drain (want >= 2)" % len(live))
            return
        rid_d = live[rng.randrange(len(live))]
        router.drain(rid_d)   # fenced handoff: shared pages + samplers
        drained.append(rid_d)

    workers = [lambda c=c: client(c) for c in range(len(plans))]
    workers.append(disruptor)
    violations.extend(_spawn(workers))

    # client-side status + token integrity
    tally = {"admitted": 0, "OK": 0, "TIMEOUT": 0, "ERROR": 0,
             "UNAVAILABLE": 0, "shed": 0, "rejected": 0}
    for c in range(len(plans)):
        for pi, sampled, stream in results[c]:
            status, tokens, _, _, _err = stream.snapshot()
            if status is None:
                violations.append("decode_prefix: client %d stream has no "
                                  "terminal status" % c)
                continue
            if stream.admitted:
                tally["admitted"] += 1
                if status not in (srv.OK, srv.TIMEOUT, srv.ERROR,
                                  srv.UNAVAILABLE):
                    violations.append("decode_prefix: admitted stream "
                                      "ended %r" % status)
                    continue
                tally[status] += 1
            elif status == srv.OVERLOADED:
                tally["shed"] += 1
            elif status == srv.UNAVAILABLE:
                tally["rejected"] += 1
            else:
                violations.append("decode_prefix: rejected stream ended %r"
                                  % status)
                continue
            ref = sam_refs[pi] if sampled else refs[pi]
            kind = "sampled" if sampled else "greedy"
            toks = list(tokens)
            if status == srv.OK and toks != ref:
                violations.append(
                    "decode_prefix: torn %s stream: client %d OK tokens "
                    "%s != reference %s" % (kind, c, toks, ref))
            elif status in (srv.TIMEOUT, srv.UNAVAILABLE) and \
                    toks != ref[:len(toks)]:
                violations.append(
                    "decode_prefix: contaminated %s partial: client %d %s "
                    "tokens %s not a prefix of %s"
                    % (kind, c, status, toks, ref))
            elif status == srv.OVERLOADED and toks:
                violations.append("decode_prefix: shed stream carries %d "
                                  "token(s)" % len(toks))

    # router-level conservation (terminal hooks fire just after complete)
    keys = ("requests", "ok", "timeouts", "errors", "unavailable", "shed",
            "invalid", "unavailable_rejected")
    settle_until = time.monotonic() + 5.0
    while True:
        after = router.decode_stats.snapshot()
        d = {k: after[k] - before[k] for k in keys}
        terminal_sum = (d["ok"] + d["timeouts"] + d["errors"]
                        + d["unavailable"])
        if d["requests"] == terminal_sum or time.monotonic() >= settle_until:
            break
        time.sleep(0.005)
    if d["requests"] != terminal_sum:
        violations.append("decode_prefix: lost streams: %d admitted, %d "
                          "terminal" % (d["requests"], terminal_sum))
    if d["requests"] != tally["admitted"]:
        violations.append("decode_prefix: admission mismatch: router %d "
                          "vs clients %d" % (d["requests"],
                                             tally["admitted"]))
    for client_key, fleet_key in (("OK", "ok"), ("TIMEOUT", "timeouts"),
                                  ("ERROR", "errors"),
                                  ("UNAVAILABLE", "unavailable"),
                                  ("shed", "shed"),
                                  ("rejected", "unavailable_rejected")):
        if d[fleet_key] != tally[client_key]:
            violations.append("decode_prefix: %s mismatch: router %d vs "
                              "clients %d"
                              % (fleet_key, d[fleet_key],
                                 tally[client_key]))
    if d["errors"]:
        violations.append("decode_prefix: %d ERROR stream(s) with no "
                          "faults injected" % d["errors"])

    # shared pages stay refcounted: every pool drains whole (shared pages
    # retire to the reusable cache — they never leak and never double-
    # count), per-engine conservation + zero recompiles hold
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        engines = router.stats()["engines"].get(name, {})
        if all(s["kv"]["used"] == 0 and s["kv"]["reserved"] == 0
               and s["kv"]["live_sequences"] == 0
               for s in engines.values()):
            break
        time.sleep(0.005)
    engines = router.stats()["engines"].get(name, {})
    for rid, s in engines.items():
        kv = s["kv"]
        if kv["used"] != 0 or kv["reserved"] != 0 \
                or kv["live_sequences"] != 0:
            violations.append("decode_prefix: KV pool not whole on %s: %r"
                              % (rid, {k: kv[k] for k in
                                       ("used", "reserved",
                                        "live_sequences")}))
        if kv["allocated_total"] != kv["freed_total"]:
            violations.append("decode_prefix: KV leak on %s: allocated %d "
                              "!= freed %d" % (rid, kv["allocated_total"],
                                               kv["freed_total"]))
        if s["requests"] + s["imported"] != (
                s["ok"] + s["timeouts"] + s["errors"] + s["unavailable"]
                + s["handed_off"]):
            violations.append("decode_prefix: engine conservation broken "
                              "on %s: req %d + imported %d != ok %d + "
                              "to %d + err %d + unavail %d + handed %d"
                              % (rid, s["requests"], s["imported"],
                                 s["ok"], s["timeouts"], s["errors"],
                                 s["unavailable"], s["handed_off"]))
        prev = before_eng.get(rid)
        if prev is not None and \
                s["cache"]["recompiles"] != prev["cache"]["recompiles"]:
            violations.append("decode_prefix: steady-state recompile on "
                              "%s: %d -> %d"
                              % (rid, prev["cache"]["recompiles"],
                                 s["cache"]["recompiles"]))

    # the multipliers actually fired (fleet-wide rollup deltas)
    roll = router.stats()["decode"]["prefix_spec"]
    for key in ("prefix_hits", "cow_forks", "spec_proposed"):
        if roll[key] - before_roll[key] <= 0:
            violations.append("decode_prefix: %s never advanced under the "
                              "storm (%d -> %d)"
                              % (key, before_roll[key], roll[key]))

    # per-tenant accounting settled (everything ran as the default tenant)
    for tname, tsnap in router.tenant_snapshot().items():
        if tsnap["inflight_tokens"] != 0:
            violations.append("decode_prefix: tenant %r still holds %d "
                              "in-flight token(s) after the storm"
                              % (tname, tsnap["inflight_tokens"]))

    # repair for the next seed, then replay probes: one greedy + one
    # sampled stream must reach OK bitwise-equal to their references
    for rid in drained:
        if router.replicas().get(rid) == "DRAINING":
            router.enable(rid)
    if not router.wait_converged(timeout_s=10.0):
        violations.append("decode_prefix: placement never re-converged: %r"
                          % router.stats()["decode_models"])
    probe = router.submit_stream(name, list(prompts[0]),
                                 max_new_tokens=_DPREFIX_MAX_NEW)
    probe.wait(_JOIN_TIMEOUT_S)
    status, tokens, _, _, err = probe.snapshot()
    if status != srv.OK or list(tokens) != refs[0]:
        violations.append("decode_prefix: post-repair greedy probe ended "
                          "%r (%r)" % (status, err))
    probe = router.submit_stream(name, list(prompts[1]),
                                 max_new_tokens=_DPREFIX_MAX_NEW,
                                 temperature=_DPREFIX_TEMP,
                                 top_k=_DPREFIX_TOPK,
                                 seed=_DPREFIX_SEED0 + 1)
    probe.wait(_JOIN_TIMEOUT_S)
    status, tokens, _, _, err = probe.snapshot()
    if status != srv.OK or list(tokens) != sam_refs[1]:
        violations.append("decode_prefix: post-repair sampled probe ended "
                          "%r (%r)" % (status, err))
    # settle so a late terminal hook can't straddle the next seed's
    # `before` snapshot
    settle_until = time.monotonic() + 5.0
    while time.monotonic() < settle_until:
        s = router.decode_stats.snapshot()
        if s["requests"] == (s["ok"] + s["timeouts"] + s["errors"]
                             + s["unavailable"]):
            break
        time.sleep(0.002)
    return violations


# ---------------------------------------------------------------------------
# scenario: tensor-parallel sharded decode storm (sharded_decode)
# ---------------------------------------------------------------------------

_DSHARD_PROMPTS = ((5, 3, 7, 1), (2, 6, 4), (9, 8, 1, 2, 3), (7, 7),
                   (1, 2, 3, 4, 5, 6))
_DSHARD_MAX_NEW = 5
_DSHARD_TEMP = 0.8
_DSHARD_TOPK = 6
_DSHARD_SEED0 = 11000   # sampled stream of prompt i uses seed 11000 + i


def _build_sharded_decode_fixture():
    """-> (router, engine_name, prompts, greedy_refs, sampled_refs).

    Two replicas, each hosting a DecodeEngine over
    ``ShardedDecodeModel(tp=2)`` — head-sharded K/V pools, gather-free
    compute-parallel Megatron kernels — declared ``tp=2`` to the router
    so the device-footprint accounting is live under the storm.  The
    references come from an UNSHARDED engine over the same seeded
    weights: the scenario's claim is sharded-vs-single-device TOKEN
    identity (logits are allclose, not bitwise, under the per-block
    psums), held across a mid-storm sharded→sharded handoff."""
    from ..serving.decode import (DecodeEngine, ShardedDecodeModel,
                                  TinyCausalLM)
    from ..serving.fleet import FleetRouter

    model_kw = dict(vocab_size=24, hidden=16, num_layers=1, num_heads=2,
                    max_len=24, seed=17)
    engine_kw = dict(max_slots=2, block_size=4, num_blocks=20,
                     max_prompt_len=8, max_new_tokens=_DSHARD_MAX_NEW,
                     max_queue=8, breaker_threshold=4,
                     breaker_backoff_ms=15.0)

    def factory(name):
        model = ShardedDecodeModel(TinyCausalLM(**model_kw), tp=2)
        return DecodeEngine(model, name=name, **engine_kw)

    router = FleetRouter(replicas=2, failover_budget=2,
                         breaker_threshold=3, breaker_backoff_ms=10.0)
    router.load_decode("shlm", factory, replicas=2, tp=2)
    ref_eng = DecodeEngine(TinyCausalLM(**model_kw), name="shref",
                           **engine_kw)
    try:
        refs = [ref_eng.generate_reference(list(p),
                                           _DSHARD_MAX_NEW).tolist()
                for p in _DSHARD_PROMPTS]
        sam_refs = [ref_eng.generate_reference(
                        list(p), _DSHARD_MAX_NEW, temperature=_DSHARD_TEMP,
                        top_k=_DSHARD_TOPK,
                        seed=_DSHARD_SEED0 + i).tolist()
                    for i, p in enumerate(_DSHARD_PROMPTS)]
    finally:
        ref_eng.stop()
    return router, "shlm", [list(p) for p in _DSHARD_PROMPTS], refs, sam_refs


def sharded_decode_storm(router, name, prompts, refs, sam_refs, seed):
    """Storm over mesh-backed engines with a mid-run drain (the
    ``sharded_decode`` scenario).

    Greedy and explicitly-seeded sampled streams run against tp=2
    engines while a disruptor drains one LIVE replica, forcing a
    sharded→sharded handoff (exported pages host-gather to the full head
    axis, the importer re-shards them).  Invariants:

    * **no torn streams** — an OK stream's tokens equal the SINGLE-DEVICE
      reference for its (prompt, seed) bitwise, across the handoff;
      TIMEOUT/UNAVAILABLE partials are strict prefixes; shed streams
      carry zero tokens;
    * **conservation** — router decode counters satisfy ``requests ==
      ok + timeouts + errors + unavailable`` and match the client tally,
      with zero ERROR streams; per-engine ``requests + imported ==
      terminal + handed_off`` holds;
    * **pools whole on every shard** — after the storm each engine's KV
      accounting drains to used == reserved == live_sequences == 0 with
      ``allocated_total == freed_total`` (the head-sharded device pool is
      one array: the host accounting covers all shards at once), and
      every engine still reports ``tp_degree == 2``;
    * **zero steady-state recompiles** — sampling, the handoff and the
      drain all ride the warmed shard_map signatures;
    * **repair + replay** — after enable() the placement re-converges
      and one greedy plus one sampled probe reach OK bitwise-equal to
      the single-device references.
    """
    from ..serving import server as srv

    violations = []
    rng = random.Random(seed ^ 0x5A4D)
    before = router.decode_stats.snapshot()
    stats0 = router.stats()
    before_eng = dict(stats0["engines"].get(name, {}))

    n_clients, per_client = 3, 2
    plans = []   # [(timeout_ms or None, prompt_idx, sampled), ...]
    for c in range(n_clients):
        plan = []
        for s in range(per_client):
            tmo = rng.uniform(200.0, 1500.0) if rng.random() < 0.15 \
                else None
            plan.append((tmo, rng.randrange(len(prompts)),
                         rng.random() < 0.35))
        plans.append(plan)
    results = [[] for _ in plans]

    def client(c):
        for tmo, pi, sampled in plans[c]:
            if sampled:
                stream = router.submit_stream(
                    name, list(prompts[pi]),
                    max_new_tokens=_DSHARD_MAX_NEW, timeout_ms=tmo,
                    temperature=_DSHARD_TEMP, top_k=_DSHARD_TOPK,
                    seed=_DSHARD_SEED0 + pi)
            else:
                stream = router.submit_stream(
                    name, list(prompts[pi]),
                    max_new_tokens=_DSHARD_MAX_NEW, timeout_ms=tmo)
            if not stream.wait(_JOIN_TIMEOUT_S):
                violations.append("sharded_decode: stream of client %d "
                                  "never terminated" % c)
            results[c].append((pi, sampled, stream))

    drained = []

    def disruptor():
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            d = router.decode_stats.snapshot()
            if d["requests"] - before["requests"] >= 2:
                break
            time.sleep(0.002)
        live = [rid for rid, state in sorted(router.replicas().items())
                if state == "LIVE"]
        if len(live) < 2:
            violations.append("sharded_decode: %d live replica(s) before "
                              "the drain (want >= 2)" % len(live))
            return
        rid_d = live[rng.randrange(len(live))]
        router.drain(rid_d)   # sharded→sharded fenced handoff
        drained.append(rid_d)

    workers = [lambda c=c: client(c) for c in range(len(plans))]
    workers.append(disruptor)
    violations.extend(_spawn(workers))

    # client-side status + token integrity vs the single-device reference
    tally = {"admitted": 0, "OK": 0, "TIMEOUT": 0, "ERROR": 0,
             "UNAVAILABLE": 0, "shed": 0, "rejected": 0}
    for c in range(len(plans)):
        for pi, sampled, stream in results[c]:
            status, tokens, _, _, _err = stream.snapshot()
            if status is None:
                violations.append("sharded_decode: client %d stream has "
                                  "no terminal status" % c)
                continue
            if stream.admitted:
                tally["admitted"] += 1
                if status not in (srv.OK, srv.TIMEOUT, srv.ERROR,
                                  srv.UNAVAILABLE):
                    violations.append("sharded_decode: admitted stream "
                                      "ended %r" % status)
                    continue
                tally[status] += 1
            elif status == srv.OVERLOADED:
                tally["shed"] += 1
            elif status == srv.UNAVAILABLE:
                tally["rejected"] += 1
            else:
                violations.append("sharded_decode: rejected stream ended "
                                  "%r" % status)
                continue
            ref = sam_refs[pi] if sampled else refs[pi]
            kind = "sampled" if sampled else "greedy"
            toks = list(tokens)
            if status == srv.OK and toks != ref:
                violations.append(
                    "sharded_decode: torn %s stream: client %d OK tokens "
                    "%s != single-device reference %s" % (kind, c, toks,
                                                          ref))
            elif status in (srv.TIMEOUT, srv.UNAVAILABLE) and \
                    toks != ref[:len(toks)]:
                violations.append(
                    "sharded_decode: contaminated %s partial: client %d "
                    "%s tokens %s not a prefix of %s"
                    % (kind, c, status, toks, ref))
            elif status == srv.OVERLOADED and toks:
                violations.append("sharded_decode: shed stream carries %d "
                                  "token(s)" % len(toks))

    # router-level conservation
    keys = ("requests", "ok", "timeouts", "errors", "unavailable", "shed",
            "invalid", "unavailable_rejected")
    settle_until = time.monotonic() + 5.0
    while True:
        after = router.decode_stats.snapshot()
        d = {k: after[k] - before[k] for k in keys}
        terminal_sum = (d["ok"] + d["timeouts"] + d["errors"]
                        + d["unavailable"])
        if d["requests"] == terminal_sum or time.monotonic() >= settle_until:
            break
        time.sleep(0.005)
    if d["requests"] != terminal_sum:
        violations.append("sharded_decode: lost streams: %d admitted, %d "
                          "terminal" % (d["requests"], terminal_sum))
    if d["requests"] != tally["admitted"]:
        violations.append("sharded_decode: admission mismatch: router %d "
                          "vs clients %d" % (d["requests"],
                                             tally["admitted"]))
    for client_key, fleet_key in (("OK", "ok"), ("TIMEOUT", "timeouts"),
                                  ("ERROR", "errors"),
                                  ("UNAVAILABLE", "unavailable"),
                                  ("shed", "shed"),
                                  ("rejected", "unavailable_rejected")):
        if d[fleet_key] != tally[client_key]:
            violations.append("sharded_decode: %s mismatch: router %d vs "
                              "clients %d"
                              % (fleet_key, d[fleet_key],
                                 tally[client_key]))
    if d["errors"]:
        violations.append("sharded_decode: %d ERROR stream(s) with no "
                          "faults injected" % d["errors"])

    # pools whole on every shard + per-engine conservation + recompiles
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        engines = router.stats()["engines"].get(name, {})
        if all(s["kv"]["used"] == 0 and s["kv"]["reserved"] == 0
               and s["kv"]["live_sequences"] == 0
               for s in engines.values()):
            break
        time.sleep(0.005)
    engines = router.stats()["engines"].get(name, {})
    for rid, s in engines.items():
        kv = s["kv"]
        if kv["used"] != 0 or kv["reserved"] != 0 \
                or kv["live_sequences"] != 0:
            violations.append("sharded_decode: KV pool not whole on %s: %r"
                              % (rid, {k: kv[k] for k in
                                       ("used", "reserved",
                                        "live_sequences")}))
        if kv["allocated_total"] != kv["freed_total"]:
            violations.append("sharded_decode: KV leak on %s: allocated "
                              "%d != freed %d"
                              % (rid, kv["allocated_total"],
                                 kv["freed_total"]))
        if s["requests"] + s["imported"] != (
                s["ok"] + s["timeouts"] + s["errors"] + s["unavailable"]
                + s["handed_off"]):
            violations.append("sharded_decode: engine conservation broken "
                              "on %s: req %d + imported %d != ok %d + "
                              "to %d + err %d + unavail %d + handed %d"
                              % (rid, s["requests"], s["imported"],
                                 s["ok"], s["timeouts"], s["errors"],
                                 s["unavailable"], s["handed_off"]))
        if s["tp_degree"] != 2:
            violations.append("sharded_decode: engine on %s reports "
                              "tp_degree %d (want 2)"
                              % (rid, s["tp_degree"]))
        prev = before_eng.get(rid)
        if prev is not None and \
                s["cache"]["recompiles"] != prev["cache"]["recompiles"]:
            violations.append("sharded_decode: steady-state recompile on "
                              "%s: %d -> %d"
                              % (rid, prev["cache"]["recompiles"],
                                 s["cache"]["recompiles"]))

    # repair for the next seed, then replay probes against the
    # single-device references
    for rid in drained:
        if router.replicas().get(rid) == "DRAINING":
            router.enable(rid)
    if not router.wait_converged(timeout_s=10.0):
        violations.append("sharded_decode: placement never re-converged: "
                          "%r" % router.stats()["decode_models"])
    probe = router.submit_stream(name, list(prompts[0]),
                                 max_new_tokens=_DSHARD_MAX_NEW)
    probe.wait(_JOIN_TIMEOUT_S)
    status, tokens, _, _, err = probe.snapshot()
    if status != srv.OK or list(tokens) != refs[0]:
        violations.append("sharded_decode: post-repair greedy probe ended "
                          "%r (%r)" % (status, err))
    probe = router.submit_stream(name, list(prompts[1]),
                                 max_new_tokens=_DSHARD_MAX_NEW,
                                 temperature=_DSHARD_TEMP,
                                 top_k=_DSHARD_TOPK,
                                 seed=_DSHARD_SEED0 + 1)
    probe.wait(_JOIN_TIMEOUT_S)
    status, tokens, _, _, err = probe.snapshot()
    if status != srv.OK or list(tokens) != sam_refs[1]:
        violations.append("sharded_decode: post-repair sampled probe "
                          "ended %r (%r)" % (status, err))
    # settle so a late terminal hook can't straddle the next seed's
    # `before` snapshot
    settle_until = time.monotonic() + 5.0
    while time.monotonic() < settle_until:
        s = router.decode_stats.snapshot()
        if s["requests"] == (s["ok"] + s["timeouts"] + s["errors"]
                             + s["unavailable"]):
            break
        time.sleep(0.002)
    return violations


# ---------------------------------------------------------------------------
# scenario: disaggregated prefill/decode tier storm (disagg)
# ---------------------------------------------------------------------------

_DISAGG_PROMPTS = ((5, 3, 7, 1), (2, 6, 4), (9, 8, 1, 2, 3), (7, 7),
                   (1, 2, 3, 4, 5))
_DISAGG_MAX_NEW = 5
_DISAGG_TEMP = 0.8
_DISAGG_TOPK = 6
_DISAGG_SEED0 = 12000   # sampled stream of prompt i uses seed 12000 + i


def _build_disagg_fixture():
    """-> (disagg_router, engine_name, prompts, greedy_refs, sampled_refs).

    Two prefill-only replicas handing off at first token to two decode
    replicas — the smallest topology where killing one prefill AND
    draining one decode replica both leave a survivor.  All engines run
    the chunked path over the same seeded weights; the references come
    from a colocated chunked engine, so the scenario's bitwise claim is
    disaggregated-vs-colocated across the tier boundary.
    ``max_prompt_len`` leaves room above the longest prompt so a killed
    stream's prompt + emitted prefix can RE-ADMIT as a new prompt."""
    from ..serving.decode import DecodeEngine, TinyCausalLM
    from ..serving.disagg import DisaggRouter

    model_kw = dict(vocab_size=24, hidden=16, num_layers=1, num_heads=2,
                    max_len=24, seed=17)
    engine_kw = dict(max_slots=2, block_size=4, num_blocks=24,
                     max_prompt_len=12, max_new_tokens=_DISAGG_MAX_NEW,
                     max_queue=8, breaker_threshold=4,
                     breaker_backoff_ms=15.0, prefill_chunk=4)

    def prefill_factory(name):
        return DecodeEngine(TinyCausalLM(**model_kw), name=name,
                            prefill_only=True, **engine_kw)

    def decode_factory(name):
        return DecodeEngine(TinyCausalLM(**model_kw), name=name,
                            **engine_kw)

    router = DisaggRouter(prefill_replicas=2, decode_replicas=2,
                          failover_budget=2, breaker_threshold=3,
                          breaker_backoff_ms=10.0)
    router.load("dglm", prefill_factory, decode_factory,
                prefill_replicas=2, decode_replicas=2)
    ref_eng = DecodeEngine(TinyCausalLM(**model_kw), name="dgref",
                           **engine_kw)
    try:
        refs = [ref_eng.generate_reference(list(p),
                                           _DISAGG_MAX_NEW).tolist()
                for p in _DISAGG_PROMPTS]
        sam_refs = [ref_eng.generate_reference(
                        list(p), _DISAGG_MAX_NEW, temperature=_DISAGG_TEMP,
                        top_k=_DISAGG_TOPK,
                        seed=_DISAGG_SEED0 + i).tolist()
                    for i, p in enumerate(_DISAGG_PROMPTS)]
    finally:
        ref_eng.stop()
    return (router, "dglm", [list(p) for p in _DISAGG_PROMPTS], refs,
            sam_refs)


def _disagg_engine_snaps(router, name):
    """{"tier/rid": engine snapshot} across both tiers."""
    stats = router.stats()
    out = {}
    for tier in ("prefill", "decode"):
        for rid, s in stats[tier]["engines"].get(name, {}).items():
            out["%s/%s" % (tier, rid)] = s
    return out


def disagg_storm(router, name, prompts, refs, sam_refs, seed):
    """Storm over both tiers with a prefill kill AND a decode drain (the
    ``disagg`` scenario).

    Greedy and explicitly-seeded sampled streams are admitted at the
    prefill tier and hand off at first token to the decode tier while a
    disruptor KILLS one live prefill replica and DRAINS one live decode
    replica mid-run.  Invariants:

    * **no torn streams** — an OK stream's tokens equal the COLOCATED
      reference for its (prompt, seed) bitwise, across the tier handoff
      and any drain-driven decode→decode migration; TIMEOUT/UNAVAILABLE
      partials are strict prefixes; shed streams carry zero tokens;
    * **prefix re-admission** — a greedy stream the kill terminated
      UNAVAILABLE re-admits as prompt + prefix and continues the greedy
      reference path bitwise (the fencing protocol yields usable
      prefixes, not just non-torn ones);
    * **cross-tier conservation** — the prefill router's single ledger
      satisfies ``requests == ok + timeouts + errors + unavailable``
      and matches the client tally with zero ERROR streams; per-engine
      ``requests + imported == terminal + handed_off`` holds on every
      surviving engine of BOTH tiers;
    * **pools whole on both tiers** — every surviving engine drains to
      used == reserved == live_sequences == 0 with ``allocated_total ==
      freed_total``;
    * **zero steady-state recompiles** — first-token handoff, adoption,
      and the decode drain all ride warmed signatures on engines that
      lived the whole seed;
    * **repair + replay** — a fresh prefill replica joins (warmed
      before cutover), the drained decode replica re-enables, both
      placements re-converge, and one greedy plus one sampled probe
      reach OK bitwise-equal to the colocated references, with the
      cross-tier handoff counter demonstrably advanced.
    """
    from ..serving import server as srv

    violations = []
    rng = random.Random(seed ^ 0xD15A)
    before = router.prefill.decode_stats.snapshot()
    before_hand = router.stats_sink.snapshot()
    before_eng = _disagg_engine_snaps(router, name)

    n_clients, per_client = 3, 2
    plans = []   # [(timeout_ms or None, prompt_idx, sampled), ...]
    for c in range(n_clients):
        plan = []
        for s in range(per_client):
            tmo = rng.uniform(200.0, 1500.0) if rng.random() < 0.15 \
                else None
            plan.append((tmo, rng.randrange(len(prompts)),
                         rng.random() < 0.35))
        plans.append(plan)
    results = [[] for _ in plans]

    def client(c):
        for tmo, pi, sampled in plans[c]:
            if sampled:
                stream = router.submit_stream(
                    name, list(prompts[pi]),
                    max_new_tokens=_DISAGG_MAX_NEW, timeout_ms=tmo,
                    temperature=_DISAGG_TEMP, top_k=_DISAGG_TOPK,
                    seed=_DISAGG_SEED0 + pi)
            else:
                stream = router.submit_stream(
                    name, list(prompts[pi]),
                    max_new_tokens=_DISAGG_MAX_NEW, timeout_ms=tmo)
            if not stream.wait(_JOIN_TIMEOUT_S):
                violations.append("disagg: stream of client %d never "
                                  "terminated" % c)
            results[c].append((pi, sampled, stream))

    killed, drained = [], []

    def disruptor():
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            d = router.prefill.decode_stats.snapshot()
            if d["requests"] - before["requests"] >= 2:
                break
            time.sleep(0.002)
        # kill one prefill replica: streams still prefilling there fence
        # to UNAVAILABLE prefixes, streams already handed off must be
        # untouched (their pins were detached at handoff)
        p_live = [rid for rid, state
                  in sorted(router.prefill.replicas().items())
                  if state == "LIVE"]
        if len(p_live) < 2:
            violations.append("disagg: %d live prefill replica(s) before "
                              "the kill (want >= 2)" % len(p_live))
        else:
            rid_k = p_live[rng.randrange(len(p_live))]
            router.prefill.kill_replica(rid_k)
            killed.append(rid_k)
        # drain one decode replica: its adopted streams migrate to the
        # surviving decode engine via the fenced export/import protocol
        d_live = [rid for rid, state
                  in sorted(router.decode.replicas().items())
                  if state == "LIVE"]
        if len(d_live) < 2:
            violations.append("disagg: %d live decode replica(s) before "
                              "the drain (want >= 2)" % len(d_live))
        else:
            rid_d = d_live[rng.randrange(len(d_live))]
            router.decode.drain(rid_d)
            drained.append(rid_d)

    workers = [lambda c=c: client(c) for c in range(len(plans))]
    workers.append(disruptor)
    violations.extend(_spawn(workers))

    # client-side status + token integrity vs the colocated reference
    tally = {"admitted": 0, "OK": 0, "TIMEOUT": 0, "ERROR": 0,
             "UNAVAILABLE": 0, "shed": 0, "rejected": 0}
    readmit = None   # (prompt_idx, prefix) of a killed greedy stream
    for c in range(len(plans)):
        for pi, sampled, stream in results[c]:
            status, tokens, _, _, _err = stream.snapshot()
            if status is None:
                violations.append("disagg: client %d stream has no "
                                  "terminal status" % c)
                continue
            if stream.admitted:
                tally["admitted"] += 1
                if status not in (srv.OK, srv.TIMEOUT, srv.ERROR,
                                  srv.UNAVAILABLE):
                    violations.append("disagg: admitted stream ended %r"
                                      % status)
                    continue
                tally[status] += 1
            elif status == srv.OVERLOADED:
                tally["shed"] += 1
            elif status == srv.UNAVAILABLE:
                tally["rejected"] += 1
            else:
                violations.append("disagg: rejected stream ended %r"
                                  % status)
                continue
            ref = sam_refs[pi] if sampled else refs[pi]
            kind = "sampled" if sampled else "greedy"
            toks = list(tokens)
            if status == srv.OK and toks != ref:
                violations.append(
                    "disagg: torn %s stream: client %d OK tokens %s != "
                    "colocated reference %s" % (kind, c, toks, ref))
            elif status in (srv.TIMEOUT, srv.UNAVAILABLE) and \
                    toks != ref[:len(toks)]:
                violations.append(
                    "disagg: contaminated %s partial: client %d %s tokens "
                    "%s not a prefix of %s" % (kind, c, status, toks, ref))
            elif status == srv.OVERLOADED and toks:
                violations.append("disagg: shed stream carries %d "
                                  "token(s)" % len(toks))
            if readmit is None and not sampled and stream.admitted \
                    and status == srv.UNAVAILABLE \
                    and 0 < len(toks) < len(ref):
                readmit = (pi, toks)

    # cross-tier conservation on the prefill router's single ledger
    keys = ("requests", "ok", "timeouts", "errors", "unavailable", "shed",
            "invalid", "unavailable_rejected")
    settle_until = time.monotonic() + 5.0
    while True:
        after = router.prefill.decode_stats.snapshot()
        d = {k: after[k] - before[k] for k in keys}
        terminal_sum = (d["ok"] + d["timeouts"] + d["errors"]
                        + d["unavailable"])
        if d["requests"] == terminal_sum or time.monotonic() >= settle_until:
            break
        time.sleep(0.005)
    if d["requests"] != terminal_sum:
        violations.append("disagg: lost streams across the tier boundary: "
                          "%d admitted, %d terminal"
                          % (d["requests"], terminal_sum))
    if d["requests"] != tally["admitted"]:
        violations.append("disagg: admission mismatch: router %d vs "
                          "clients %d" % (d["requests"], tally["admitted"]))
    for client_key, fleet_key in (("OK", "ok"), ("TIMEOUT", "timeouts"),
                                  ("ERROR", "errors"),
                                  ("UNAVAILABLE", "unavailable"),
                                  ("shed", "shed"),
                                  ("rejected", "unavailable_rejected")):
        if d[fleet_key] != tally[client_key]:
            violations.append("disagg: %s mismatch: router %d vs clients "
                              "%d" % (fleet_key, d[fleet_key],
                                      tally[client_key]))
    if d["errors"]:
        violations.append("disagg: %d ERROR stream(s) with no faults "
                          "injected" % d["errors"])

    # pools whole + per-engine conservation + recompiles, on BOTH tiers
    # (blocks are freed before the terminal is tallied, so settle on the
    # conservation identity too, not just on empty pools)
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        snaps = _disagg_engine_snaps(router, name)
        if all(s["kv"]["used"] == 0 and s["kv"]["reserved"] == 0
               and s["kv"]["live_sequences"] == 0
               and s["requests"] + s["imported"] == (
                   s["ok"] + s["timeouts"] + s["errors"]
                   + s["unavailable"] + s["handed_off"])
               for s in snaps.values()):
            break
        time.sleep(0.005)
    snaps = _disagg_engine_snaps(router, name)
    for key, s in snaps.items():
        kv = s["kv"]
        if kv["used"] != 0 or kv["reserved"] != 0 \
                or kv["live_sequences"] != 0:
            violations.append("disagg: KV pool not whole on %s: %r"
                              % (key, {k: kv[k] for k in
                                       ("used", "reserved",
                                        "live_sequences")}))
        if kv["allocated_total"] != kv["freed_total"]:
            violations.append("disagg: KV leak on %s: allocated %d != "
                              "freed %d" % (key, kv["allocated_total"],
                                            kv["freed_total"]))
        if s["requests"] + s["imported"] != (
                s["ok"] + s["timeouts"] + s["errors"] + s["unavailable"]
                + s["handed_off"]):
            violations.append("disagg: engine conservation broken on %s: "
                              "req %d + imported %d != ok %d + to %d + "
                              "err %d + unavail %d + handed %d"
                              % (key, s["requests"], s["imported"],
                                 s["ok"], s["timeouts"], s["errors"],
                                 s["unavailable"], s["handed_off"]))
        prev = before_eng.get(key)
        if prev is not None and \
                s["cache"]["recompiles"] != prev["cache"]["recompiles"]:
            violations.append("disagg: steady-state recompile on %s: "
                              "%d -> %d"
                              % (key, prev["cache"]["recompiles"],
                                 s["cache"]["recompiles"]))

    # repair for the next seed: a fresh prefill replica joins (the
    # rebalancer warms its engine before placement commits), the drained
    # decode replica re-enables, then replay probes cross the boundary
    if killed:
        router.prefill.add_replica()
    for rid in drained:
        if router.decode.replicas().get(rid) == "DRAINING":
            router.decode.enable(rid)
    if not router.prefill.wait_converged(timeout_s=10.0):
        violations.append("disagg: prefill placement never re-converged: "
                          "%r" % router.prefill.stats()["decode_models"])
    if not router.decode.wait_converged(timeout_s=10.0):
        violations.append("disagg: decode placement never re-converged: "
                          "%r" % router.decode.stats()["decode_models"])
    probe = router.submit_stream(name, list(prompts[0]),
                                 max_new_tokens=_DISAGG_MAX_NEW)
    probe.wait(_JOIN_TIMEOUT_S)
    status, tokens, _, _, err = probe.snapshot()
    if status != srv.OK or list(tokens) != refs[0]:
        violations.append("disagg: post-repair greedy probe ended %r (%r)"
                          % (status, err))
    probe = router.submit_stream(name, list(prompts[1]),
                                 max_new_tokens=_DISAGG_MAX_NEW,
                                 temperature=_DISAGG_TEMP,
                                 top_k=_DISAGG_TOPK,
                                 seed=_DISAGG_SEED0 + 1)
    probe.wait(_JOIN_TIMEOUT_S)
    status, tokens, _, _, err = probe.snapshot()
    if status != srv.OK or list(tokens) != sam_refs[1]:
        violations.append("disagg: post-repair sampled probe ended %r (%r)"
                          % (status, err))
    if readmit is not None:
        # the kill's prefix must RE-ADMIT and continue the greedy path:
        # greedy decode is deterministic, so prompt + prefix decodes to
        # exactly the reference's remaining tokens
        pi, prefix = readmit
        want = refs[pi][len(prefix):]
        probe = router.submit_stream(name, list(prompts[pi]) + prefix,
                                     max_new_tokens=len(want))
        probe.wait(_JOIN_TIMEOUT_S)
        status, tokens, _, _, err = probe.snapshot()
        if status != srv.OK or list(tokens) != want:
            violations.append("disagg: re-admitted prefix diverged: %r "
                              "tokens %r != %r (%r)"
                              % (status, list(tokens), want, err))
    hand = router.stats_sink.snapshot()
    if hand["handoffs"] - before_hand["handoffs"] < 1:
        violations.append("disagg: no cross-tier handoff happened all "
                          "seed (%d -> %d)"
                          % (before_hand["handoffs"], hand["handoffs"]))
    # settle so a late terminal hook can't straddle the next seed's
    # `before` snapshot
    settle_until = time.monotonic() + 5.0
    while time.monotonic() < settle_until:
        s = router.prefill.decode_stats.snapshot()
        if s["requests"] == (s["ok"] + s["timeouts"] + s["errors"]
                             + s["unavailable"]):
            break
        time.sleep(0.002)
    return violations


# ---------------------------------------------------------------------------
# scenario 14: memory-pressure storm on the paged KV pool + byte accountant
# ---------------------------------------------------------------------------

def mem_storm(seed, n_threads=4, rounds=3):
    """Memory-pressure storm: the runtime half of the mxmem lint pass.

    A deliberately tiny ``PagedKVCache`` (16 allocatable 512-byte blocks)
    is driven to near-exhaustion by concurrent sequence lifecycles —
    ``reserve`` (some shed) -> ``ensure_capacity`` growth -> prefix
    ``register``/re-admission (the handoff-import path) -> copy-on-write
    ``writable`` forks -> ``free_seq`` — while LRU eviction recycles
    cached prefix pages underneath and chaos stretches every lock edge.

    Invariants:
    * **attachment conservation** — once every sequence is freed,
      ``allocated_total == freed_total`` and no block stays in use;
    * **twin exactness** — the byte accountant's region mirrors the
      cache ledger exactly: ``allocs == allocated_total``,
      ``frees == freed_total``, ``alloc_bytes == allocated_total *
      block_bytes``, and ``live_bytes == 0`` after the drain;
    * **declared-budget peak** — ``peak_bytes`` never exceeds the
      admission worst case declared below (each thread's one live
      sequence attaches at most its shared prefix + its full
      reservation), and the cache's own ``peak_used`` never exceeds
      physical capacity — the no-mid-stream-OOM contract MEM004 makes
      static;
    * **activity** — the storm demonstrably allocated and shared;
    * **no deadlock** — every worker joins.
    """
    from .. import memory_accounting
    from ..serving.decode.kv_cache import PagedKVCache

    violations = []
    region = "mem_storm:%d:%d" % (seed, time.monotonic_ns() % (1 << 30))
    cache = PagedKVCache(2, 17, 4, 2, 4, account_region=region)
    rng = random.Random(seed ^ 0x3E3)
    # three 12-token prompts (3 full blocks each): enough overlap for
    # prefix hits and CoW forks, enough variety for eviction pressure
    prompts = [[rng.randrange(1000) for _ in range(12)] for _ in range(3)]
    res_blocks = 4   # per-sequence reservation (4 threads x 4 = capacity)
    shed = [0]

    def lifecycle(tid):
        for r in range(rounds):
            seq = "m%d_%d_%d" % (seed, tid, r)
            prompt = prompts[(tid + r) % len(prompts)]
            res = cache.reserve(seq, res_blocks, prompt=prompt)
            if not res:
                shed[0] += 1      # benign: admission shed under pressure
                continue
            cache.ensure_capacity(seq, len(prompt))
            cache.writable(seq, 0)          # forks iff the page is shared
            cache.register_prefix(seq, prompt)
            cache.free_seq(seq)

    violations.extend(_spawn([lambda t=t: lifecycle(t)
                              for t in range(n_threads)]))

    stats = cache.stats()
    mem = memory_accounting.memory_counters().get(region, {})
    bb = cache.block_bytes
    if stats["allocated_total"] != stats["freed_total"]:
        violations.append("mem: KV ledger leaked: allocated %d != freed %d"
                          % (stats["allocated_total"], stats["freed_total"]))
    if stats["used"] != 0 or stats["live_sequences"] != 0:
        violations.append("mem: pool not drained: used=%d live_sequences=%d"
                          % (stats["used"], stats["live_sequences"]))
    if mem.get("allocs", -1) != stats["allocated_total"]:
        violations.append("mem: accountant allocs %r != cache "
                          "allocated_total %d"
                          % (mem.get("allocs"), stats["allocated_total"]))
    if mem.get("frees", -1) != stats["freed_total"]:
        violations.append("mem: accountant frees %r != cache freed_total %d"
                          % (mem.get("frees"), stats["freed_total"]))
    if mem.get("alloc_bytes", -1) != stats["allocated_total"] * bb:
        violations.append("mem: accountant alloc_bytes %r != %d x %dB"
                          % (mem.get("alloc_bytes"),
                             stats["allocated_total"], bb))
    if mem.get("live_bytes", -1) != 0:
        violations.append("mem: accountant live_bytes %r != 0 after drain"
                          % (mem.get("live_bytes"),))
    # admission worst case: each thread's single live sequence holds at
    # most its shared prefix (3 blocks) plus its full reservation
    budget = n_threads * (3 + res_blocks) * bb
    if mem.get("peak_bytes", 0) > budget:
        violations.append("mem: peak_bytes %r over the declared budget %d"
                          % (mem.get("peak_bytes"), budget))
    if stats["peak_used"] > cache.capacity():
        violations.append("mem: peak_used %d over physical capacity %d"
                          % (stats["peak_used"], cache.capacity()))
    if stats["allocated_total"] == 0:
        violations.append("mem: storm allocated nothing (shed %d)"
                          % shed[0])
    return violations


# ---------------------------------------------------------------------------
# scenario 15: generation-fenced rolling weight deployment (deploy)
# ---------------------------------------------------------------------------

_DEPLOY_PROMPT = (3, 1, 2)
_DEPLOY_MAX_NEW = 5
_DEPLOY_WSEEDS = {"A": 21, "B": 22}   # weight seed per generation flavor
_DEPLOY_SITES = ("deploy.resolve", "deploy.warmup", "deploy.cutover",
                 "deploy.commit")
_DEPLOY_MODEL_KW = dict(vocab_size=24, hidden=16, num_layers=1, num_heads=2,
                        max_len=24)
_DEPLOY_ENGINE_KW = dict(max_slots=2, block_size=4, num_blocks=24,
                         max_prompt_len=12, max_new_tokens=_DEPLOY_MAX_NEW,
                         max_queue=8, breaker_threshold=4,
                         breaker_backoff_ms=15.0)


def _deploy_save(prefix, epoch, flavor):
    """Publish TinyCausalLM weights of ``flavor`` as checkpoint ``epoch``
    — manifest-committed, exactly like a trainer's ``do_checkpoint``."""
    from .. import model as model_mod
    from .. import symbol as sym_mod
    from ..serving.decode import TinyCausalLM
    lm = TinyCausalLM(seed=_DEPLOY_WSEEDS[flavor], **_DEPLOY_MODEL_KW)
    model_mod.save_checkpoint(prefix, epoch, sym_mod.Variable("data"),
                              dict(lm._params), {})


def _deploy_builder(srv_name, arg_params, aux_params, generation):
    """DeploymentController engine builder: checkpoint params -> warmed
    generation-tagged engine."""
    from ..serving.decode import DecodeEngine, TinyCausalLM
    lm = TinyCausalLM(params=arg_params, **_DEPLOY_MODEL_KW)
    return DecodeEngine(lm, name=srv_name, generation=generation,
                        **_DEPLOY_ENGINE_KW)


def _build_deploy_fixture():
    """-> (router, "dplm", prefix, refs, state).

    A 2-replica decode fleet first deployed at checkpoint epoch 1
    (weight flavor "A").  Each seed's storm publishes the next epoch
    with the OTHER flavor's weights and rolls it live — or crashes the
    controller mid-roll at a seeded fault point.  ``refs`` holds the
    per-flavor greedy reference, so "every stream finishes against ONE
    weight generation" is checkable bitwise: any token list that is
    neither flavor's reference (nor a strict prefix of one) is torn or
    mixed-generation output."""
    import os
    import tempfile
    from ..serving.decode import DecodeEngine, TinyCausalLM
    from ..serving.deploy import DeploymentController
    from ..serving.fleet import FleetRouter

    tmpdir = tempfile.mkdtemp(prefix="mxstress-deploy-")
    prefix = os.path.join(tmpdir, "ck")
    _deploy_save(prefix, 1, "A")
    refs = {}
    for flavor, wseed in sorted(_DEPLOY_WSEEDS.items()):
        eng = DecodeEngine(TinyCausalLM(seed=wseed, **_DEPLOY_MODEL_KW),
                           name="dpref-%s" % flavor, **_DEPLOY_ENGINE_KW)
        try:
            refs[flavor] = eng.generate_reference(
                list(_DEPLOY_PROMPT), _DEPLOY_MAX_NEW).tolist()
        finally:
            eng.stop()
    if refs["A"] == refs["B"]:
        raise RuntimeError("deploy fixture weight seeds produce identical "
                           "outputs; the bitwise generation check is vacuous")
    router = FleetRouter(replicas=2, failover_budget=2)
    router.load_decode(
        "dplm",
        lambda n: DecodeEngine(TinyCausalLM(seed=_DEPLOY_WSEEDS["A"],
                                            **_DEPLOY_MODEL_KW),
                               name=n, **_DEPLOY_ENGINE_KW),
        replicas=2)
    ctl = DeploymentController(router, prefix,
                               engines={"dplm": _deploy_builder})
    report = ctl.poll()
    if report is None or report["status"] != "deployed":
        raise RuntimeError("deploy fixture: initial roll to epoch 1 "
                           "failed: %r" % (report,))
    state = {"dir": tmpdir, "epoch": 1, "flavors": {1: "A"}}
    return (router, "dplm", prefix, refs, state)


def deploy_storm(router, name, prefix, refs, state, seed):
    """Rolling-deployment storm (the ``deploy`` scenario).

    Each seed publishes the next checkpoint epoch carrying the OTHER
    weight flavor, then either KILLS the controller at a seeded
    ``deploy.*`` fault point (even seeds, site rotating over all four)
    or rolls the swap for real under concurrent client streams — some
    seeds racing a ``kill_replica`` against the controller.  Invariants:

    * **crash-safe** — a controller killed at ANY fault point leaves the
      fleet HEALTHY and serving the OLD generation bitwise, with no
      staging debris after ``recover()``; the queued generation then
      deploys cleanly;
    * **single-generation streams** — every OK stream's tokens equal ONE
      flavor's greedy reference exactly; TIMEOUT/UNAVAILABLE partials
      are strict prefixes of one flavor (never an interleaving);
    * **conservation** — the router ledger settles to ``requests == ok +
      timeouts + errors + unavailable`` with zero ERROR streams, and
      every surviving engine's KV pool drains whole;
    * **flexible verdict under replica kill** — a kill racing the swap
      may abort it or let it finish; either way the fleet re-converges
      on ONE consistent generation matching the controller's report and
      probes bitwise on that generation's reference;
    * **zero steady-state recompiles** — post-swap probes ride warmed
      signatures on every surviving engine.
    """
    from .. import faults
    from ..base import MXNetError
    from ..serving import server as srv
    from ..serving.deploy import DeploymentController
    from ..serving.health import HEALTHY

    violations = []
    rng = random.Random(seed ^ 0xDE7)

    def cur_epoch():
        return router.stats()["deploy"]["generation"]

    def probe(flavor, label):
        stream = router.submit_stream(name, list(_DEPLOY_PROMPT),
                                      max_new_tokens=_DEPLOY_MAX_NEW)
        if not stream.wait(_JOIN_TIMEOUT_S):
            violations.append("deploy: %s probe never terminated" % label)
            return
        status, tokens, _, _, err = stream.snapshot()
        if status != srv.OK or list(tokens) != refs[flavor]:
            violations.append(
                "deploy: %s probe ended %r tokens %r != flavor-%s "
                "reference %r (%r)" % (label, status, list(tokens),
                                       flavor, refs[flavor], err))

    old_epoch = cur_epoch()
    old_flavor = state["flavors"][old_epoch]
    new_flavor = "B" if old_flavor == "A" else "A"
    state["epoch"] += 1
    new_epoch = state["epoch"]
    state["flavors"][new_epoch] = new_flavor
    _deploy_save(prefix, new_epoch, new_flavor)
    ctl = DeploymentController(router, prefix,
                               engines={name: _deploy_builder})

    if seed % 2 == 0:
        # kill the controller at a seeded fault point: the fleet must
        # keep serving the OLD generation as if nothing happened
        site = _DEPLOY_SITES[(seed // 2) % len(_DEPLOY_SITES)]
        plan = faults.FaultPlan(seed).add(site, kind="crash", times=1)
        crashed = False
        try:
            with faults.plan(plan):
                ctl.poll()
        except faults.SimulatedCrash:
            crashed = True
        if not crashed:
            violations.append("deploy: planted crash at %s never fired"
                              % site)
        ctl = DeploymentController(router, prefix,
                                   engines={name: _deploy_builder})
        ctl.recover()
        if cur_epoch() != old_epoch:
            violations.append("deploy: crash at %s left generation %r "
                              "(want old %r)"
                              % (site, cur_epoch(), old_epoch))
        if router.health() != HEALTHY:
            violations.append("deploy: fleet %r (not HEALTHY) after a "
                              "crash at %s" % (router.health(), site))
        st = router.stats()["deploy"]
        if st["in_progress"] is not None or st["retiring"]:
            violations.append("deploy: staging/retiring debris after "
                              "recover() from a crash at %s: %r"
                              % (site, st))
        probe(old_flavor, "post-crash(%s)" % site)

    # the swap itself, under concurrent client streams — and, on some odd
    # seeds, a replica kill racing the controller mid-swap.  Settle the
    # ledger first so a probe's late terminal hook can't straddle the
    # conservation window.
    settle_until = time.monotonic() + 5.0
    while time.monotonic() < settle_until:
        snap = router.decode_stats.snapshot()
        if snap["requests"] == (snap["ok"] + snap["timeouts"]
                                + snap["errors"] + snap["unavailable"]):
            break
        time.sleep(0.002)
    before = router.decode_stats.snapshot()
    kill_mode = seed % 2 == 1 and rng.random() < 0.4
    results, swap_report, swap_error, killed = [], [], [], []

    def clients():
        for i in range(4):
            slow = (lambda t: time.sleep(0.004)) if i % 2 == 0 else None
            results.append(router.submit_stream(
                name, list(_DEPLOY_PROMPT),
                max_new_tokens=_DEPLOY_MAX_NEW, on_token=slow))
            time.sleep(0.002)
        for stream in results:
            if not stream.wait(_JOIN_TIMEOUT_S):
                violations.append("deploy: client stream never terminated")

    def swapper():
        try:
            swap_report.append(ctl.poll())
        except MXNetError as exc:
            swap_error.append(str(exc))   # aborted by a racing kill: legal

    def killer():
        time.sleep(rng.random() * 0.05)
        live = [rid for rid, st in sorted(router.replicas().items())
                if st == "LIVE"]
        if len(live) >= 2:
            rid = live[rng.randrange(len(live))]
            router.kill_replica(rid)
            killed.append(rid)

    workers = [clients, swapper]
    if kill_mode:
        workers.append(killer)
    violations.extend(_spawn(workers))

    # repair + debris sweep, then the fleet must sit on ONE generation
    if killed:
        router.add_replica()
    DeploymentController(router, prefix,
                         engines={name: _deploy_builder}).recover()
    if not router.wait_converged(timeout_s=10.0):
        violations.append("deploy: placement never re-converged: %r"
                          % router.stats()["decode_models"])
    final = cur_epoch()
    if final not in (old_epoch, new_epoch):
        violations.append("deploy: fleet on unexpected generation %r "
                          "(want %r or %r)" % (final, old_epoch, new_epoch))
    report = swap_report[0] if swap_report else None
    if report is not None and report["status"] == "deployed" \
            and final != new_epoch:
        violations.append("deploy: controller reported 'deployed' to %r "
                          "but the fleet serves %r" % (new_epoch, final))
    if report is None and not swap_error and not killed:
        violations.append("deploy: swap neither reported nor errored "
                          "with no kill in play")

    # single-generation token integrity: OK == one flavor's reference
    # bitwise; partials are strict prefixes of one flavor
    for stream in results:
        status, tokens, _, _, _err = stream.snapshot()
        toks = list(tokens)
        if status == srv.OK:
            if toks != refs[old_flavor] and toks != refs[new_flavor]:
                violations.append("deploy: torn/mixed-generation OK "
                                  "stream: %r (refs %r / %r)"
                                  % (toks, refs[old_flavor],
                                     refs[new_flavor]))
        elif status in (srv.TIMEOUT, srv.UNAVAILABLE):
            if toks != refs[old_flavor][:len(toks)] \
                    and toks != refs[new_flavor][:len(toks)]:
                violations.append("deploy: contaminated %s partial: %r"
                                  % (status, toks))
        elif status == srv.OVERLOADED:
            if toks:
                violations.append("deploy: shed stream carries %d "
                                  "token(s)" % len(toks))
        elif status is not None:
            violations.append("deploy: stream ended %r" % status)

    # conservation on the router ledger (late terminal hooks settle)
    keys = ("requests", "ok", "timeouts", "errors", "unavailable")
    settle_until = time.monotonic() + 5.0
    while True:
        after = router.decode_stats.snapshot()
        d = {k: after[k] - before[k] for k in keys}
        terminal_sum = (d["ok"] + d["timeouts"] + d["errors"]
                        + d["unavailable"])
        if d["requests"] == terminal_sum \
                or time.monotonic() >= settle_until:
            break
        time.sleep(0.005)
    if d["requests"] != terminal_sum:
        violations.append("deploy: lost streams across the swap: %d "
                          "admitted, %d terminal"
                          % (d["requests"], terminal_sum))
    if d["errors"]:
        violations.append("deploy: %d ERROR stream(s) with no faults "
                          "injected" % d["errors"])

    # KV pools whole on every surviving engine
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        snaps = router.stats()["engines"].get(name, {})
        if all(s["kv"]["used"] == 0 and s["kv"]["reserved"] == 0
               and s["kv"]["live_sequences"] == 0 for s in snaps.values()):
            break
        time.sleep(0.005)
    snaps = router.stats()["engines"].get(name, {})
    for rid, s in sorted(snaps.items()):
        kv = s["kv"]
        if kv["used"] != 0 or kv["reserved"] != 0 \
                or kv["live_sequences"] != 0:
            violations.append("deploy: KV pool not whole on %s: %r"
                              % (rid, {k: kv[k] for k in
                                       ("used", "reserved",
                                        "live_sequences")}))
        if kv["allocated_total"] != kv["freed_total"]:
            violations.append("deploy: KV leak on %s: allocated %d != "
                              "freed %d" % (rid, kv["allocated_total"],
                                            kv["freed_total"]))

    # post-swap probe on the committed generation, then zero recompiles
    final_flavor = state["flavors"][final]
    recomp0 = {rid: s["cache"]["recompiles"]
               for rid, s in sorted(snaps.items())}
    probe(final_flavor, "post-swap")
    for rid, s in sorted(router.stats()["engines"].get(name, {}).items()):
        if rid in recomp0 and s["cache"]["recompiles"] != recomp0[rid]:
            violations.append("deploy: steady-state recompile on %s: "
                              "%d -> %d" % (rid, recomp0[rid],
                                            s["cache"]["recompiles"]))
    return violations


# ---------------------------------------------------------------------------
# orchestration
# ---------------------------------------------------------------------------

SCENARIOS = ("serving", "registry", "cache", "bulk", "feed", "faults",
             "crash", "decode", "fleet", "decode_fleet", "decode_prefix",
             "sharded_decode", "disagg", "mem", "deploy")


def stress(seeds=SMOKE_SEEDS, scenarios=SCENARIOS, p_preempt=0.25,
           max_sleep_ms=0.5, n_clients=4, per_client=3, max_queue=2,
           log=None):
    """Run the invariant suite under every seed; -> report dict.

    ``report["violations"]`` is the flat total; zero means every seeded
    interleaving preserved every invariant."""
    sched = ChaosScheduler(0, p_preempt=p_preempt, max_sleep_ms=max_sleep_ms)
    report = {"seeds": {}, "violations": 0, "preemptions": 0}
    t0 = time.monotonic()
    with chaos(sched):
        # fixtures are warmup-compiled, so each is built only when a
        # requested scenario actually drives it
        needs_server = bool({"serving", "registry", "cache", "faults"}
                            & set(scenarios))
        server = name = net = inputs = expected = None
        if needs_server:
            server, name, net, inputs, expected = _build_fixture(
                n_clients, max_queue)
        decode_fixture = (_build_decode_fixture()
                          if "decode" in scenarios else None)
        fleet_fixture = (_build_fleet_fixture(n_clients)
                         if "fleet" in scenarios else None)
        dfleet_fixture = (_build_decode_fleet_fixture()
                          if "decode_fleet" in scenarios else None)
        dprefix_fixture = (_build_decode_prefix_fixture()
                           if "decode_prefix" in scenarios else None)
        dshard_fixture = (_build_sharded_decode_fixture()
                          if "sharded_decode" in scenarios else None)
        disagg_fixture = (_build_disagg_fixture()
                          if "disagg" in scenarios else None)
        deploy_fixture = (_build_deploy_fixture()
                          if "deploy" in scenarios else None)
        try:
            for seed in seeds:
                sched.reseed(seed)
                per_seed = {}
                if "serving" in scenarios:
                    per_seed["serving"] = serving_storm(
                        server, name, inputs, expected, seed,
                        per_client=per_client)
                if "registry" in scenarios:
                    per_seed["registry"] = registry_churn(
                        server, name, net, inputs, seed)
                if "cache" in scenarios:
                    per_seed["cache"] = cache_stats_hammer(server, name,
                                                           seed)
                if "bulk" in scenarios:
                    per_seed["bulk"] = bulk_scopes(seed)
                if "feed" in scenarios:
                    per_seed["feed"] = feed_pipeline(seed)
                if "faults" in scenarios:
                    per_seed["faults"] = fault_storm(
                        server, name, inputs, expected, seed,
                        per_client=per_client)
                if "crash" in scenarios:
                    per_seed["crash"] = crash_sweep(seed)
                if decode_fixture is not None:
                    per_seed["decode"] = decode_storm(
                        decode_fixture[0], decode_fixture[1],
                        decode_fixture[2], seed)
                if fleet_fixture is not None:
                    per_seed["fleet"] = fleet_storm(
                        fleet_fixture[0], fleet_fixture[1],
                        fleet_fixture[2], fleet_fixture[3], seed,
                        per_client=per_client)
                if dfleet_fixture is not None:
                    per_seed["decode_fleet"] = decode_fleet_storm(
                        dfleet_fixture[0], dfleet_fixture[1],
                        dfleet_fixture[2], dfleet_fixture[3], seed)
                if dprefix_fixture is not None:
                    per_seed["decode_prefix"] = decode_prefix_storm(
                        dprefix_fixture[0], dprefix_fixture[1],
                        dprefix_fixture[2], dprefix_fixture[3],
                        dprefix_fixture[4], seed)
                if dshard_fixture is not None:
                    per_seed["sharded_decode"] = sharded_decode_storm(
                        dshard_fixture[0], dshard_fixture[1],
                        dshard_fixture[2], dshard_fixture[3],
                        dshard_fixture[4], seed)
                if disagg_fixture is not None:
                    per_seed["disagg"] = disagg_storm(
                        disagg_fixture[0], disagg_fixture[1],
                        disagg_fixture[2], disagg_fixture[3],
                        disagg_fixture[4], seed)
                if "mem" in scenarios:
                    per_seed["mem"] = mem_storm(seed)
                if deploy_fixture is not None:
                    per_seed["deploy"] = deploy_storm(
                        deploy_fixture[0], deploy_fixture[1],
                        deploy_fixture[2], deploy_fixture[3],
                        deploy_fixture[4], seed)
                n = sum(len(v) for v in per_seed.values())
                report["seeds"][seed] = per_seed
                report["violations"] += n
                if log is not None:
                    log("seed %3d: %s (%d preemption(s) so far)"
                        % (seed, "ok" if not n else "%d VIOLATION(S)" % n,
                           sched.preemptions))
        finally:
            sched.enabled = False
            if server is not None:
                server.stop()
            if decode_fixture is not None:
                decode_fixture[0].stop()
            if fleet_fixture is not None:
                fleet_fixture[0].stop()
            if dfleet_fixture is not None:
                dfleet_fixture[0].stop()
            if dprefix_fixture is not None:
                dprefix_fixture[0].stop()
            if dshard_fixture is not None:
                dshard_fixture[0].stop()
            if disagg_fixture is not None:
                disagg_fixture[0].stop()
            if deploy_fixture is not None:
                deploy_fixture[0].stop()
                import shutil
                shutil.rmtree(deploy_fixture[4]["dir"], ignore_errors=True)
    report["preemptions"] = sched.preemptions
    report["elapsed_s"] = time.monotonic() - t0
    return report
