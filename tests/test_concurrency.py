"""Concurrency invariants under adversarial schedules (tier-1).

Wires ``tools/mxstress.py --smoke`` into the suite: the serving storm /
registry churn / cache-stats hammer / bulk-scope scenarios run under 25
seeded preemption patterns and every invariant must hold.  Plus direct
regression tests for the two concurrency fixes this harness motivated:
the Request completion race (deadline expiry vs batch completion) and the
``engine.bulk`` thread-local scope.
"""
import threading
import time

import numpy as np

from mxnet_tpu import engine
from mxnet_tpu.analysis import schedule
from mxnet_tpu.serving.batcher import Request


# ---------------------------------------------------------------------------
# the tier-1 smoke: 25 seeded interleavings, zero violations
# ---------------------------------------------------------------------------

def test_stress_smoke_25_seeds_zero_violations():
    # the five concurrency scenarios; the fault-injection pair ("faults",
    # "crash") has its own tier-1 gate in tests/test_faults.py so the two
    # smokes stay independently budgeted
    report = schedule.stress(seeds=schedule.SMOKE_SEEDS,
                             scenarios=("serving", "registry", "cache",
                                        "bulk", "feed"))
    flat = ["seed %s [%s] %s" % (seed, scen, v)
            for seed, per_seed in report["seeds"].items()
            for scen, violations in per_seed.items()
            for v in violations]
    assert report["violations"] == 0, "\n".join(flat)
    # the harness must actually have perturbed something, or the pass is
    # vacuous
    assert report["preemptions"] > 100
    assert len(report["seeds"]) == 25


# ---------------------------------------------------------------------------
# Request completion race (serving/batcher.py): first completion wins,
# atomically — a TIMEOUT observed by anyone must never carry outputs
# ---------------------------------------------------------------------------

def _race_once():
    req = Request((np.zeros(2, np.float32),),
                  deadline=time.monotonic() + 0.001)
    outs = [np.ones(2, np.float32)]
    wins = []
    barrier = threading.Barrier(2)

    def worker():
        barrier.wait()
        if req.complete("OK", outputs=outs):
            wins.append("OK")

    def expirer():
        barrier.wait()
        if req.complete("TIMEOUT"):
            wins.append("TIMEOUT")

    ts = [threading.Thread(target=worker), threading.Thread(target=expirer)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(5)
    status, outputs, latency_ms, error = req.snapshot()
    assert len(wins) == 1, "both completions claimed the request"
    assert status == wins[0]
    if status == "TIMEOUT":
        assert outputs is None, "TIMEOUT result carries the OK outputs"
    else:
        assert outputs is outs
    assert latency_ms is not None
    assert req.wait(0)   # event set exactly after the terminal state
    return status


def test_request_completion_race_first_wins_atomically():
    sched = schedule.ChaosScheduler(0, p_preempt=0.5, max_sleep_ms=0.2)
    seen = set()
    with schedule.chaos(sched):
        for seed in range(60):
            sched.reseed(seed)
            seen.add(_race_once())
    # under 60 seeded schedules both orders should win at least once
    # (observed split is ~80/20); if not, the race isn't being exercised
    # and this test is vacuous
    assert seen == {"OK", "TIMEOUT"}, seen


def test_request_snapshot_is_atomic_under_concurrent_completion():
    """A reader polling snapshot() must never observe a half-written
    terminal state (status without its fields)."""
    sched = schedule.ChaosScheduler(7, p_preempt=0.5, max_sleep_ms=0.2)
    with schedule.chaos(sched):
        for seed in range(15):
            sched.reseed(seed)
            req = Request((np.zeros(2, np.float32),))
            outs = [np.ones(2, np.float32)]
            torn = []

            def reader():
                while True:
                    status, outputs, latency_ms, _ = req.snapshot()
                    if status is None:
                        continue
                    if status == "OK" and (outputs is None
                                           or latency_ms is None):
                        torn.append(status)
                    return

            t = threading.Thread(target=reader)
            t.start()
            req.complete("OK", outputs=outs)
            t.join(5)
            assert not t.is_alive()
            assert torn == []


# ---------------------------------------------------------------------------
# engine.bulk: per-thread dynamic scope (the CON102 exemplar fix)
# ---------------------------------------------------------------------------

def test_bulk_size_is_thread_local():
    results = {}

    def worker(tid, size):
        with engine.bulk(size):
            time.sleep(0.01)   # overlap every scope with every other
            results[tid] = engine.bulk_size()
        results["after-%d" % tid] = engine.bulk_size()

    threads = [threading.Thread(target=worker, args=(i, 100 + i))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(5)
    for i in range(4):
        assert results[i] == 100 + i, "bulk scope leaked across threads"
        assert results["after-%d" % i] == 15
    assert engine.bulk_size() == 15   # main thread untouched throughout


def test_set_bulk_size_returns_previous():
    prev = engine.set_bulk_size(3)
    try:
        assert prev == 15
        assert engine.bulk_size() == 3
    finally:
        engine.set_bulk_size(prev)


# ---------------------------------------------------------------------------
# harness self-checks: chaos wrappers keep lock semantics
# ---------------------------------------------------------------------------

def test_chaos_locks_preserve_mutual_exclusion():
    sched = schedule.ChaosScheduler(3, p_preempt=0.5, max_sleep_ms=0.1)
    with schedule.chaos(sched):
        lock = threading.Lock()
        cond = threading.Condition()
        event = threading.Event()
    counter = {"n": 0}

    def bump():
        for _ in range(50):
            with lock:
                n = counter["n"]
                counter["n"] = n + 1

    ts = [threading.Thread(target=bump) for _ in range(3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(10)
    assert counter["n"] == 150

    # condition + event round-trip through the wrapped primitives
    hits = []

    def waiter():
        with cond:
            cond.wait(5)
            hits.append(1)
        event.set()

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.02)
    with cond:
        cond.notify_all()
    assert event.wait(5)
    t.join(5)
    assert hits == [1]
    assert sched.preemptions > 0


def test_preemption_source_can_be_reentered_by_a_finalizer():
    """The collector may run a finalizer on a thread that is inside
    ``maybe_preempt()``, and ``DeviceFeed.__del__`` takes a wrapped lock
    there, which re-enters it.  Under a lock of the scheduler's own the
    thread deadlocked against itself and every other thread of the run
    queued behind it: the hang the 25-seed smoke showed on a loaded box."""
    sched = schedule.ChaosScheduler(0, p_preempt=1.0, max_sleep_ms=0.0)
    lock = schedule._ChaosLock(sched)
    draw, entered = sched._rng.random, []

    def draw_with_a_finalizer_inside():
        if not entered:
            entered.append(True)
            with lock:              # what close() does from __del__
                pass
        return draw()

    sched._rng.random = draw_with_a_finalizer_inside
    t = threading.Thread(target=sched.maybe_preempt, daemon=True)
    t.start()
    t.join(10)
    assert not t.is_alive(), "maybe_preempt() deadlocked on re-entry"
    assert sched.preemptions == 3     # the outer call, acquire, release


def test_stress_detects_unguarded_shared_state():
    """Meta-test: chaos preemption must FIND a planted race, or the
    smoke's green result is meaningless.

    The planted bug is the classic read-under-lock / write-outside-lock
    split: the unguarded window is a couple of bytecodes wide, but the
    chaos lock's release-edge preemption lands exactly inside it, so the
    harness must surface lost updates that plain scheduling rarely hits.
    """
    sched = schedule.ChaosScheduler(0, p_preempt=0.5, max_sleep_ms=0.3)

    class Racy:
        def __init__(self):
            self.lock = threading.Lock()   # chaos-wrapped under the patch
            self.n = 0
            self.barrier = threading.Barrier(4)

        def bump(self):
            self.barrier.wait()
            for _ in range(150):
                with self.lock:
                    n = self.n
                self.n = n + 1     # BUG: modify-write escapes the lock

    found = False
    with schedule.chaos(sched):
        for seed in range(10):
            sched.reseed(seed)
            racy = Racy()
            ts = [threading.Thread(target=racy.bump) for _ in range(4)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(60)
            if racy.n != 4 * 150:
                found = True
                break
    assert found, "planted lost-update race never observed under chaos"
    assert sched.preemptions > 0
