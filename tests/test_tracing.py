"""The program's own recorder (mxnet_tpu/profiler.py, PR 25): the spans and
counters that fit(), the DeviceFeed, CompiledTrainStep and CachedOp leave, the
names the step carries on the device, and the recorder's own bounds."""
import json
import sys
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, profiler
from mxnet_tpu.cached_op import CachedOp
from mxnet_tpu.io.device_feed import DeviceFeed

STEPS, BATCH, EPOCHS = 6, 4, 2


def tiny_symbol():
    net = mx.sym.Convolution(mx.sym.var("data"), num_filter=4, kernel=(3, 3),
                             name="conv0")
    net = mx.sym.BatchNorm(net, name="bn0")
    net = mx.sym.Activation(net, act_type="relu", name="relu0")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=3, name="fc0")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def tiny_iter():
    rng = np.random.RandomState(0)
    return mx.io.NDArrayIter(
        rng.rand(STEPS * BATCH, 1, 8, 8).astype("float32"),
        np.arange(STEPS * BATCH) % 3, batch_size=BATCH)


@pytest.fixture
def fitted():
    """One fit() of the tiny symbol through the feed; the module and the
    spans it left, oldest first."""
    profiler.reset_spans()
    mod = mx.mod.Module(tiny_symbol(), context=mx.cpu())
    mod.fit(tiny_iter(), num_epoch=EPOCHS, optimizer="sgd", eval_metric="ce",
            prefetch_to_device=mx.cpu())
    return mod, profiler.spans()


def by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s.name, []).append(s)
    return out


# (span, the parents it may have, how many of it the fit above leaves)
FIT_SPANS = [
    ("fit.bind", {None}, 1),
    ("fit.init_params", {None}, 1),
    ("fit.init_optimizer", {None}, 1),
    ("fit.build_step", {None}, 1),
    ("fit.step", {None}, STEPS * EPOCHS),
    ("fit.next", {None, "fit.step"}, (STEPS + 1) * EPOCHS),
    ("step.dispatch", {"fit.step"}, STEPS * EPOCHS),
    ("fit.metric_sync", {"fit.step"}, EPOCHS),
    ("fit.callback", {"fit.step"}, STEPS * EPOCHS),
    ("step.hyper", {"step.dispatch"}, STEPS * EPOCHS),
    ("step.stack", {"step.dispatch"}, STEPS * EPOCHS),
    ("cachedop.first_call", {"step.dispatch"}, 1),
    ("cachedop.call", {"step.dispatch"}, STEPS * EPOCHS - 1),
    ("feed.source", {None}, (STEPS + 1) * EPOCHS),
    ("feed.h2d", {None}, STEPS * EPOCHS),
    ("feed.put_wait", {None}, STEPS * EPOCHS),
]


@pytest.mark.parametrize("name,parents,number", FIT_SPANS,
                         ids=[s[0] for s in FIT_SPANS])
def test_fit_leaves_the_span(fitted, name, parents, number):
    _, spans = fitted
    mine = by_name(spans).get(name, [])
    assert len(mine) == number
    assert {s.parent for s in mine} <= parents
    # the CPU clock is read where something reads it, and nowhere else
    timed = name == "fit.step" or name.startswith("feed.")
    for s in mine:
        assert s.end_ns >= s.start_ns
        assert s.cpu_ns >= 0 if timed else s.cpu_ns is None
    caller = {s.thread for s in spans if s.name == "fit.step"}
    on_feed = name.startswith("feed.")
    assert all((s.thread in caller) != on_feed for s in mine)


def test_seq_is_the_step_and_the_number_of_its_batch(fitted):
    _, spans = fitted
    steps = by_name(spans)["fit.step"]
    assert [s.seq for s in steps] == list(range(STEPS)) * EPOCHS
    for step in steps:
        inside = [s for s in spans if s.thread == step.thread
                  and step.start_ns <= s.start_ns and s.end_ns <= step.end_ns]
        assert len(inside) >= 6          # next, dispatch and its three, callback
        # step k trains on batch k and fetches batch k + 1 meanwhile
        fetch = [s for s in inside if s.name in ("fit.next", "feed.wait")]
        assert {s.seq for s in fetch} == {step.seq + 1}
        assert {s.seq for s in inside if s not in fetch} == {step.seq}
    named = by_name(spans)
    # the feed numbers its batches as the loop numbers its fetches
    for stage in ("feed.h2d", "feed.put_wait"):
        assert [s.seq for s in named[stage]] == list(range(STEPS)) * EPOCHS
    assert [s.seq for s in named["fit.next"]] == \
        list(range(STEPS + 1)) * EPOCHS
    # a wait for the feed lies inside the next() that met the empty queue
    for wait in named.get("feed.wait", []):
        assert wait.parent == "fit.next"


def test_totals_outlive_the_feed(fitted):
    mod, _ = fitted
    totals = profiler.totals()      # fit() dropped both feeds long ago
    assert totals["feed.batches"]["count"] == STEPS * EPOCHS
    assert "fit.eager_fallback" not in totals
    step = totals["fit.step"]
    assert step["count"] == STEPS * EPOCHS
    assert step["max"] <= step["wall_ns"] and step["cpu_ns"] > 0
    # the step compiled once, inside its first call, and nowhere else
    assert totals["cachedop.first_call"]["compile.count"] >= 1
    assert "compile.count" not in totals["cachedop.call"]
    assert mod._compiled_step.cache_stats()["misses"] == 1


def test_feed_transform_span_and_stats_shape():
    profiler.reset_spans()
    src = [np.full((4,), i, np.float32) for i in range(5)]
    with DeviceFeed(src, ctx=mx.cpu(0), transform=lambda x: x + 1) as feed:
        got = list(feed)
        stats = feed.stats()
    assert len(got) == 5 and float(got[0][0]) == 1.0
    assert set(stats) == {"batches", "h2d_ms", "starved_ms",
                          "max_queue_depth", "avg_h2d_ms"}
    assert stats["batches"] == 5 and stats["h2d_ms"] > 0
    transforms = by_name(profiler.spans())["feed.transform"]
    assert [s.seq for s in transforms] == list(range(5))
    h2d_ns = profiler.totals()["feed.h2d"]["wall_ns"]
    assert stats["h2d_ms"] == pytest.approx(h2d_ns / 1e6)   # one clock


def test_monitor_counts_one_eager_fallback():
    profiler.reset_spans()
    mod = mx.mod.Module(tiny_symbol(), context=mx.cpu())
    mod.fit(tiny_iter(), num_epoch=1, optimizer="sgd", eval_metric="ce",
            monitor=mx.monitor.Monitor(interval=100))
    totals = profiler.totals()
    assert totals["fit.eager_fallback"]["count"] == 1
    # the eager loop carries the same four names around its own calls
    for name in ("fit.next", "step.dispatch", "fit.metric_sync",
                 "fit.callback"):
        assert totals[name]["count"] >= STEPS
    assert "cachedop.first_call" not in totals
    assert totals["fit.step"]["count"] == STEPS


def test_second_signature_is_a_second_first_call_charged_its_compile():
    profiler.reset_spans()
    weight = nd.ones((3, 3))
    op = CachedOp(lambda p, x: nd.dot(x, p["w"]) * 3.0, {"w": weight},
                  name="probe")
    op({"w": weight}, nd.ones((2, 3))).wait_to_read()
    op({"w": weight}, nd.ones((2, 3))).wait_to_read()
    one = profiler.totals()["cachedop.first_call"]
    assert one["count"] == 1 and one["compile.count"] >= 1
    op({"w": weight}, nd.ones((5, 3))).wait_to_read()
    two = profiler.totals()["cachedop.first_call"]
    assert two["count"] == 2
    assert two["compile.count"] > one["compile.count"]
    assert two["compile.ns"] > one["compile.ns"]
    calls = profiler.totals()["cachedop.call"]
    assert calls["count"] == 1 and "compile.count" not in calls
    firsts = by_name(profiler.spans())["cachedop.first_call"]
    assert [s.attrs for s in firsts] == [{"op": "probe"}] * 2


def lowered_step(cstep, batch):
    """The step as jax lowers it for one batch, with its locations."""
    import jax.numpy as jnp
    op = cstep.cached_op
    t_nd, lr_nd = cstep._hyper_vectors(1)
    vals = [cstep.state[n]._data for n in op._param_names]
    vals += [t_nd._data, lr_nd._data]
    vals += [jnp.stack([x._data]) for x in batch]
    vals.append(mx.random.next_key())
    return op._get_jitted(True).lower(*vals).as_text(debug_info=True)


def test_the_step_carries_its_names_to_the_device(fitted):
    mod, _ = fitted
    it = tiny_iter()
    batch = next(iter(it))
    text = lowered_step(mod._compiled_step,
                        tuple(batch.data) + tuple(batch.label))
    assert "module @jit_train_step" in text
    for scope in ("fwd/jvp(conv0)/conv_general_dilated", "fwd/jvp(bn0)/",
                  "fwd/jvp(fc0)/dot_general", "opt/", "metric/",
                  # a backward operation names the node it transposes
                  "bwd/transpose(jvp(conv0))/"):
        assert "jit(train_step)/" + scope in text, scope


def test_from_block_step_carries_the_same_scopes():
    from mxnet_tpu.module.compiled_step import CompiledTrainStep
    net = mx.gluon.nn.Dense(3, in_units=5)
    net.initialize()
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    cstep = CompiledTrainStep.from_block(
        net, lambda outs, y: loss_fn(outs[0], y).mean(),
        mx.optimizer.SGD(learning_rate=0.1), eval_metric=mx.metric.create("acc"))
    x, y = nd.ones((4, 5)), nd.array([0, 1, 2, 0])
    before = net.weight.data().asnumpy().copy()
    cstep.step(x, y).wait_to_read()
    assert not np.allclose(before, net.weight.data().asnumpy())
    text = lowered_step(cstep, (x, y))
    assert "module @jit_train_step" in text
    for scope in ("fwd/", "bwd/", "opt/", "metric/"):
        assert "jit(train_step)/" + scope in text, scope


def test_ring_is_bounded_and_events_stay_empty_without_a_session():
    profiler.reset_spans()
    assert profiler.state() == "stop"
    events = len(profiler._events)
    counter = profiler.Domain("d").new_counter("c", 0)
    marker = profiler.Domain("d").new_marker("m")
    for i in range(profiler.RING_SIZE + 500):
        with profiler.span("tick", seq=i):
            pass
    for _ in range(100):
        counter.increment()
        marker.mark()
        with profiler.Task(profiler.Domain("d"), "task"):
            pass
    assert len(profiler.spans()) == profiler.RING_SIZE
    assert profiler.spans()[-1].name == "task"
    assert profiler.totals()["tick"]["count"] == profiler.RING_SIZE + 500
    assert len(profiler._events) == events == 0
    newest = profiler.spans(since_ns=profiler.spans()[-10].end_ns)
    assert len(newest) == 10


def test_a_session_shows_the_spans_in_dump_and_dumps(tmp_path):
    fname = str(tmp_path / "spans.json")
    profiler.set_config(filename=fname)
    profiler.set_state("run")
    try:
        with profiler.span("outer", seq=7, why="test"):
            with profiler.span("inner"):
                pass
    finally:
        profiler.set_state("stop")
    table = profiler.dumps(reset=True)
    assert "outer" in table and "inner" in table
    profiler.dump()
    events = json.load(open(fname))["traceEvents"]
    outer = [e for e in events if e["name"] == "outer"]
    inner = [e for e in events if e["name"] == "inner"]
    assert [e["ph"] for e in outer] == ["B", "E"]
    assert outer[0]["args"] == {"why": "test"}
    assert outer[0]["ts"] <= inner[0]["ts"] <= inner[1]["ts"] <= outer[1]["ts"]
    assert profiler._events == []       # dump() retired them


def test_a_task_open_when_the_session_stops_keeps_its_begin(tmp_path):
    fname = str(tmp_path / "open.json")
    profiler.set_config(filename=fname)
    profiler.set_state("run")
    task = profiler.Domain("d").new_task("left_open").start()
    profiler.set_state("stop")
    task.stop()
    profiler.dump()
    events = [e for e in json.load(open(fname))["traceEvents"]
              if e["name"] == "left_open"]
    assert [(e["ph"], e["cat"]) for e in events] == [("B", "d")]


def test_a_span_reads_the_cpu_clock_only_when_asked():
    profiler.reset_spans()
    for cpu in (True, False):
        with profiler.span("timed" if cpu else "untimed", cpu=cpu):
            sum(i * i for i in range(200_000))
    timed, untimed = profiler.spans()
    assert 0 < timed.cpu_ns <= timed.end_ns - timed.start_ns + 20_000_000
    assert untimed.cpu_ns is None
    totals = profiler.totals()
    assert totals["timed"]["cpu_ns"] == timed.cpu_ns
    assert totals["untimed"]["cpu_ns"] == 0 < totals["untimed"]["wall_ns"]


def test_a_task_stopped_out_of_order_leaves_a_sound_stack():
    profiler.reset_spans()
    domain = profiler.Domain("d")
    a, b = domain.new_task("a").start(), domain.new_task("b").start()
    a.stop()
    b.stop()
    with profiler.span("after"):
        pass
    spans = by_name(profiler.spans())
    assert spans["b"][0].parent == "a"
    assert spans["after"][0].parent is None


def test_counts_from_many_threads_lose_nothing():
    """More threads than cores add to one counter and close one span name
    without a lock; the totals are per thread, so none is lost, and the sums
    of threads that have ended are kept."""
    profiler.reset_spans()
    threads, each = 16, 2000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(each):
                profiler.count("shared.counter", 2)
                with profiler.span("shared.span"):
                    pass
        pool = [threading.Thread(target=work) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(60)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(old)
    totals = profiler.totals()
    assert totals["shared.counter"]["count"] == threads * each * 2
    assert totals["shared.span"]["count"] == threads * each
    # a new thread's first span retires the tables of the sixteen
    late = threading.Thread(target=lambda: profiler.count("late"))
    late.start()
    late.join(60)
    assert len(profiler._tables) <= 3
    assert profiler.totals()["shared.span"]["count"] == threads * each
