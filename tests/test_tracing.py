"""The program's own recorder (mxnet_tpu/profiler.py, PR 25): the spans and
counters that fit(), the DeviceFeed, CompiledTrainStep and CachedOp leave, the
names the step carries on the device, the table from a compiled step's
instructions to those names (PR 37), and the recorder's own bounds."""
import gc
import json
import re
import sys
import threading
import weakref

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
    ("cachedop.lower", {"cachedop.first_call"}, 1),
    ("cachedop.compile", {"cachedop.first_call"}, 1),
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
    # the step compiled once, in its first call's compile span, and
    # nowhere else
    assert totals["cachedop.compile"]["compile.count"] == 1
    for elsewhere in ("cachedop.first_call", "cachedop.lower",
                      "cachedop.call"):
        assert "compile.count" not in totals[elsewhere]
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
    assert profiler.totals()["cachedop.first_call"]["count"] == 1
    one = profiler.totals()["cachedop.compile"]
    assert one["count"] == 1 and one["compile.count"] == 1
    op({"w": weight}, nd.ones((5, 3))).wait_to_read()
    assert profiler.totals()["cachedop.first_call"]["count"] == 2
    two = profiler.totals()["cachedop.compile"]
    assert two["count"] == 2 and two["compile.count"] == 2
    assert two["compile.ns"] > one["compile.ns"]
    calls = profiler.totals()["cachedop.call"]
    assert calls["count"] == 1 and "compile.count" not in calls
    named = by_name(profiler.spans())
    for part in ("cachedop.first_call", "cachedop.lower", "cachedop.compile"):
        assert [s.attrs for s in named[part]] == [{"op": "probe"}] * 2
    assert sorted(sig for _, sig in profiler.programs()) == [
        "infer|float32[2,3]", "infer|float32[5,3]"]


def _replicate(value):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
    return jax.device_put(value, NamedSharding(mesh, PartitionSpec()))


@pytest.mark.parametrize("flags", [{}, {"donate_params": True},
                                   {"place_inputs": _replicate}],
                         ids=["plain", "donation", "place_inputs"])
def test_a_first_call_traces_once_and_compiles_once(flags):
    """Lowering and compiling ahead of the dispatch costs no second trace and
    no second compile: the call that follows finds both in jax's caches."""
    import jax
    compiles, traces = [], []
    listen = lambda event, duration, **_: compiles.append(event) \
        if event == profiler._BACKEND_COMPILE else None
    weight, seen = nd.ones((3, 3)), nd.zeros((3,))
    params = {"w": weight, "seen": seen}

    def forward(p, x):
        traces.append(1)
        p["seen"]._set_data((p["seen"] + 1.0)._data)
        return nd.dot(x, p["w"]) * 3.0

    op = CachedOp(forward, params, aux_names=("seen",), flags=flags,
                  name="first_call_probe")
    x = nd.ones((2, 3))
    x.wait_to_read()
    profiler.reset_spans()
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        with mx.autograd.train_mode():
            for _ in range(3):
                out = op(params, x)
        out.wait_to_read()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert len(traces) == 1 and len(compiles) == 1
    assert float(seen.asnumpy()[0]) == 3.0      # the state went through
    totals = profiler.totals()
    assert totals["cachedop.compile"]["compile.count"] == 1
    assert totals["cachedop.call"]["count"] == 2
    # nothing but the one span on a later call
    later = [s.name for s in profiler.spans()][-2:]
    assert later == ["cachedop.call"] * 2


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


# -- the table from a compiled step's instructions to its scopes ------------

DECODER = dict(
    reference="sdar_moe", optimizer="adam", hidden_size=64,
    num_attention_heads=8, num_key_value_heads=1, head_dim=16,
    moe_intermediate_size=24, num_experts=4, num_experts_per_tok=2,
    num_hidden_layers=2, vocab_size=96, rms_norm_eps=1e-6, rope_theta=1e6,
    block_length=4, deployment=dict(num_experts_total=8, first_expert=0))


@pytest.fixture(scope="module")
def decoder_table():
    """One step of a two-layer routed decoder through from_block, each layer
    recomputed; then the step, the network and the batch are dropped, and
    only then is the table asked for.  Gives (table, entry computation's
    instruction names, whether step / network / a parameter's buffer are
    still alive)."""
    import jax
    from mxnet_tpu.gluon.model_zoo import block_diffusion
    from mxnet_tpu.module.compiled_step import CompiledTrainStep
    profiler.reset_spans()
    rng = np.random.default_rng(0)
    rows, mask_id = 32, DECODER["vocab_size"] - 1
    clean = rng.integers(0, mask_id, (2, rows), dtype=np.int32)
    masked = rng.random((2, rows)) < 0.5
    batch = (np.concatenate([np.where(masked, mask_id, clean), clean], 1),
             clean, masked.astype(np.float32) * 2)
    net = block_diffusion.build(DECODER)
    net.initialize(mx.init.Xavier(), ctx=mx.current_context())
    step = CompiledTrainStep.from_block(
        net, block_diffusion.loss,
        mx.optimizer.create("adam", learning_rate=1e-3),
        n_inputs=block_diffusion.N_INPUTS)
    # an executable out of the persistent cache carries the names of the
    # tree that compiled it (jax keys the cache without metadata unless
    # asked): this one is keyed with its names
    keyed = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, keyed)
    jax.config.update(keyed, True)
    try:
        step.step(*[mx.nd.array(a, dtype=a.dtype) for a in batch]) \
            .wait_to_read()
    finally:
        jax.config.update(keyed, before)
    (name, _), compiled = list(profiler.programs().items())[-1]
    assert name == "train_step"
    entry = re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", compiled.as_text(),
                      re.S | re.M).group(1)
    names = re.findall(r"^\s+(?:ROOT )?%?([\w.\-]+) = ", entry, re.M)
    alive = [weakref.ref(step), weakref.ref(net),
             weakref.ref(next(iter(step.state.values()))._data)]
    del step, net, compiled
    gc.collect()
    alive = [ref() is not None for ref in alive]
    return profiler.program_ops("train_step"), names, alive


def rows_where(table, phase=None, scope=None, recomputed=None):
    return [name for name, (p, s, r, _) in table.items()
            if (phase is None or p == phase) and (scope is None or s == scope)
            and (recomputed is None or r == recomputed)]


TABLE_CASES = {
    "every instruction of the entry computation has a row":
        lambda table, names: len(names) > 100
        and all(n in table for n in names),
    "the three phases occur":
        lambda table, names: all(rows_where(table, phase=p)
                                 for p in ("fwd", "bwd", "opt")),
    "the experts occur in both passes":
        lambda table, names: rows_where(table, "fwd", "moe.experts")
        and rows_where(table, "bwd", "moe.experts"),
    "a recomputed layer's rows say so, in the backward pass alone":
        lambda table, names: rows_where(table, "bwd", "moe.experts", True)
        and rows_where(table, "bwd", "attn.proj", True)
        and rows_where(table, "bwd", "moe.experts", False)
        and not rows_where(table, "fwd", recomputed=True)
        and not rows_where(table, "opt", recomputed=True),
    "the projections, the embedding, the head and the loss are named":
        lambda table, names: all(
            rows_where(table, "fwd", s) and rows_where(table, "bwd", s)
            for s in ("attn.proj", "lm.embed", "lm.head", "loss")),
    "the optimizer's update is in no scope":
        lambda table, names: {table[n][1] for n in rows_where(table, "opt")}
        == {None},
    "a fusion is one row and its inside none":
        lambda table, names: any(op == "fusion" for *_, op in table.values())
        and not any("fused_computation" in n or n.startswith("param_")
                    for n in table),
    "the table outlives the step, the network and their buffers":
        lambda table, names: len(table) >= len(names),
}


@pytest.mark.parametrize("case", list(TABLE_CASES))
def test_the_step_names_its_instructions(decoder_table, case):
    table, names, alive = decoder_table
    assert alive == [False, False, False]
    assert TABLE_CASES[case](table, names)


@pytest.mark.parametrize("path,reads", [
    ("jit(train_step)/fwd/jvp(moe.experts)/tanh",
     ("fwd", "moe.experts", False)),
    ("jit(train_step)/bwd/transpose(jvp(fwd))/jvp()/checkpoint/"
     "rematted_computation/moe.experts/dot_general",
     ("bwd", "moe.experts", True)),
    ("jit(train_step)/bwd/transpose(jvp(fwd))/jvp()/checkpoint/attn.proj/"
     "jit(FullyConnected)/dot_general", ("bwd", "attn.proj", False)),
    ("jit(train_step)/bwd/transpose(jvp(lm.head))/jit(FullyConnected)/mul",
     ("bwd", "lm.head", False)),
    ("jit(train_step)/fwd/jvp(dsa.attend)/jvp(attn.block_mask)/attention_fwd",
     ("fwd", "attn.block_mask", False)),
    ("jit(train_step)/fwd/jvp(loss)/jit(pick)/jit(take_along_axis)/gather",
     ("fwd", "loss", False)),
    ("jit(train_step)/opt/jit(adam_update)/sub", ("opt", None, False)),
    ("jit(train_step)/fwd/jvp(conv0)/conv_general_dilated",
     ("fwd", None, False)),
    ("jit(train_step)/metric/eq", ("metric", None, False)),
    ("jit(split)/threefry2x32", (None, None, False)),
    ("", (None, None, False)),
])
def test_scope_of_a_path(path, reads):
    assert profiler.scope_of(path) == reads


SCHEDULED = """HloModule jit_train_step, is_scheduled=true

fused_computation.1 {
  param_0.1 = parameter(0)
  ROOT multiply.1 = multiply(param_0.1, param_0.1), metadata={op_name="jit(train_step)/fwd/jvp(moe.experts)/mul"}
}

body.1 {
  p.1 = parameter(0)
  copy.6 = copy(p.1)
  ROOT dot.7 = dot(copy.6, copy.6), metadata={op_name="jit(train_step)/bwd/transpose(jvp(fwd))/jvp()/checkpoint/rematted_computation/attn.proj/dot_general" stack_frame_id=3}
}

ENTRY main.9 {
  vals_0_.1 = parameter(0), metadata={op_name="vals[0]"}
  copy-start.1 = copy-start(vals_0_.1)
  copy-done.1 = copy-done(copy-start.1)
  fusion.1 = fusion(copy-done.1), kind=kLoop, calls=fused_computation.1, metadata={op_name="jit(train_step)/fwd/jvp(moe.experts)/mul" stack_frame_id=1}
  copy.2 = copy(fusion.1)
  while.3 = while(copy.2), condition=cond.1, body=body.1, metadata={op_name="jit(train_step)/bwd/transpose(jvp(fwd))/jvp()/checkpoint/while"}
  convert.4 = convert(while.3), metadata={op_name="jit(train_step)/convert_element_type"}
  copy.5 = copy(convert.4)
  ROOT tuple.8 = tuple(copy.5)
}
"""


@pytest.mark.parametrize("instruction,row", [
    ("fusion.1", ("fwd", "moe.experts", False, "fusion")),
    ("multiply.1", None),                       # inside the fusion
    ("dot.7", ("bwd", "attn.proj", True, "dot")),
    ("while.3", ("bwd", None, False, "while")),
    # made by the compiler, no name: the phase the schedule runs them in,
    # which is the next named instruction's, in their own computation
    ("copy-start.1", ("fwd", None, False, "copy-start")),
    ("copy-done.1", ("fwd", None, False, "copy-done")),
    ("copy.2", ("bwd", None, False, "copy")),
    ("copy.6", ("bwd", None, False, "copy")),
    ("p.1", ("bwd", None, False, "parameter")),
    # named outside every phase, and what runs after the last name
    ("vals_0_.1", (None, None, False, "parameter")),
    ("convert.4", (None, None, False, "convert")),
    ("copy.5", (None, None, False, "copy")),
    ("tuple.8", (None, None, False, "tuple")),
])
def test_the_table_of_a_scheduled_text(instruction, row):
    assert profiler._parse_ops(SCHEDULED).get(instruction) == row


def test_the_registry_keeps_the_newest_programs_and_reset_empties_it():
    profiler.reset_spans()
    assert profiler.programs() == {} and profiler.program_ops("p") is None
    kept = profiler.PROGRAMS_KEPT
    for i in range(kept + 3):
        profiler.program("p", "sig%d" % i, object())
    profiler.program("p", "sig5", object())      # again: now the newest
    sigs = [sig for _, sig in profiler.programs()]
    assert len(sigs) == kept and sigs[-1] == "sig5"
    assert sigs[0] == "sig3" and "sig2" not in sigs
    # something that is no executable gives no table, and raises nothing
    assert profiler.program_ops("p") is None
    profiler.reset_spans()
    assert profiler.programs() == {}


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
