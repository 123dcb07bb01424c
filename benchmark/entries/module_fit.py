"""Entry ``module_fit``: the window is one ``Module.fit()`` call.

The zoo network of the configuration is bound as a ``Module`` and trained
through ``fit()`` -> ``CompiledTrainStep`` with a ``DeviceFeed``
(``prefetch_to_device``), the configuration's optimizer (as its file under
``reference/optimizers/`` names it to the program), the cross-entropy metric,
over this file's own ``DataIter``.  It cycles the seed's pool of batches from
host memory: epoch 0 is the warm-up (``warmup_steps`` steps, the first three
of which the comparison reads), epoch 1 is the window and ends on the clock.
The run fails if ``fit()`` fell back to the eager loop.  The entry reports
``train_images_per_s`` (samples, that is rows of a batch, completed per second
of the window) and ``setup_s``; a step cannot fail short of the run.

The window opens at the iterator's first ``next()`` of epoch 1, with the
device idle, and closes when the state of the last step is ready.  A traced
run traces the last ``trace_seconds`` of the window: a trace of all 20 s of
these steps took the profiler some 170 s to write out, one of the last 5 s
takes 8 to 11 s (PERF.md, PR 24).
"""
from __future__ import annotations

import time

import jax

from benchmark import trace as trace_mod
from benchmark import traffic as traffic_mod
from benchmark.harness import BenchmarkError
from benchmark.reference import common as reference

COMPARED_STEPS = 3


def fetch_state(cstep, kind, prefix):
    """Host copies of a compiled step's state entries of one kind (``p:``
    parameters, ``a:`` BatchNorm statistics), keyed by the name without the
    network's ``prefix``; of ``o:``, each parameter's optimizer state, the
    list of its arrays in the program's order (``o:<name>:<i>``)."""
    skip = len(kind) + len(prefix)
    values = jax.device_get({k: v._data for k, v in cstep.state.items()
                             if k.startswith(kind)})
    if kind != "o:":
        return {k[skip:]: v for k, v in values.items()}
    found = {}
    for name, i in sorted((k.rsplit(":", 1) for k in values),
                          key=lambda ni: (ni[0], int(ni[1]))):
        found.setdefault(name[skip:], []).append(values[name + ":" + i])
    return found


def training_window(steps, batch, t_start, t_open, t_close, compiles_at_open,
                    compiles_at_close, **counters):
    """The window a training entry's ``drive()`` returns: the end-to-end
    values (all samples of all steps over all the window's seconds; process
    start to the window's opening), the counts of operations, and the
    counters the per-layer readers and the log take."""
    window_s, setup_s = t_close - t_open, t_open - t_start
    return {
        "end_to_end": {"train_images_per_s": steps * batch / window_s,
                       "setup_s": setup_s},
        "attempted": steps, "failed": 0, "samples": steps * batch,
        "steps": steps, "window_s": window_s,
        "t_open": t_open, "t_close": t_close, "setup_s": setup_s,
        "window_compiles": compiles_at_close["compiles"]
        - compiles_at_open["compiles"],
        **counters,
        **{"setup_" + k: v for k, v in compiles_at_open.items()},
    }


def build_network(config):
    from mxnet_tpu.gluon.model_zoo import vision
    return vision.get_model(config["network"], classes=config["classes"])


class Run:
    def __init__(self, cell, seed, seconds, devices, meter, t_start,
                 trace_dir):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.devices, self.meter, self.t_start = devices, meter, t_start
        self.trace_dir = trace_dir
        self.losses = []
        self.stamps = {}             # set-up's phases, seconds from t_start
        self.snapshots = {}
        self.module = None
        self.pool = None
        self.tracing = False

    # -- the harness's DataIter -----------------------------------------
    def _make_iter(self, mx, pool, batch):
        run = self
        host = mx.cpu()
        annotate = jax.profiler.TraceAnnotation
        traced = run.cell.traffic["trace_seconds"]
        batches = [mx.io.DataBatch(
            data=[mx.nd.array(x, ctx=host)], label=[mx.nd.array(y, ctx=host)],
            pad=0) for x, y in pool]

        class PoolIter(mx.io.DataIter):
            """Cycles the pool; epoch 0 ends after the warm-up steps, every
            later epoch ``seconds`` after its first ``next()``."""

            def __init__(self):
                super().__init__(batch)
                self.provide_data = [mx.io.DataDesc("data",
                                                    pool[0][0].shape)]
                self.provide_label = [mx.io.DataDesc("softmax_label",
                                                     pool[0][1].shape)]
                self.epoch = self.cursor = self.served = 0
                self.t_open = None

            def reset(self):
                self.epoch += 1
                self.served = 0
                run._stamp("warmed_up")

            def next(self):
                with annotate("bench:next"):
                    if self.epoch == 0:
                        if self.served >= run.cell.traffic["warmup_steps"]:
                            raise StopIteration
                    else:
                        now = time.perf_counter()
                        if self.t_open is None:
                            self.t_open = now
                            run.compiles_at_open = run.meter.read()
                            with annotate("bench:window_open"):
                                pass
                        if now - self.t_open >= run.seconds:
                            raise StopIteration
                        if run.trace_dir and not run.tracing and \
                                now - self.t_open >= run.seconds - traced:
                            run.tracing = True
                            trace_mod.start(run.trace_dir)
                    item = batches[self.cursor % len(batches)]
                    self.cursor += 1
                    self.served += 1
                    return item

        return PoolIter()

    # -- the batch-end callback -----------------------------------------
    def _stamp(self, name):
        self.stamps.setdefault("setup_%s_s" % name,
                               time.perf_counter() - self.t_start)

    def _on_batch(self, param):
        with jax.profiler.TraceAnnotation("bench:callback"):
            cstep = param.locals.get("cstep")
            if cstep is None:
                raise BenchmarkError("fit() fell back to the eager loop")
            if self.iter.epoch == 0:
                done = param.nbatch + 1
                if done <= COMPARED_STEPS:
                    cstep.sync_metric()
                    self.losses.append(
                        float(param.eval_metric.get_name_value()[0][1]))
                    param.eval_metric.reset()
                if done == 1:
                    self._stamp("first_step")
                    self.snapshots["opt"] = fetch_state(cstep, "o:",
                                                        self.prefix)
                if done == COMPARED_STEPS:
                    self.snapshots["end"] = {
                        **fetch_state(cstep, "p:", self.prefix),
                        **fetch_state(cstep, "a:", self.prefix)}
            elif param.locals["is_final"]:
                jax.block_until_ready(
                    [v._data for v in cstep.state.values()])
                self.t_close = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench:window_close"):
                    pass
                self.compiles_at_close = self.meter.read()
                self.window_steps = param.nbatch + 1

    # -- the run ----------------------------------------------------------
    def drive(self, window=True):
        """Set-up (epoch 0), then the window (epoch 1) unless ``window`` is
        false: the readings under checks/ need no measured window."""
        import mxnet_tpu as mx
        from mxnet_tpu.ndarray import from_jax

        cell, config, job = self.cell, self.cell.config, self.cell.traffic
        self._stamp("imported")
        ctx = mx.current_context()
        if ctx.jax_device() != self.devices[0]:
            raise BenchmarkError("the program's default device is %s, not %s"
                                 % (ctx.jax_device(), self.devices[0]))
        net = build_network(config)
        self.prefix = net.prefix
        sym = mx.sym.SoftmaxOutput(net(mx.sym.var("data")), name="softmax")
        self._stamp("network_traced")
        params, aux = reference.xavier_init(config, self.seed)
        jax.block_until_ready(params)
        self._stamp("weights_made")
        self.pool = traffic_mod.make_pool(config, job, self.seed)
        self._stamp("pool_made")
        self.iter = self._make_iter(mx, self.pool, job["batch"])
        metric = mx.metric.create("ce")
        self._stamp("inputs_made")
        self.module = mod = mx.mod.Module(sym, context=ctx)
        wrap = lambda tree: {self.prefix + k: from_jax(v, ctx=ctx)
                             for k, v in tree.items()}
        optimizer = reference.optimizer(config)
        mod.fit(self.iter, num_epoch=2 if window else 1,
                optimizer=optimizer.MXNET,
                optimizer_params=optimizer.mxnet_params(job),
                eval_metric=metric, initializer=mx.init.Zero(),
                arg_params=wrap(params), aux_params=wrap(aux),
                batch_end_callback=self._on_batch, metric_interval=None,
                steps_per_call=job["steps_per_call"],
                prefetch_to_device=ctx)
        trace_stop_s = 0.0
        if self.tracing:
            t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            trace_stop_s = time.perf_counter() - t_stop
        if mod._compiled_step is None:
            raise BenchmarkError("fit() fell back to the eager loop")
        if not window:
            return None
        return training_window(
            self.window_steps, job["batch"], self.t_start, self.iter.t_open,
            self.t_close, self.compiles_at_open, self.compiles_at_close,
            step_signatures=mod._compiled_step.cache_stats()["misses"],
            final_loss=float(metric.get_name_value()[0][1]),
            trace_stop_s=trace_stop_s, **self.stamps)

    def readings(self):
        """What the comparison reads, from the snapshots taken in set-up."""
        return reference.program_readings(
            self.cell.config, self.cell.traffic, self.seed, self.losses,
            self.snapshots.get("opt", {}), self.snapshots.get("end", {}))

    def first_batches(self):
        return self.pool[:COMPARED_STEPS]

    def free(self):
        self.module = self.iter = None
        self.snapshots = {}
