"""Entry ``block_step``: the window is a user's loop over
``CompiledTrainStep.from_block``.

The configuration's ``network`` key names a module (dotted path) that builds
the network as the program has it: ``build(config)`` gives a Gluon
``HybridBlock`` whose parameters carry the reference's names behind the
block's prefix, ``loss(outputs, *labels)`` is the loss in ``nd`` operators,
and ``N_INPUTS`` says how many of a batch's arrays feed the block (the rest
go to the loss).  The block's parameters are set from the seed's weights, the
optimizer is the configuration's (as its file under ``reference/optimizers/``
names it to the program), and the step is captured once with
``CompiledTrainStep.from_block``.

The seed's pool of batches is staged on the device once, in set-up: a batch
of token ids is a few hundred kilobytes, and feeding it is not what such a
cell measures.  Set-up drives ``warmup_steps`` steps, the first three of
which the comparison reads (each step's loss, the optimizer's state after
the first, the parameters after the third); the window then cycles the pool
through that same compiled step until the clock ends it.  It opens with the
device idle and closes when the state of the last step is ready.  The entry
reports ``train_images_per_s`` (samples, that is rows of a batch, completed
per second of the window) and ``setup_s``; a step cannot fail short of the
run.  A traced run traces the last ``trace_seconds`` of the window.
"""
from __future__ import annotations

import importlib
import time

import jax

from benchmark import trace as trace_mod
from benchmark import traffic as traffic_mod
from benchmark.entries.module_fit import (COMPARED_STEPS, fetch_state,
                                          training_window)
from benchmark.harness import BenchmarkError
from benchmark.reference import common as reference


class Run:
    def __init__(self, cell, seed, seconds, devices, meter, t_start,
                 trace_dir):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.devices, self.meter, self.t_start = devices, meter, t_start
        self.trace_dir = trace_dir
        self.losses = []
        self.stamps = {}             # set-up's phases, seconds from t_start
        self.snapshots = {}
        self.cstep = self.pool = self.staged = None

    def _stamp(self, name):
        self.stamps["setup_%s_s" % name] = time.perf_counter() - self.t_start

    def _step(self, i):
        return self.cstep.step(*self.staged[i % len(self.staged)])

    def _wait(self):
        jax.block_until_ready([v._data for v in self.cstep.state.values()])

    def drive(self, window=True):
        """Set-up, then the window unless ``window`` is false: the readings
        under checks/ need no measured window."""
        import mxnet_tpu as mx
        from mxnet_tpu.module.compiled_step import CompiledTrainStep
        from mxnet_tpu.ndarray import from_jax

        config, job = self.cell.config, self.cell.traffic
        self._stamp("imported")
        ctx = mx.current_context()
        if ctx.jax_device() != self.devices[0]:
            raise BenchmarkError("the program's default device is %s, not %s"
                                 % (ctx.jax_device(), self.devices[0]))
        network = importlib.import_module(config["network"])
        net = network.build(config)
        net.initialize(mx.init.Zero(), ctx=ctx)
        self.prefix = net.prefix
        params, aux = reference.xavier_init(config, self.seed)
        held = net.collect_params()
        for name, value in {**params, **aux}.items():
            held[self.prefix + name].set_data(from_jax(value, ctx=ctx))
        self._stamp("weights_made")
        self.pool = traffic_mod.make_pool(config, job, self.seed)
        self.staged = [tuple(mx.nd.array(a, ctx=ctx, dtype=a.dtype)
                             for a in batch) for batch in self.pool]
        self._stamp("pool_made")
        optimizer = reference.optimizer(config)
        self.cstep = cstep = CompiledTrainStep.from_block(
            net, network.loss,
            mx.optimizer.create(optimizer.MXNET,
                                **optimizer.mxnet_params(job)),
            n_inputs=network.N_INPUTS, steps_per_call=job["steps_per_call"])
        for i in range(job["warmup_steps"]):
            out = self._step(i)
            if i < COMPARED_STEPS:
                self.losses.append(float(out.asnumpy()[0]))
            if i == 0:
                self._stamp("first_step")
                self.snapshots["opt"] = fetch_state(cstep, "o:", self.prefix)
            if i + 1 == COMPARED_STEPS:
                self.snapshots["end"] = fetch_state(cstep, "p:", self.prefix)
        self._wait()
        self._stamp("warmed_up")
        if not window:
            return None

        annotate = jax.profiler.TraceAnnotation
        traced = job["trace_seconds"]
        tracing, steps = False, 0
        compiles_at_open = self.meter.read()
        t_open = time.perf_counter()
        with annotate("bench:window_open"):
            pass
        while True:
            now = time.perf_counter()
            if now - t_open >= self.seconds:
                break
            if self.trace_dir and not tracing and \
                    now - t_open >= self.seconds - traced:
                tracing = True
                trace_mod.start(self.trace_dir)
            out = self._step(job["warmup_steps"] + steps)
            steps += 1
        self._wait()
        t_close = time.perf_counter()
        with annotate("bench:window_close"):
            pass
        compiles_at_close = self.meter.read()
        trace_stop_s = 0.0
        if tracing:
            t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            trace_stop_s = time.perf_counter() - t_stop
        return training_window(
            steps, job["batch"], self.t_start, t_open, t_close,
            compiles_at_open, compiles_at_close,
            step_signatures=cstep.cache_stats()["misses"],
            final_loss=float(out.asnumpy()[-1]),
            trace_stop_s=trace_stop_s, **self.stamps)

    def readings(self):
        """What the comparison reads, from the snapshots taken in set-up."""
        return reference.program_readings(
            self.cell.config, self.cell.traffic, self.seed, self.losses,
            self.snapshots.get("opt", {}), self.snapshots.get("end", {}))

    def first_batches(self):
        return self.pool[:COMPARED_STEPS]

    def free(self):
        self.cstep = self.staged = None
        self.snapshots = {}
