"""BaseModule: the high-level symbolic training loop.

Reference: python/mxnet/module/base_module.py — ``fit`` (:410) drives
bind → init_params → init_optimizer → per-batch forward_backward/update/
update_metric with epoch callbacks; score/predict evaluation entry points.
"""
from __future__ import annotations

import logging
import time

from .. import metric as metric_mod
from .. import profiler
from ..model import BatchEndParam
from ..base import string_types
from ..ndarray import NDArray
from ..context import cpu


def _as_list(obj):
    if isinstance(obj, list):
        return obj
    return [obj]


def _fire(callbacks, *args):
    """Invoke a callback, a list of callbacks, or nothing (None)."""
    if callbacks is None:
        return
    for callback in _as_list(callbacks):
        callback(*args)


_NO_BATCH = object()  # sentinel: iterator exhausted


def _check_input_names(symbol, names, typename, throw):
    args = symbol.list_arguments()
    for name in names:
        if name in args:
            continue
        candidates = [arg for arg in args if arg not in names]
        msg = "\033[91mYou created Module with Module(..., %s_names=%s) but input with" \
              " name '%s' is not found in symbol.list_arguments(). Did you mean one" \
              " of:\n\t%s\033[0m" % (typename, str(names), name, "\n\t".join(candidates))
        if throw:
            raise ValueError(msg)
        logging.warning(msg)


class BaseModule:
    # lifecycle flags, all False until the corresponding stage runs
    _STAGE_FLAGS = ("binded", "for_training", "inputs_need_grad",
                    "params_initialized", "optimizer_initialized")

    def __init__(self, logger=logging):
        self.logger = logger
        for flag in self._STAGE_FLAGS:
            setattr(self, flag, False)
        self._symbol = None
        self._total_exec_bytes = 0

    # ------------------------------------------------------------------
    # high-level
    # ------------------------------------------------------------------
    def forward_backward(self, data_batch):
        self.forward(data_batch, is_train=True)
        self.backward()

    def score(self, eval_data, eval_metric, num_batch=None, batch_end_callback=None,
              score_end_callback=None, reset=True, epoch=0, sparse_row_id_fn=None):
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)
        eval_metric.reset()
        actual_num_batch = 0
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            self._metric_from_batch(eval_metric, eval_batch)
            _fire(batch_end_callback,
                  BatchEndParam(epoch=epoch, nbatch=nbatch,
                                eval_metric=eval_metric, locals=locals()))
            actual_num_batch += 1
        if score_end_callback:
            _fire(score_end_callback,
                  BatchEndParam(epoch=epoch, nbatch=actual_num_batch,
                                eval_metric=eval_metric, locals=locals()))
        return eval_metric.get_name_value()

    def _metric_from_batch(self, eval_metric, batch):
        """Update a metric from one batch, which may be pre-sliced per device."""
        if isinstance(batch, list):
            self.update_metric(eval_metric, [b.label for b in batch],
                               pre_sliced=True)
        else:
            self.update_metric(eval_metric, batch.label)

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad] for out in self.get_outputs()]
            yield (outputs, nbatch, eval_batch)

    def predict(self, eval_data, num_batch=None, merge_batches=True, reset=True,
                always_output_list=False, sparse_row_id_fn=None):
        from .. import ndarray as nd
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        output_list = []
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad].copy() for out in self.get_outputs()]
            output_list.append(outputs)
        if len(output_list) == 0:
            return output_list
        if merge_batches:
            num_outputs = len(output_list[0])
            for out in output_list:
                assert len(out) == num_outputs
            output_list2 = [nd.concat(*[out[i] for out in output_list], dim=0)
                            for i in range(num_outputs)]
            if num_outputs == 1 and not always_output_list:
                return output_list2[0]
            return output_list2
        return output_list

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=None, arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None, monitor=None,
            sparse_row_id_fn=None, prefetch_to_device=None,
            resume_from=None, auto_resume=False, compiled=None,
            steps_per_call=1, metric_interval=None, donate="auto",
            shard_update=False, wire_format=None, wire_threshold=0.5):
        """Train the module (reference base_module.py:410).

        Compiled training (default ON, docs/PERF.md "Compiled training
        step"): ``compiled=None``/``True`` captures forward + backward +
        optimizer update as ONE CachedOp via
        :class:`~mxnet_tpu.module.compiled_step.CompiledTrainStep` —
        params/optimizer state update in place on device, metrics accumulate
        on-device, and the host fetches them only every ``metric_interval``
        batches (``None`` = at epoch end only), so the per-batch host
        barrier of the eager loop is gone.  ``steps_per_call=N`` scans a
        window of N batches per dispatch.  Configurations the capture cannot
        express (multi-context binds, kvstore updates, non-trace_safe
        optimizers, metrics with no device twin, monitors) fall back to the
        eager loop with a one-line warning; ``compiled=False`` forces eager.
        Under the compiled path, callbacks observe metric values that lag by
        up to ``metric_interval`` batches.

        ``shard_update=True`` (docs/PERF.md "Sharded weight update (ZeRO)")
        runs the compiled step's optimizer update ZeRO-sharded over all
        local devices: optimizer state lives dp-sharded at 1/N bytes per
        replica and each replica updates only its flat parameter shard
        (bitwise-equal to the replicated step for elementwise optimizers;
        checkpoints/resume keep working — the updater's state arrays simply
        hold the flat sharded form).  ``wire_format="2bit"`` additionally
        routes the gradient reduce through the error-feedback 2-bit codec
        (``wire_threshold`` is its quantization step) — 4x fewer wire
        bytes, with the residual carried per replica in the module's shared
        ResidualStore.  Both require the compiled path: configurations that
        fall back to eager train replicated, with the usual warning.

        ``prefetch_to_device`` (a Context) routes each epoch's batches
        through an ``io.DeviceFeed``: a background thread stays up to two
        batches ahead, staging DataBatch arrays onto the device so the
        step never pays decode or host→device transfer inline (safe even
        for iterators that reuse host buffers between ``next()`` calls —
        staging copies each batch to the device before the feed advances
        the source again).

        Crash recovery (docs/ROBUSTNESS.md): ``resume_from=prefix`` scans
        ``prefix-manifest.json`` for the newest COMPLETE checkpoint (torn
        or uncommitted saves are skipped by content hash), restores params
        + optimizer state + epoch, and continues training from there; with
        no complete checkpoint it raises.  ``auto_resume=True`` is the
        opportunistic form: resume when a complete checkpoint exists, start
        fresh otherwise — and when ``resume_from`` is not given, the prefix
        is discovered from a ``do_checkpoint``/``module_checkpoint`` epoch
        callback (their ``checkpoint_prefix`` attribute), so the idiom
        ``fit(..., epoch_end_callback=do_checkpoint(p), auto_resume=True)``
        makes a preempted-and-restarted job pick itself back up.
        """
        assert num_epoch is not None, "please specify number of epochs"
        import os
        from ..initializer import Uniform
        if initializer is None:
            initializer = Uniform(0.01)

        resume_prefix = resume_from
        if resume_prefix is None and auto_resume and \
                epoch_end_callback is not None:
            for cb in _as_list(epoch_end_callback):
                prefix = getattr(cb, "checkpoint_prefix", None)
                if prefix:
                    resume_prefix = prefix
                    break
        resume_epoch = None
        if resume_prefix is not None:
            from ..model import latest_complete_checkpoint, load_checkpoint
            resume_epoch = latest_complete_checkpoint(resume_prefix)
            if resume_epoch is None:
                if not auto_resume:
                    raise FileNotFoundError(
                        "resume_from=%r: no complete checkpoint found "
                        "(torn/partial saves are skipped via the manifest)"
                        % resume_prefix)
                self.logger.info("auto_resume: no complete checkpoint under "
                                 "%r; starting fresh", resume_prefix)
            else:
                _, arg_params, aux_params = load_checkpoint(resume_prefix,
                                                            resume_epoch)
                force_init = True
                allow_missing = False
                begin_epoch = max(begin_epoch, resume_epoch)
                self.logger.info("Resuming from checkpoint %r epoch %d",
                                 resume_prefix, resume_epoch)

        with profiler.span("fit.bind"):
            self.bind(data_shapes=train_data.provide_data,
                      label_shapes=train_data.provide_label,
                      for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        with profiler.span("fit.init_params"):
            self.init_params(initializer=initializer, arg_params=arg_params,
                             aux_params=aux_params,
                             allow_missing=allow_missing,
                             force_init=force_init)
        with profiler.span("fit.init_optimizer"):
            self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                                optimizer_params=optimizer_params)

        if resume_epoch is not None:
            # optimizer state rides along only when the manifest committed
            # it for that epoch — a stray .states from a torn save is not
            # trusted (checkpoint_files returns only hash-verified entries)
            from ..model import checkpoint_files
            state_file = "%s-%04d.states" % (resume_prefix, resume_epoch)
            listed = checkpoint_files(resume_prefix, resume_epoch)
            if listed is not None and state_file in listed and \
                    os.path.exists(state_file) and \
                    hasattr(self, "load_optimizer_states"):
                self.load_optimizer_states(state_file)
                self.logger.info("Restored optimizer state from %r",
                                 state_file)

        if validation_metric is None:
            validation_metric = eval_metric
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)

        if (shard_update or wire_format is not None) and compiled is not None \
                and not compiled:
            raise ValueError("shard_update/wire_format need the compiled "
                             "path (fit(compiled=False) trains replicated)")
        compiled_step = None
        if compiled is None or compiled:
            from .compiled_step import (CompiledTrainStep,
                                        CompiledStepUnsupported)
            reason = None
            if monitor is not None:
                reason = "a monitor needs per-op eager dispatch"
            elif sparse_row_id_fn is not None:
                reason = "sparse_row_id_fn prefetch is an eager-loop hook"
            else:
                try:
                    with profiler.span("fit.build_step"):
                        compiled_step = CompiledTrainStep.from_module(
                            self, eval_metric=eval_metric,
                            steps_per_call=steps_per_call, donate=donate,
                            shard_update=shard_update,
                            wire_format=wire_format,
                            wire_threshold=wire_threshold)
                except CompiledStepUnsupported as exc:
                    reason = str(exc)
            if compiled_step is None:
                # how many fits of this process ran eager; the warning
                # below says why
                profiler.count("fit.eager_fallback")
                if shard_update or wire_format is not None:
                    self.logger.warning(
                        "fit(shard_update=%s, wire_format=%s): the ZeRO "
                        "sharded update is unavailable here — training "
                        "REPLICATED via the eager loop: %s",
                        shard_update, wire_format, reason)
                else:
                    self.logger.warning(
                        "fit(compiled=%s): falling back to the eager loop: "
                        "%s", compiled, reason)
        self._compiled_step = compiled_step

        for epoch in range(begin_epoch, num_epoch):
            tic = time.time()
            eval_metric.reset()
            eval_name_vals = []
            feed = None
            if prefetch_to_device is not None:
                from ..io.device_feed import DeviceFeed
                feed = DeviceFeed(train_data, ctx=prefetch_to_device,
                                  name="fit")
                batches = iter(feed)
            else:
                batches = iter(train_data)
            try:
                if compiled_step is not None:
                    nbatch, eval_name_vals = self._fit_compiled_epoch(
                        compiled_step, batches, eval_metric, epoch,
                        batch_end_callback, metric_interval)
                    data_batch = _NO_BATCH
                else:
                    with profiler.span("fit.next", seq=0):
                        data_batch = next(batches, _NO_BATCH)
                    nbatch = 0
                while data_batch is not _NO_BATCH:
                    with profiler.span("fit.step", seq=nbatch, cpu=True):
                        if monitor is not None:
                            monitor.tic()
                        with profiler.span("step.dispatch"):
                            self.forward_backward(data_batch)
                            self.update()
                        with profiler.span("fit.metric_sync"):
                            self._metric_from_batch(eval_metric, data_batch)
                        # only fetch the next batch AFTER training on this
                        # one — a DataIter may reuse the previous batch's
                        # buffers on next() (the feed path is exempt: batches
                        # arrive as device copies, staged before the source
                        # advances)
                        with profiler.span("fit.next", seq=nbatch + 1):
                            upcoming = next(batches, _NO_BATCH)
                        if upcoming is not _NO_BATCH:
                            # prefetch hook for the next batch (sparse row
                            # pull)
                            self.prepare(upcoming,
                                         sparse_row_id_fn=sparse_row_id_fn)
                        if monitor is not None:
                            monitor.toc_print()
                        if upcoming is _NO_BATCH:
                            # snapshot before callbacks may auto-reset the
                            # metric
                            eval_name_vals = eval_metric.get_name_value()
                        with profiler.span("fit.callback"):
                            _fire(batch_end_callback,
                                  BatchEndParam(epoch=epoch, nbatch=nbatch,
                                                eval_metric=eval_metric,
                                                locals=locals()))
                    data_batch = upcoming
                    nbatch += 1
            finally:
                if feed is not None:
                    feed.close()
            for name, val in eval_name_vals:
                self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
            toc = time.time()
            self.logger.info("Epoch[%d] Time cost=%.3f", epoch, (toc - tic))
            arg_params_, aux_params_ = self.get_params()
            if compiled_step is None:
                # multi-device sync-back: each replica gets the averaged
                # params.  The compiled path is single-device and its state
                # handles ARE the canonical buffers — writing the same
                # values back would only swap committed jit-output buffers
                # for fresh copies and silently flip the step's jit cache
                # key (one stealth recompile per epoch).
                self.set_params(arg_params_, aux_params_)
            _fire(epoch_end_callback, epoch, self.symbol, arg_params_, aux_params_)
            if eval_data is not None:
                res = self.score(eval_data, validation_metric,
                                 score_end_callback=eval_end_callback,
                                 batch_end_callback=eval_batch_end_callback,
                                 epoch=epoch)
                for name, val in res:
                    self.logger.info("Epoch[%d] Validation-%s=%f", epoch, name, val)
            train_data.reset()

    def _fit_compiled_epoch(self, cstep, batches, eval_metric, epoch,
                            batch_end_callback, metric_interval):
        """One epoch through the compiled train step (docs/PERF.md).

        Batches group into windows of ``cstep.steps_per_call`` (the epoch
        tail dispatches as a shorter window — one extra compiled signature,
        stable across epochs); each window is ONE CachedOp dispatch with no
        host fetch.  Metrics sync from the device accumulators only every
        ``metric_interval`` batches and at epoch end, so callbacks observe
        values that lag up to one interval."""
        nbatch = 0
        eval_name_vals = []
        window = []
        step = 0         # the loop's iteration, and the number of its batch
        with profiler.span("fit.next", seq=0):
            data_batch = next(batches, _NO_BATCH)
        while data_batch is not _NO_BATCH:
            if isinstance(data_batch, list):
                raise ValueError("pre-sliced multi-device batches reach the "
                                 "compiled path only through a bug: "
                                 "multi-context binds fall back to eager")
            with profiler.span("fit.step", seq=step, cpu=True):
                window.append(data_batch)
                # batch step + 1: the feed numbers it so too
                with profiler.span("fit.next", seq=step + 1):
                    upcoming = next(batches, _NO_BATCH)
                if len(window) == cstep.steps_per_call or \
                        upcoming is _NO_BATCH:
                    with profiler.span("step.dispatch"):
                        cstep.run_window([tuple(b.data) + tuple(b.label or ())
                                          for b in window])
                    last_in_epoch = upcoming is _NO_BATCH
                    for i in range(len(window)):
                        done = nbatch + 1
                        is_final = last_in_epoch and i == len(window) - 1
                        if is_final or (metric_interval
                                        and done % metric_interval == 0):
                            with profiler.span("fit.metric_sync"):
                                cstep.sync_metric()
                        if is_final:
                            # snapshot before callbacks may auto-reset the
                            # metric
                            eval_name_vals = eval_metric.get_name_value()
                        with profiler.span("fit.callback"):
                            _fire(batch_end_callback,
                                  BatchEndParam(epoch=epoch, nbatch=nbatch,
                                                eval_metric=eval_metric,
                                                locals=locals()))
                        nbatch = done
                    window = []
            data_batch = upcoming
            step += 1
        return nbatch, eval_name_vals

    # ------------------------------------------------------------------
    # abstract interface
    # ------------------------------------------------------------------
    @property
    def data_names(self):
        raise NotImplementedError()

    @property
    def output_names(self):
        raise NotImplementedError()

    @property
    def data_shapes(self):
        raise NotImplementedError()

    @property
    def label_shapes(self):
        raise NotImplementedError()

    @property
    def output_shapes(self):
        raise NotImplementedError()

    @property
    def symbol(self):
        return self._symbol

    def get_params(self):
        raise NotImplementedError()

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False, allow_extra=False):
        raise NotImplementedError()

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init, allow_extra=allow_extra)

    def save_params(self, fname):
        from .. import ndarray as nd
        arg_params, aux_params = self.get_params()
        save_dict = {("arg:%s" % k): v for k, v in arg_params.items()}
        save_dict.update({("aux:%s" % k): v for k, v in aux_params.items()})
        nd.save(fname, save_dict)

    def load_params(self, fname):
        from .. import ndarray as nd
        save_dict = nd.load(fname)
        arg_params = {}
        aux_params = {}
        for k, value in save_dict.items():
            arg_type, name = k.split(":", 1)
            if arg_type == "arg":
                arg_params[name] = value
            elif arg_type == "aux":
                aux_params[name] = value
            else:
                raise ValueError("Invalid param file " + fname)
        self.set_params(arg_params, aux_params)

    def get_states(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        assert not merge_multi_context
        return []

    def set_states(self, states=None, value=None):
        assert self.binded and self.params_initialized
        assert not states and not value

    def install_monitor(self, mon):
        raise NotImplementedError()

    def prepare(self, data_batch, sparse_row_id_fn=None):
        pass

    def forward(self, data_batch, is_train=None):
        raise NotImplementedError()

    def backward(self, out_grads=None):
        raise NotImplementedError()

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError()

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError()

    def update(self):
        raise NotImplementedError()

    def update_metric(self, eval_metric, labels, pre_sliced=False):
        raise NotImplementedError()

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        raise NotImplementedError()

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        raise NotImplementedError()
