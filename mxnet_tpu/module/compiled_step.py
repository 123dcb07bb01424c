"""CompiledTrainStep: the whole training iteration as ONE CachedOp.

The eager ``fit()`` loop dispatches forward, backward, one optimizer-update
kernel per parameter, and a metric fetch — with a host sync on every batch.
This module makes the fused step, one XLA module per iteration, a
first-class citizen of the module layer.  The benchmark's cells run it
(``Module.fit()`` and ``from_block``), and PERF.md §5 says where their
time goes on the chip.  It follows the
"compile the whole program, not ops" thesis of the Julia->TPU paper
(arxiv 1810.09868), with the dataflow-step discipline of TensorFlow
(arxiv 1605.08695):

* forward + backward + the optimizer update of EVERY parameter are captured
  as one :class:`~mxnet_tpu.cached_op.CachedOp`; all mutable training state
  (params, BatchNorm running stats, optimizer slots, metric accumulators)
  rides as CachedOp aux and is written back in place after each dispatch;
* buffer donation (``CachedOp(flags={'donate_params': True})``) lets XLA
  alias each state input's allocation to its output — true in-place update;
  on CPU backends donation is a no-op, so ``donate='auto'`` only requests it
  off-CPU;
* ``steps_per_call=N`` wraps the step in ``jax.lax.scan`` over a
  device-resident window of N microbatches, so N optimizer steps cost ONE
  dispatch (and one host->device transfer of the stacked window);
* metrics accumulate ON DEVICE through each metric's ``traced_update`` twin
  (metric.py); the host fetches the (sum, count) scalars only at
  ``metric_interval`` boundaries or at epoch end — the per-step host
  barrier is gone;
* per-step hyperparameters (the step count ``t`` and the scheduler-resolved
  base learning rate) enter the trace as scalar INPUTS, so lr schedules and
  t-dependent optimizers (Adam bias correction, FTML) run compiled without
  per-step recompiles.

Two frontends share the machinery:

* :meth:`CompiledTrainStep.from_module` — a bound symbolic ``Module`` with
  its initialized optimizer; the step is built over the executor's traced
  graph (grads = vjp with ones cotangents, the ``backward()`` contract) and
  the optimizer's own ``update_multi_precision`` traced through NDArray
  tracer handles, so the compiled and eager paths run the SAME update
  kernels.  This is what ``BaseModule.fit(compiled=True)`` uses.
* :meth:`CompiledTrainStep.from_block` — a gluon block + explicit loss:
  a user's Gluon loop and the benchmark's ``block_step`` entry, so both
  kinds of cell exercise one code path.

Limitations become :class:`CompiledStepUnsupported` (the caller falls back
to the eager loop with a one-line warning): multi-context binds, kvstore
updates, non-``trace_safe`` optimizers, metrics with no device twin.
"""
from __future__ import annotations

import contextlib

import numpy as _np

from .. import autograd
from .. import profiler
from ..base import MXNetError
from ..cached_op import CachedOp
from ..ndarray import NDArray, _wrap

__all__ = ["CompiledTrainStep", "CompiledStepUnsupported"]


class CompiledStepUnsupported(MXNetError):
    """This configuration cannot be captured as a single compiled step;
    the message says why.  Callers fall back to the eager loop."""


# ---------------------------------------------------------------------------
# optimizer capture helpers
# ---------------------------------------------------------------------------

_MISSING = object()


@contextlib.contextmanager
def _step_hyperparams(opt, lr_val, t_val):
    """Route the optimizer's per-step hyperparameters through traced scalars
    for the duration of one traced update.

    ``_get_lr`` returns ``lr_val`` (the host-resolved base lr for this
    microstep, scheduler already applied) times the static per-param
    multiplier, and ``_index_update_count[...]`` reads as ``t_val`` — so
    t-dependent math (Adam bias correction, FTML) stays correct across steps
    of one compiled executable.  Count WRITES are discarded: the host
    advances the real counters after the dispatch
    (CompiledTrainStep._advance_counts)."""

    class _Counts(dict):
        def __missing__(self, key):
            return t_val

        def __setitem__(self, key, value):
            pass

    saved = {name: opt.__dict__.get(name, _MISSING)
             for name in ("_get_lr", "_update_count", "_index_update_count")}
    opt._get_lr = lambda index: lr_val * opt._index_mult(
        index, opt.lr_mult, "lr_mult")
    opt._update_count = lambda index: None
    opt._index_update_count = _Counts()
    try:
        yield
    finally:
        for name, value in saved.items():
            if value is _MISSING:
                del opt.__dict__[name]
            else:
                setattr(opt, name, value)


def _state_leaf_nds(state):
    """NDArray leaves of an optimizer-state structure, depth-first."""
    if isinstance(state, NDArray):
        return [state]
    if isinstance(state, (list, tuple)):
        return [leaf for part in state for leaf in _state_leaf_nds(part)]
    return []   # None / plain scalars carry no device state


def _rebuild_state(template, leaf_iter):
    """The template structure with NDArray leaves drawn from ``leaf_iter``."""
    if isinstance(template, NDArray):
        return next(leaf_iter)
    if isinstance(template, (list, tuple)):
        return type(template)(_rebuild_state(part, leaf_iter)
                              for part in template)
    return template


def _check_optimizer(opt):
    if not getattr(opt, "trace_safe", False):
        raise CompiledStepUnsupported(
            "optimizer %s is not marked trace_safe (its update cannot be "
            "captured in a fixed trace)" % type(opt).__name__)


def _metric_leaves(metric):
    """Flatten a metric (possibly composite) into device-updatable leaves."""
    from .. import metric as metric_mod
    if metric is None:
        return []
    if isinstance(metric, metric_mod.CompositeEvalMetric):
        leaves = []
        for child in metric.metrics:
            leaves.extend(_metric_leaves(child))
        return leaves
    if not metric.supports_device_update():
        raise CompiledStepUnsupported(
            "metric %s (%s) has no traced_update device twin"
            % (metric.name, type(metric).__name__))
    return [metric]


class _ShardInfo:
    """Static layout of a ``shard_update=True`` step (docs/PERF.md "Sharded
    weight update"): the 1-D dp mesh, per-parameter flat/padded metas
    (parallel/zero.py), the wire-format threshold (None = fp32 reduce), and
    the ``r:`` aux key per parameter when the 2-bit codec is on."""

    def __init__(self, mesh, dp, wire, metas, residual_keys):
        self.mesh = mesh
        self.dp = dp
        self.wire = wire            # quantization threshold, or None
        self.metas = metas          # pkey -> parallel.zero.ParamMeta
        self.residual_keys = residual_keys   # pkey -> "r:<name>"

    def state_spec(self, key):
        """The PartitionSpec a state entry holds in steady state: optimizer
        leaves live flat-sharded over dp (the ZeRO 1/N win), residual rows
        shard over the replica axis, everything else is replicated."""
        from jax.sharding import PartitionSpec as P
        if key.startswith("o:"):
            return P("dp")
        if key.startswith("r:"):
            return P("dp", None)
        return P()


def _resolve_donate(donate, ctx):
    if donate != "auto":
        return bool(donate)
    # CPU XLA cannot alias donated buffers — requesting donation there only
    # produces a "donated buffers were not usable" warning per compile.
    # Key on the STEP's device, not jax.default_backend(): a cpu-bound
    # module in a TPU-backed process must not request donation either.
    if ctx is not None:
        try:
            return ctx.jax_device().platform != "cpu"
        except Exception:
            pass
    import jax
    return jax.default_backend() != "cpu"


# ---------------------------------------------------------------------------
# the step
# ---------------------------------------------------------------------------

class CompiledTrainStep:
    """One-dispatch training over a window of ``steps_per_call`` batches.

    Construction is via :meth:`from_module` / :meth:`from_block`.  The
    instance owns a flat ``state`` dict of NDArray handles (``p:`` params,
    ``a:`` executor aux, ``o:`` optimizer-state leaves, ``m:`` metric
    accumulators) — the SAME handles the module/block reads — all registered
    as CachedOp aux, so every dispatch writes the new values back in place.
    """

    def __init__(self, microstep, state_nd, optimizer, opt_bindings,
                 opt_indices, metrics, metric_keys, n_inputs, keys_per_step,
                 steps_per_call, ctx, donate, owner=None, shard=None):
        if steps_per_call < 1:
            raise ValueError("steps_per_call must be >= 1")
        self._shard = shard
        self._microstep = microstep
        self.state = state_nd
        self._state_names = sorted(state_nd)
        self._optimizer = optimizer
        self._opt_bindings = opt_bindings
        self._opt_indices = opt_indices
        self._metrics = metrics
        self._metric_keys = metric_keys
        self._n_inputs = n_inputs
        self._keys_per_step = max(1, keys_per_step)
        self.steps_per_call = steps_per_call
        self._ctx = ctx
        self._owner = owner
        flags = {"donate_params": True} if _resolve_donate(donate, ctx) \
            else {}
        self.cached_op = CachedOp(self._make_forward_fn(), state_nd,
                                  aux_names=tuple(state_nd), flags=flags,
                                  name="train_step")  # mxmem: nodonate(donate='auto' resolves per backend at dispatch: CPU XLA cannot alias, accelerator backends donate via donate_params — see _resolve_donate)

    # -- trace ----------------------------------------------------------
    def _make_forward_fn(self):
        microstep = self._microstep
        state_names = self._state_names
        opt_bindings = self._opt_bindings
        metrics = self._metrics
        metric_keys = self._metric_keys
        opt = self._optimizer
        n_keys = self._keys_per_step

        shard = self._shard

        def apply_optimizer(carry, new_carry, grads, lr_t, t_t):
            """Run the optimizer's own (traced) update kernels over NDArray
            wrappers of the carry values; harvest the mutated handles."""
            if shard is not None:
                return apply_optimizer_sharded(carry, new_carry, grads,
                                               lr_t, t_t)
            staged = []
            for index, pkey, template, leaf_keys in opt_bindings:
                weight = NDArray(new_carry.get(pkey, carry[pkey]))
                grad = NDArray(grads[pkey])
                leaves = iter([NDArray(carry[k]) for k in leaf_keys])
                state = _rebuild_state(template, leaves)
                staged.append((index, pkey, weight, grad, state, leaf_keys))
            with _step_hyperparams(opt, lr_t, t_t):
                for index, pkey, weight, grad, state, leaf_keys in staged:
                    opt.update_multi_precision(index, weight, grad, state)
            for index, pkey, weight, grad, state, leaf_keys in staged:
                new_carry[pkey] = weight._data
                for key, leaf in zip(leaf_keys, _state_leaf_nds(state)):
                    new_carry[key] = leaf._data

        def apply_optimizer_sharded(carry, new_carry, grads, lr_t, t_t):
            """The ZeRO variant: ONE shard_map region updates every
            parameter's flat 1/N slice on its owning replica (optimizer
            state enters as true dp-sharded vectors, so the in_specs are
            free slicing, not resharding), then all-gathers the updated
            shards.  For elementwise optimizers this is bitwise the full
            update (docs/PERF.md).  With the 2-bit wire format on, each
            replica EF-quantizes the full flat gradient against its own
            residual row and the int8 codes cross the wire reduce-scattered
            as int32."""
            import jax
            import jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from jax import shard_map
            from ..parallel.collectives import allgather
            from ..parallel.zero import (flatten_param, unflatten_param,
                                         quantized_reduce_scatter)
            axis = "dp"
            wire_t = shard.wire
            repl = NamedSharding(shard.mesh, P())
            wf, gf, sf, rf = [], [], [], []
            for index, pkey, template, leaf_keys in opt_bindings:
                meta = shard.metas[pkey]
                # pin the raw gradient (and weight) REPLICATED before it
                # feeds the shard_map: without the constraint GSPMD
                # back-propagates the region's P("dp") in_specs into the
                # vjp itself and partitions the backward reductions —
                # different summation order, so grads drift a ulp from the
                # replicated program and the bitwise parity gate breaks
                w_full = jax.lax.with_sharding_constraint(
                    new_carry.get(pkey, carry[pkey]), repl)
                g_full = jax.lax.with_sharding_constraint(grads[pkey], repl)
                wf.append(flatten_param(w_full, meta.padded))
                gf.append(flatten_param(g_full, meta.padded))
                sf.append(tuple(carry[k] for k in leaf_keys))
                if wire_t is not None:
                    rf.append(carry[shard.residual_keys[pkey]])
            wf, gf, sf, rf = tuple(wf), tuple(gf), tuple(sf), tuple(rf)

            def region(wl, gl, sl, rl, lr_v, t_v):
                staged = []
                new_r = []
                for i, (index, pkey, template, leaf_keys) in \
                        enumerate(opt_bindings):
                    if wire_t is not None:
                        # fit-path gradients are replicated (the batch is),
                        # so the psum_scatter/dp mean of dp identical
                        # dequantized copies models exactly one quantizer
                        g_shard, r_new = quantized_reduce_scatter(
                            gl[i], rl[i][0], wire_t, axis, shard.dp)
                        new_r.append(r_new[None])
                    else:
                        g_shard = gl[i]   # in_spec P("dp") sliced it
                    weight = NDArray(wl[i])
                    grad = NDArray(g_shard)
                    leaves = iter([NDArray(v) for v in sl[i]])
                    state = _rebuild_state(template, leaves)
                    staged.append((index, weight, grad, state))
                with _step_hyperparams(opt, lr_v, t_v):
                    for index, weight, grad, state in staged:
                        opt.update_multi_precision(index, weight, grad,
                                                   state)
                out_w = tuple(allgather(weight._data, axis)
                              for _, weight, _, _ in staged)
                out_s = tuple(tuple(leaf._data
                                    for leaf in _state_leaf_nds(state))
                              for _, _, _, state in staged)
                return out_w, out_s, tuple(new_r)

            s_specs = tuple(tuple(P(axis) for _ in s) for s in sf)
            r_specs = tuple(P(axis, None) for _ in rf)
            region_sh = shard_map(
                region, mesh=shard.mesh,
                in_specs=(tuple(P(axis) for _ in wf),
                          tuple(P() if wire_t is not None else P(axis)
                                for _ in gf),
                          s_specs, r_specs, P(), P()),
                out_specs=(tuple(P() for _ in wf), s_specs, r_specs),
                check_vma=False)
            new_w, new_s, new_r = region_sh(wf, gf, sf, rf, lr_t, t_t)
            for i, (index, pkey, template, leaf_keys) in \
                    enumerate(opt_bindings):
                meta = shard.metas[pkey]
                new_carry[pkey] = unflatten_param(new_w[i], meta.shape,
                                                  meta.size)
                for key, leaf in zip(leaf_keys, new_s[i]):
                    new_carry[key] = leaf
                if wire_t is not None:
                    new_carry[shard.residual_keys[pkey]] = new_r[i]

        def body(carry, xs):
            # the scopes name the step's device operations in a trace:
            # fwd and bwd (in the microstep), opt, metric
            import jax
            import jax.numpy as jnp
            t_t, lr_t, keys_t = xs["t"], xs["lr"], xs["keys"]
            grads, updates, preds, labels, extra = microstep(
                carry, xs["in"], keys_t)
            new_carry = dict(carry)
            new_carry.update(updates)
            with jax.named_scope("opt"):
                apply_optimizer(carry, new_carry, grads, lr_t, t_t)
            deltas = []
            with jax.named_scope("metric"):
                for m, (skey, ckey) in zip(metrics, metric_keys):
                    stat, count = m.traced_update(labels, preds)
                    new_carry[skey] = carry[skey] + stat
                    new_carry[ckey] = carry[ckey] + count
                    deltas += [stat, count]
            if extra is not None:
                y = extra
            elif deltas:
                y = jnp.stack([jnp.asarray(d, jnp.float32) for d in deltas])
            else:
                y = jnp.float32(0.0)
            return new_carry, y

        # the compiled fit step's declared worst case: params + grads +
        # optimizer slots live at once, plus the sharded-update region's
        # full-weight gather temps (the symbolic sites MEM_MAP catalogs)
        # mxmem: budget(hbm=1GB)
        def forward_fn(p, t_nd, lr_nd, *input_nds):
            import jax
            import jax.numpy as jnp
            from .. import random as _random

            window = int(t_nd.shape[0])
            carry = {k: p[k]._data for k in state_names}
            in_vals = [x._data for x in input_nds]
            # one key row per (microstep, rng site), all derived from the
            # CachedOp's per-call key input (random.key_override is active)
            keys = jnp.stack([
                jnp.stack([_random.next_key() for _ in range(n_keys)])
                for _ in range(window)])
            if window == 1:
                carry, y = body(carry, {
                    "t": t_nd._data[0], "lr": lr_nd._data[0],
                    "keys": keys[0], "in": [v[0] for v in in_vals]})
                ys = jnp.asarray(y)[None]
            else:
                carry, ys = jax.lax.scan(body, carry, {
                    "t": t_nd._data, "lr": lr_nd._data, "keys": keys,
                    "in": in_vals})
            if shard is not None:
                # pin every carried output to its canonical steady-state
                # sharding: without the constraint GSPMD may pick a
                # different output layout than the inputs arrived with,
                # and step 2 would silently recompile on the changed
                # input shardings (a stealth recompile cache_stats cannot
                # see — its signature is shapes/dtypes only)
                from jax.sharding import NamedSharding
                carry = {k: jax.lax.with_sharding_constraint(
                             v, NamedSharding(shard.mesh,
                                              shard.state_spec(k)))
                         for k, v in carry.items()}
            for k in state_names:
                p[k]._set_data(carry[k])
            return NDArray(ys)

        return forward_fn

    # -- dispatch -------------------------------------------------------
    def _hyper_vectors(self, window):
        opt = self._optimizer
        base = opt.num_update
        ts, lrs = [], []
        for k in range(1, window + 1):
            t = base + k
            ts.append(float(t))
            lrs.append(float(opt.lr_scheduler(t))
                       if opt.lr_scheduler is not None else float(opt.lr))
        if self._shard is not None:
            # every step input must live on the mesh: a vector committed to
            # a single device cannot enter the same jit as dp-sharded state
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            from ..ndarray import from_jax
            repl = NamedSharding(self._shard.mesh, P())
            return (from_jax(jax.device_put(_np.asarray(ts, _np.float32),
                                            repl), ctx=self._ctx),
                    from_jax(jax.device_put(_np.asarray(lrs, _np.float32),
                                            repl), ctx=self._ctx))
        from ..ndarray import array
        return (array(_np.asarray(ts, _np.float32), ctx=self._ctx),
                array(_np.asarray(lrs, _np.float32), ctx=self._ctx))

    def _advance_counts(self, window):
        opt = self._optimizer
        for index in self._opt_indices:
            count = opt._index_update_count.get(
                index, opt.begin_num_update) + window
            opt._index_update_count[index] = count
            opt.num_update = max(count, opt.num_update)

    def run_window(self, batches_io):  # mxflow: hot (compiled train step)
        """Train on a window of 1..steps_per_call batches in ONE dispatch.

        ``batches_io``: one tuple of input NDArrays per batch, in the
        step's input order (data..., then labels...).  Returns the step's
        per-microstep output array WITHOUT fetching it (shape [W] losses
        for from_block steps, [W, 2*n_metrics] accumulator deltas for
        from_module steps)."""
        import jax.numpy as jnp
        window = len(batches_io)
        if not 1 <= window <= self.steps_per_call:
            raise ValueError("window of %d batches vs steps_per_call=%d"
                             % (window, self.steps_per_call))
        if self._n_inputs is not None and \
                len(batches_io[0]) != self._n_inputs:
            raise ValueError("batch provides %d inputs, step expects %d"
                             % (len(batches_io[0]), self._n_inputs))
        with profiler.span("step.hyper"):
            t_nd, lr_nd = self._hyper_vectors(window)
        stacked = []
        with profiler.span("step.stack"):
            for j in range(len(batches_io[0])):
                vals = [b[j]._data for b in batches_io]
                val = jnp.stack(vals)
                if self._shard is not None:
                    # replicate the window onto the mesh (the shard_update
                    # fit path keeps the batch replicated — the sharding is
                    # of the UPDATE and optimizer state, docs/PERF.md)
                    import jax
                    from jax.sharding import NamedSharding, \
                        PartitionSpec as P
                    val = jax.device_put(
                        val, NamedSharding(self._shard.mesh, P()))
                stacked.append(_wrap(val, ctx=self._ctx))
        with autograd.train_mode():
            out = self.cached_op(self.state, t_nd, lr_nd, *stacked)
        self._advance_counts(window)
        if self._owner is not None:
            self._owner._params_dirty = True
        return out

    def step(self, *inputs):
        """Single-batch convenience over :meth:`run_window`."""
        return self.run_window([tuple(inputs)])

    def sync_metric(self):
        """Fetch the on-device metric accumulators into their EvalMetric
        objects and zero them.  This is a host sync — the ONLY one the
        compiled path performs — so call it at metric_interval boundaries
        or epoch end, never per batch."""
        for m, (skey, ckey) in zip(self._metrics, self._metric_keys):
            stat = float(_np.asarray(self.state[skey].asnumpy()))  # mxflow: sync-ok(metric boundary: the one sanctioned fetch of the compiled path)
            count = float(_np.asarray(self.state[ckey].asnumpy()))  # mxflow: sync-ok(metric boundary: the one sanctioned fetch of the compiled path)
            if stat or count:
                m._device_accumulate(stat, count)
            with autograd.pause():
                # one fresh buffer per slot: sharing one zero across slots
                # would alias state entries and break buffer donation
                # ("attempt to donate the same buffer twice")
                self.state[skey]._set_data(self._committed_zero())
                self.state[ckey]._set_data(self._committed_zero())

    def _committed_zero(self):
        """A device-committed f32 scalar zero.  The steady-state accumulator
        buffers are jit outputs (committed to their device); resetting with
        an UNcommitted constant would flip the jit cache key and silently
        recompile the whole step on the next window."""
        import jax
        if self._shard is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            dev = NamedSharding(self._shard.mesh, P())
        elif self._ctx is not None:
            dev = self._ctx.jax_device()
        else:
            dev = jax.devices()[0]
        # a fresh numpy scalar per call: jnp constants can be cached, and a
        # shared buffer across state slots would defeat per-slot donation
        return jax.device_put(_np.zeros((), _np.float32), dev)

    def cache_stats(self):
        """The underlying CachedOp's per-signature compile counters."""
        return self.cached_op.cache_stats()

    # ------------------------------------------------------------------
    # frontends
    # ------------------------------------------------------------------
    @classmethod
    def from_module(cls, module, eval_metric=None, steps_per_call=1,
                    donate="auto", shard_update=False, wire_format=None,
                    wire_threshold=0.5, residual_store=None):
        """Capture a bound Module's forward+backward+update as one CachedOp.

        State handles are the executor's own ``arg_dict``/``aux_dict``
        entries and the updater's state arrays — so ``get_params()``,
        ``save_optimizer_states()`` and crash-resume (docs/ROBUSTNESS.md)
        see exactly what the step trains, and a run killed mid-epoch
        resumes bitwise like the eager path.

        ``shard_update=True`` builds the step over the default 1-D dp mesh
        (all local devices): parameters/aux replicate across the mesh while
        optimizer state converts IN PLACE to flat dp-sharded vectors
        (1/N bytes per replica — ZeRO-1/2), and the update runs per-shard
        inside a shard_map region (bitwise-equal to the replicated step for
        elementwise optimizers).  The SAME updater state handles now hold
        the flat vectors, so save/load_optimizer_states and crash-resume
        keep working bitwise — a restored flat vector is recognized by its
        padded size and re-placed sharded.  ``wire_format="2bit"`` adds the
        error-feedback quantized gradient reduce, with per-replica residual
        rows riding as ``r:`` aux entries keyed in ``residual_store`` (one
        shared :class:`~mxnet_tpu.gradient_compression.ResidualStore`; by
        default the module's own, so residuals carry across fit calls)."""
        handles_fn = getattr(module, "_compiled_step_handles", None)
        if handles_fn is None:
            raise CompiledStepUnsupported(
                "%s has no compiled-step support" % type(module).__name__)
        h = handles_fn()
        exe = h["executor"]
        opt = h["optimizer"]
        updater = h["updater"]
        if updater is None:
            raise CompiledStepUnsupported("no local updater")
        _check_optimizer(opt)
        if wire_format not in (None, "2bit"):
            raise ValueError("unknown wire_format %r (supported: '2bit')"
                             % (wire_format,))
        if wire_format is not None and not shard_update:
            raise ValueError("wire_format=%r requires shard_update=True"
                             % (wire_format,))
        shard_mesh = None
        if shard_update:
            if not getattr(opt, "elementwise", False):
                raise CompiledStepUnsupported(
                    "optimizer %s is not elementwise: the ZeRO sharded "
                    "update runs the update rule on flat 1/N parameter "
                    "slices, which is only the full update for per-element "
                    "rules" % type(opt).__name__)
            from ..parallel import make_mesh
            shard_mesh = make_mesh()
        metrics = _metric_leaves(eval_metric)

        param_names = [n for n in h["param_names"] if n in exe.arg_names]
        input_names = list(h["data_names"]) + list(h["label_names"])
        for req_name in h["data_names"]:
            if req_name not in exe.arg_names:
                raise CompiledStepUnsupported(
                    "data input %r is not a graph argument" % req_name)
        wrt_names = [n for n in param_names
                     if exe.grad_req.get(n, "null") not in ("null",)]
        for n in wrt_names:
            if exe.grad_req[n] != "write":
                raise CompiledStepUnsupported(
                    "grad_req=%r for %r (only 'write' is capturable)"
                    % (exe.grad_req[n], n))
        if not wrt_names:
            raise CompiledStepUnsupported("no trainable parameters")

        fn = exe._build_fn(True)
        n_rng = exe._n_rng
        aux_update_names = list(exe._aux_update_names)
        aux_names = list(exe.aux_names)
        arg_names = list(exe.arg_names)

        # ensure optimizer state exists under the eager updater's indices so
        # save/load_optimizer_states and resume interoperate unchanged
        name_to_index = {n: i for i, n in enumerate(param_names)}
        for n in wrt_names:
            index = name_to_index[n]
            if index not in updater.states:
                updater.states[index] = \
                    opt.create_state_multi_precision(index, exe.arg_dict[n])
                updater.states_synced[index] = True
            elif not updater.states_synced.get(index, True):
                updater.states[index] = updater._to_nd(
                    updater.states[index], exe.arg_dict[n].context)
                updater.states_synced[index] = True

        state_nd = {}
        for n in param_names:
            state_nd["p:" + n] = exe.arg_dict[n]
        for n in aux_names:
            state_nd["a:" + n] = exe.aux_dict[n]
        opt_bindings = []
        opt_indices = []
        for n in wrt_names:
            index = name_to_index[n]
            template = updater.states[index]
            leaf_keys = ["o:%s:%d" % (n, i)
                         for i in range(len(_state_leaf_nds(template)))]
            for key, leaf in zip(leaf_keys, _state_leaf_nds(template)):
                state_nd[key] = leaf
            opt_bindings.append((index, "p:" + n, template, leaf_keys))
            opt_indices.append(index)

        shard = None
        if shard_mesh is not None:
            shard = cls._shard_state(
                state_nd, opt_bindings, exe, shard_mesh, wire_format,
                wire_threshold, residual_store, h)
        metric_keys = cls._metric_state(state_nd, metrics, h["context"],
                                        mesh=shard_mesh)

        input_pos = {n: i for i, n in enumerate(input_names)}
        label_idx = [input_pos[n] for n in h["label_names"]]
        wrt_pos = {n: i for i, n in enumerate(wrt_names)}

        def microstep(carry, batch_vals, keys_t):
            import jax
            import jax.numpy as jnp
            aux_vals = [carry["a:" + n] for n in aux_names]

            def arg_vals(wrt_vals):
                vals = []
                for n in arg_names:
                    if n in input_pos:
                        vals.append(batch_vals[input_pos[n]])
                    elif n in wrt_pos:
                        vals.append(wrt_vals[wrt_pos[n]])
                    else:
                        vals.append(carry["p:" + n])
                return vals

            def f_wrt(*wv):
                return tuple(fn(arg_vals(wv), aux_vals, keys_t))

            with jax.named_scope("fwd"):
                outs, vjp = jax.vjp(f_wrt,
                                    *[carry["p:" + n] for n in wrt_names])
            n_graph = len(outs) - len(aux_update_names)
            # the fit loop's backward() contract: ones cotangents on every
            # graph output, zeros on the appended BN running-stat tail
            cts = tuple(jnp.ones_like(o) for o in outs[:n_graph]) + \
                tuple(jnp.zeros_like(o) for o in outs[n_graph:])
            with jax.named_scope("bwd"):
                grad_vals = vjp(cts)
            grads = {"p:" + n: g for n, g in zip(wrt_names, grad_vals)}
            updates = {"a:" + n: v
                       for n, v in zip(aux_update_names, outs[n_graph:])}
            preds = list(outs[:n_graph])
            labels = [batch_vals[i] for i in label_idx]
            return grads, updates, preds, labels, None

        return cls(microstep, state_nd, opt, opt_bindings, opt_indices,
                   metrics, metric_keys, len(input_names), n_rng,
                   steps_per_call, h["context"], donate, owner=module,
                   shard=shard)

    @staticmethod
    def _shard_state(state_nd, opt_bindings, exe, mesh, wire_format,
                     wire_threshold, residual_store, h):
        """Re-place the step's state for shard_update mode, IN PLACE on the
        live handles: params/aux replicate over the mesh (a single-device-
        committed array cannot enter the same jit as mesh-sharded state),
        optimizer-state leaves flatten+pad to dp-sharded vectors (the
        updater now holds — and checkpoints — the flat form; a leaf already
        flat from a resumed checkpoint is re-placed bitwise), and the wire
        format's per-replica residual rows are created (or adopted from the
        shared ResidualStore) as ``r:`` aux entries."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..ndarray import from_jax
        from ..parallel.zero import (param_meta, check_flat_state,
                                     flatten_param)

        dp = int(mesh.shape["dp"])
        repl = NamedSharding(mesh, P())
        vec = NamedSharding(mesh, P("dp"))
        row = NamedSharding(mesh, P("dp", None))

        for key, nd in state_nd.items():
            if key.startswith(("p:", "a:")):
                nd._set_data(jax.device_put(nd._data, repl))

        metas, residual_keys = {}, {}
        store = None
        if wire_format == "2bit":
            store = residual_store
            if store is None:
                make_store = h.get("residual_store")
                store = make_store() if make_store is not None else None
            if store is None:
                from ..gradient_compression import ResidualStore
                store = ResidualStore()
        for index, pkey, template, leaf_keys in opt_bindings:
            name = pkey[2:]
            weight = exe.arg_dict[name]
            meta = param_meta(name, weight._data, dp)
            metas[pkey] = meta
            for key in leaf_keys:
                leaf = state_nd[key]
                padded = check_flat_state(name, int(leaf._data.size),
                                          meta.size, dp)
                flat = flatten_param(leaf._data.reshape(-1), padded)
                leaf._set_data(jax.device_put(flat, vec))
            if store is not None:
                rkey = "r:" + name

                def make_residual(meta=meta, dtype=weight._data.dtype):
                    return from_jax(
                        jax.device_put(
                            jnp.zeros((dp, meta.padded), dtype), row),
                        ctx=h["context"])

                res_nd = store.get_or_create(name, make_residual)
                if tuple(res_nd.shape) != (dp, meta.padded):
                    raise ValueError(
                        "sharded-update flattener: residual for parameter "
                        "%r has shape %s; expected (%d, %d) for dp=%d"
                        % (name, tuple(res_nd.shape), dp, meta.padded, dp))
                # adopt a carried-over residual onto this mesh (bitwise)
                res_nd._set_data(jax.device_put(res_nd._data, row))
                state_nd[rkey] = res_nd
                residual_keys[pkey] = rkey
        return _ShardInfo(mesh, dp,
                          wire_threshold if wire_format == "2bit" else None,
                          metas, residual_keys)

    @classmethod
    def from_block(cls, block, loss_fn, optimizer, n_inputs=1,
                   eval_metric=None, steps_per_call=1, donate="auto"):
        """Capture a gluon block + explicit loss + optimizer as one CachedOp.

        ``loss_fn(outputs, *labels) -> scalar NDArray`` over the block's
        outputs; ``n_inputs`` leading step inputs feed the block, the rest
        go to the loss (and metrics) as labels.  Parameter/optimizer state
        is updated in place in the block's own Parameter storage."""
        from ..gluon.block import split_param_names
        _check_optimizer(optimizer)
        metrics = _metric_leaves(eval_metric)
        params = {p.name: p for p in block.collect_params().values()}
        train_names, frozen_names = split_param_names(block)
        param_nd = {n: params[n].data() for n in params}
        ctx = next(iter(param_nd.values())).context if param_nd else None

        state_nd = {"p:" + n: param_nd[n] for n in params}
        opt_bindings = []
        for n in train_names:
            template = optimizer.create_state_multi_precision(n, param_nd[n])
            leaf_keys = ["o:%s:%d" % (n, i)
                         for i in range(len(_state_leaf_nds(template)))]
            for key, leaf in zip(leaf_keys, _state_leaf_nds(template)):
                state_nd[key] = leaf
            opt_bindings.append((n, "p:" + n, template, leaf_keys))
        metric_keys = cls._metric_state(state_nd, metrics, ctx)

        def microstep(carry, batch_vals, keys_t):
            import jax
            import jax.numpy as jnp
            from ..gluon.block import functional_call
            x_vals = batch_vals[:n_inputs]
            label_vals = batch_vals[n_inputs:]
            frozen_vals = {n: carry["p:" + n] for n in frozen_names}

            def loss_of(train_vals):
                full = dict(frozen_vals)
                full.update(train_vals)
                outs, new_aux = functional_call(block, full, *x_vals,
                                                training=True,
                                                rng_key=keys_t[0])
                with jax.named_scope("loss"):
                    loss = loss_fn([NDArray(o) for o in outs],
                                   *[NDArray(v) for v in label_vals])
                # mxnet reductions keep a (1,) shape; grad needs a scalar
                return loss._data.reshape(()), (new_aux, outs)

            with jax.named_scope("fwd"):
                loss, vjp, (new_aux, outs) = jax.vjp(
                    loss_of, {n: carry["p:" + n] for n in train_names},
                    has_aux=True)
            with jax.named_scope("bwd"):
                grad_vals, = vjp(jnp.ones_like(loss))
            grads = {"p:" + n: grad_vals[n] for n in train_names}
            updates = {"p:" + n: v for n, v in new_aux.items()}
            return grads, updates, list(outs), list(label_vals), loss

        return cls(microstep, state_nd, optimizer, opt_bindings,
                   list(train_names), metrics, metric_keys, None,
                   1, steps_per_call, ctx, donate)

    @staticmethod
    def _metric_state(state_nd, metrics, ctx, mesh=None):
        """Allocate the (sum, count) scalar accumulator pair per metric
        (device-committed, matching the steady-state jit-output buffers —
        see _committed_zero; mesh-replicated under shard_update)."""
        import jax
        from ..ndarray import from_jax
        metric_keys = []
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            dev = NamedSharding(mesh, P())
        else:
            dev = ctx.jax_device() if ctx is not None else jax.devices()[0]
        for j, _m in enumerate(metrics):
            skey, ckey = "m:%d:s" % j, "m:%d:n" % j
            for key in (skey, ckey):
                state_nd[key] = from_jax(
                    jax.device_put(_np.zeros((), _np.float32), dev), ctx=ctx)
            metric_keys.append((skey, ckey))
        return metric_keys
