"""CachedOp: whole-graph compilation of an imperative forward.

Reference: src/imperative/cached_op.{cc,h} — Gluon ``hybridize()`` traces the
python forward once into an NNVM graph and replays it with pre-planned memory
(StaticForward) or re-inferred shapes (DynamicForward); registered as the
``_CachedOp`` op so the whole call is one node on the autograd tape.

TPU-native redesign: the python forward runs once under ``jax.jit`` tracing —
NDArray handles wrap tracers, every registered op applies its jax fcompute, and
XLA compiles the entire model as ONE module (the reference's whole-graph
executor + memory planner + op bulking, all in the compiler).  Notes:

  * static_alloc/static_shape ≙ XLA buffer assignment + (optionally) donation;
    there is no dynamic path to choose — shapes are static per compiled
    signature, and a new input signature triggers a cached recompile (the
    analog of bucketed DynamicForward).
  * training vs inference are two cache entries (mode changes dropout/BN).
  * aux state (BatchNorm running stats) is threaded functionally: mutations
    layers make to aux NDArray handles during the trace are captured as extra
    outputs and written back after the call.
  * under autograd.record() the call runs via jax.vjp over the jitted
    function, and ONE tape node carries the precomputed compiled vjp —
    exactly mirroring ``_CachedOp``'s single-node recording (cached_op.cc:228).
"""
from __future__ import annotations

import threading as _threading

from . import autograd
from . import profiler
from .base import MXNetError

__all__ = ["CachedOp"]


def remat_policy(flags):
    """(whether to recompute, the jax.checkpoint policy) that hybridize
    flags ask for: ``remat`` (else MXNET_BACKWARD_DO_MIRROR) and
    ``remat_policy`` (else MXNET_REMAT_POLICY): a jax.checkpoint_policies
    name, 'full', or (the flag alone) a tuple of the names of values to keep
    (``jax.ad_checkpoint.checkpoint_name``; the attention call names
    ``ops.pallas_ops.ATTENTION_RESIDUALS``) while the rest is recomputed.
    Shared by the CachedOp and by a hybridized child block that is called
    inside someone else's trace (gluon/block.py)."""
    from . import env
    remat = flags.get("remat")
    if remat is None:
        remat = env.get("MXNET_BACKWARD_DO_MIRROR")
    if not remat:
        return False, None
    import jax
    asked = flags.get("remat_policy")
    if asked is None:
        asked = env.get("MXNET_REMAT_POLICY")
    if not asked or asked == "full":
        return True, None
    if isinstance(asked, tuple) and all(isinstance(n, str) for n in asked):
        return True, jax.checkpoint_policies.save_only_these_names(*asked)
    policy = getattr(jax.checkpoint_policies, asked, None) \
        if isinstance(asked, str) else None
    if policy is None:
        raise MXNetError(
            "unknown remat policy %r: a name in jax.checkpoint_policies, "
            "'full', or a tuple of checkpoint_name names to keep" % (asked,))
    return True, policy


class CachedOp:
    def __init__(self, forward_fn, param_dict, aux_names=(), flags=None,
                 name="traced"):
        """
        forward_fn(params: dict name->NDArray, *inputs: NDArray) -> NDArray or
            list/tuple of NDArray.  Must be jax-traceable (the gluon
            hybrid_forward path is).
        param_dict: dict name -> NDArray handle (live parameter storage).
        aux_names: parameter names whose mutation during forward must be
            captured and written back (BatchNorm running stats).
        name: the jitted function's name: the XLA module is ``jit_<name>``
            in traces and in the persistent cache's key.
        """
        self._forward_fn = forward_fn
        self._name = name
        self._param_names = sorted(param_dict.keys())
        self._aux_names = [n for n in self._param_names if n in set(aux_names)]
        self._flags = dict(flags or {})
        self._jitted = {}          # training(bool) -> jitted fn
        self._bwd_jitted = {}      # training(bool) -> jitted backward
        self._out_tree = None      # 'single' | 'list'
        self._sig_stats = {}       # signature str -> [hits, misses]
        self._stats_lock = _threading.Lock()

    # ------------------------------------------------------------------
    @staticmethod
    def _signature(training, input_vals):
        """Compile-cache key of one dispatch, as a readable string.

        jax.jit keys its executable cache on the argument shapes/dtypes (and
        static state); parameters keep one shape for the life of the op, so
        the observable signature is (mode, input shapes/dtypes) — e.g.
        ``infer|float32[4,16]``.  A new signature means XLA compiles a fresh
        executable (the bucketed-DynamicForward recompile analog)."""
        parts = ["train" if training else "infer"]
        for v in input_vals:
            shape = ",".join(str(d) for d in getattr(v, "shape", ()))
            parts.append("%s[%s]" % (getattr(v, "dtype", "?"), shape))
        return "|".join(parts)

    def _note_dispatch(self, training, input_vals):
        """Count the dispatch; its signature where it is the first of that
        signature (the program is then traced, lowered and compiled or
        loaded), else None."""
        sig = self._signature(training, input_vals)
        with self._stats_lock:
            rec = self._sig_stats.get(sig)
            if rec is None:
                self._sig_stats[sig] = [0, 1]
            else:
                rec[0] += 1
        return sig if rec is None else None

    def _first_call(self, jitted, vals, signature):
        """A signature's first call in its parts, ahead of the dispatch:
        ``cachedop.lower`` (trace and lower: Python and JAX) and
        ``cachedop.compile`` (the backend's compile, or the persistent
        cache's load: the recorder charges it there, and its
        ``compile.cache_hits`` says which).  The call that follows finds both
        in jax's caches, so nothing is traced or compiled twice.  The
        executable goes to the recorder, which can name its instructions'
        scopes when asked (profiler.program_ops)."""
        with profiler.span("cachedop.lower", op=self._name):
            lowered = jitted.lower(*vals)
        with profiler.span("cachedop.compile", op=self._name):
            compiled = lowered.compile()
        profiler.program(self._name, signature, compiled)

    def cache_stats(self):
        """Per-signature compile-cache counters (debugging / serving aid).

        Returns ``{"signatures": {sig: {"hits": h, "misses": m}},
        "hits": H, "misses": M, "recompiles": M}``.  A *miss* is the first
        dispatch of a signature (jax.jit traces + XLA compiles); every later
        dispatch of that signature is a *hit* (executable-cache lookup).
        ``recompiles`` == total misses, the number the serving warmup gate
        asserts stays flat in steady state.  Caveat: a parameter cast()
        changes jit's cache key without changing the input signature, so it
        recompiles without a counted miss — rebuild the CachedOp after
        casting instead."""
        with self._stats_lock:
            sigs = {sig: {"hits": rec[0], "misses": rec[1]}
                    for sig, rec in self._sig_stats.items()}
        hits = sum(r["hits"] for r in sigs.values())
        misses = sum(r["misses"] for r in sigs.values())
        return {"signatures": sigs, "hits": hits, "misses": misses,
                "recompiles": misses}

    def reset_cache_stats(self):
        """Zero the hit/miss counters (does NOT drop compiled executables)."""
        with self._stats_lock:
            self._sig_stats.clear()

    # ------------------------------------------------------------------
    def _make_traced(self, training):
        from .ndarray import NDArray
        forward_fn = self._forward_fn
        names = self._param_names
        aux_names = self._aux_names
        n_params = len(names)

        def traced(*vals):
            # vals = param vals (ordered) + input vals + (rng_key,)
            key = vals[-1]
            param_vals = vals[:n_params]
            input_vals = vals[n_params:-1]
            param_nds = {n: NDArray(v) for n, v in zip(names, param_vals)}
            input_nds = [NDArray(v) for v in input_vals]
            from . import random as _random
            with autograd._RecordingStateScope(False, training), \
                    _random.key_override(key):
                out = forward_fn(param_nds, *input_nds)
            if isinstance(out, (list, tuple)):
                outs = list(out)
                self._out_tree = "list"
            else:
                outs = [out]
                self._out_tree = "single"
            out_vals = tuple(o._data for o in outs)
            aux_vals = tuple(param_nds[n]._data for n in aux_names)
            return out_vals + aux_vals

        traced.__name__ = traced.__qualname__ = self._name
        return traced

    def _make_lowerable(self, training):
        """The traced forward with the remat policy applied (pre-jit).

        ``remat`` is the MXNET_BACKWARD_DO_MIRROR analog (reference
        docs/faq/env_var.md:140-145, docs/architecture/note_memory.md): the
        reference re-executes cheap forward nodes during backward to shed
        activation memory; here ``jax.checkpoint`` makes the vjp recompute
        the forward instead of saving residuals, with an optional named
        policy from jax.checkpoint_policies selecting what is still saved
        (e.g. "dots_saveable" keeps matmul outputs, recomputes the rest)."""
        traced = self._make_traced(training)
        remat, policy = remat_policy(self._flags)
        if not remat:
            return traced
        import jax
        return jax.checkpoint(traced, policy=policy)

    def _get_jitted(self, training):
        fn = self._jitted.get(training)
        if fn is None:
            import jax
            kwargs = {}
            if self._flags.get("donate_params"):
                # donate the aux-listed parameter buffers: every aux entry is
                # written back after the call (its input buffer is dead the
                # moment the XLA program consumes it), so XLA may alias the
                # input allocation to the matching output — in-place
                # param/momentum update at the buffer level, the analog of
                # the reference's shared-memory-pool trick
                # (graph_executor.cc:927).  Non-aux params are NOT donated:
                # their handles keep pointing at the input buffer.
                aux = set(self._aux_names)
                kwargs["donate_argnums"] = tuple(
                    i for i, n in enumerate(self._param_names) if n in aux)
            fn = jax.jit(self._make_lowerable(training), **kwargs)
            self._jitted[training] = fn
        return fn

    def _get_bwd(self, training):
        """Jitted recompute-based backward: vjp is built INSIDE the jit so
        jax's compile cache memoizes it per shape signature.

        Calling ``jax.vjp(jitted, *vals)`` at forward time instead would
        re-linearize (re-trace the whole graph in Python) on EVERY training
        step — measured 1.09 s/step vs 2 ms compiled on a 40-step LSTM
        unroll (1-core CPU).  The price is that backward re-executes the
        forward for residuals (the reference's MXNET_BACKWARD_DO_MIRROR
        behavior, always-on for this path); composing with remat flags is
        free since the recompute IS remat."""
        fn = self._bwd_jitted.get(training)
        if fn is None:
            fn = autograd.make_jitted_vjp(self._make_lowerable(training))
            self._bwd_jitted[training] = fn
        return fn

    # ------------------------------------------------------------------
    def __call__(self, param_dict, *inputs):
        import jax
        from .ndarray import NDArray, _wrap
        from . import random as _random

        training = autograd.is_training()
        recording = autograd.is_recording()
        if recording and self._flags.get("donate_params"):
            # the recorded vjp replays the saved input values at backward
            # time, but donation has already invalidated those buffers
            raise MXNetError(
                "CachedOp(donate_params=True) cannot run under "
                "autograd.record(): donated input buffers are dead by "
                "backward time — rebuild without donation to record")
        param_handles = [param_dict[n] for n in self._param_names]
        param_vals = [p._data for p in param_handles]
        input_vals = [x._data for x in inputs]
        place = self._flags.get("place_inputs")
        if place is not None:
            # mesh-sharded models (serving/decode/sharding.py): one jit
            # call cannot mix single-device-committed and mesh-committed
            # operands, so the model pins every operand's placement —
            # already-mesh-resident values pass through untouched
            param_vals = [place(v) for v in param_vals]
            input_vals = [place(v) for v in input_vals]
        key = _random.next_key()
        vals = tuple(param_vals) + tuple(input_vals) + (key,)
        ctx = inputs[0].context if inputs else param_handles[0].context

        jitted = self._get_jitted(training)
        n_aux = len(self._aux_names)
        first = self._note_dispatch(training, input_vals)
        # the dispatch, not the work: jitted() returns once the program is
        # enqueued (on a first call: traced, lowered, compiled and enqueued)
        with profiler.span("cachedop.call" if first is None
                           else "cachedop.first_call", op=self._name):
            if first is not None:
                self._first_call(jitted, vals, first)
            if profiler.profiling_imperative():
                # in a session also under the name of the reference's
                # _CachedOp engine op (cached_op.cc registers the whole
                # capture as a single profilable op)
                with profiler.span("_CachedOp", cat="cached_op"):
                    flat_out = jitted(*vals)
            else:
                flat_out = jitted(*vals)
        vjp_fn = (_LazyVjp(self._get_bwd(training), vals)
                  if recording else None)

        if n_aux:
            out_vals = flat_out[:-n_aux]
            aux_vals = flat_out[-n_aux:]
        else:
            out_vals, aux_vals = flat_out, ()

        outputs = [_wrap(v, ctx=ctx) for v in out_vals]
        aux_outputs = [_wrap(v, ctx=ctx) for v in aux_vals]

        # write updated aux state back into the live parameters
        if training and n_aux:
            with autograd.pause():
                for name, v in zip(self._aux_names, aux_vals):
                    param_dict[name]._set_data(v)

        if recording:
            autograd.record_op(
                None, list(param_handles) + list(inputs),
                outputs + aux_outputs, name="_CachedOp",
                vjp_fn=_VjpAdapter(vjp_fn, len(vals) - 1),
                primals_out=tuple(flat_out))
            # patch: record_op stored fn=None; backward uses vjp_fn
        if self._out_tree == "single":
            return outputs[0]
        return outputs


class _LazyVjp:
    """Defer the vjp to backward time through the compiled backward."""

    def __init__(self, bwd_fn, vals):
        self._bwd_fn = bwd_fn
        self._vals = vals

    def __call__(self, cts):
        return self._bwd_fn(self._vals, cts)


class _VjpAdapter:
    """Adapt jax vjp over (params..., inputs..., key) to the tape's
    (params..., inputs...) cotangent contract by dropping the key cotangent."""

    def __init__(self, vjp_fn, n_real_inputs):
        self._vjp_fn = vjp_fn
        self._n = n_real_inputs

    def __call__(self, out_cts):
        in_cts = self._vjp_fn(out_cts)
        return in_cts[:self._n]
