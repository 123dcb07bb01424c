"""Gluon Block / HybridBlock / SymbolBlock.

Reference: python/mxnet/gluon/block.py — ``Block`` (:127) imperative container
with prefix/param scoping; ``HybridBlock`` (:673) adds ``hybridize()`` which
traces the forward into a CachedOp (:787-797); ``SymbolBlock`` (:954) wraps a
saved symbol graph.

TPU-native: hybridize() compiles the forward (and, under record, its vjp) into
a single XLA module via mxnet_tpu.cached_op.CachedOp.  ``hybrid_forward`` is
F-generic exactly like the reference: F=mx.nd eagerly, and the same code also
builds a Symbol graph (F=mx.sym) for ``export()``/SymbolBlock round-trips.
"""
from __future__ import annotations

import copy
import re
import threading
import warnings
from collections import OrderedDict

from ..base import MXNetError
from ..context import Context, current_context
from ..ndarray import NDArray
from .. import ndarray as nd_mod
from .. import autograd
from ..cached_op import CachedOp
from .parameter import Parameter, ParameterDict, DeferredInitializationError
from ..name import NameManager, Prefix


class _TraceNames(Prefix):
    """Prefix name manager that keeps node names unique across one symbolic
    trace.  Sibling blocks may share a prefix (gluon allows ``prefix=""``
    children), and layers name their op nodes with fixed hints like "fwd" —
    without trace-wide dedup, exported graphs would contain colliding names.
    """

    def __init__(self, prefix, seen):
        super().__init__(prefix)
        self._seen = seen

    @classmethod
    def nested(cls, prefix):
        """A manager for `prefix` sharing the enclosing trace's seen-set."""
        current = getattr(NameManager._current, "value", None)
        seen = current._seen if isinstance(current, cls) else set()
        return cls(prefix, seen)

    def get(self, name, hint):
        base = super().get(name, hint)
        unique = base
        suffix = 0
        while unique in self._seen:
            suffix += 1
            unique = "%s_%d" % (base, suffix)
        self._seen.add(unique)
        return unique


class _BlockScope:
    """Name scoping for nested blocks (reference block.py:35)."""

    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}     # per-hint child numbering inside this scope
        self._old_scope = None
        self._name_scope = None

    @staticmethod
    def create(prefix, params, hint):
        """Resolve a new block's (prefix, ParameterDict) against the
        enclosing scope: top-level blocks auto-number through NameManager,
        nested ones through the parent scope's counter."""
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            from ..name import current as current_names
            if prefix is None:
                prefix = current_names().get(None, hint) + "_"
            params = ParameterDict(prefix) if params is None \
                else ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            prefix = "%s%d_" % (hint, count)
            current._counter[hint] = count + 1
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self  # prefix="" blocks are name-transparent
        from ..name import Prefix
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        # symbols built inside the scope get the block's prefix too
        self._name_scope = Prefix(self._block.prefix)
        self._name_scope.__enter__()
        return self

    def __exit__(self, ptype, value, trace):
        if self._block._empty_prefix:
            return
        # unwind in reverse order of __enter__
        self._name_scope.__exit__(ptype, value, trace)
        self._name_scope = None
        _BlockScope._current.value = self._old_scope


class Block:
    """Base building block (reference block.py:127)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(prefix, params,
                                                        self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") else self._prefix
        self._scope = _BlockScope(self)
        self._children = OrderedDict()
        self._reg_params = {}
        self._forward_hooks = OrderedDict()
        self._forward_pre_hooks = OrderedDict()

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join(["  ({key}): {block}".format(
            key=key, block=_indent(str(block), 2))
            for key, block in self.__dict__.items()
            if isinstance(block, Block)])
        return s.format(name=self.__class__.__name__, modstr=modstr)

    def __setattr__(self, name, value):
        if hasattr(self, name):
            existing = getattr(self, name)
            if isinstance(existing, (Parameter, Block)) and not isinstance(
                    value, type(existing)):
                raise TypeError("Changing attribute type for {name} from {type1} "
                                "to {type2} is not allowed.".format(
                                    name=name, type1=type(existing), type2=type(value)))
        if isinstance(value, Block):
            self.register_child(value, name)
        elif isinstance(value, Parameter):
            assert name not in self._reg_params, \
                "Overriding Parameter attribute %s is not allowed." % name
            self._reg_params[name] = value
        super().__setattr__(name, value)

    def _alias(self):
        return self.__class__.__name__.lower()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        return self._scope

    @property
    def params(self):
        return self._params

    def collect_params(self, select=None):
        ret = ParameterDict(self._params.prefix)
        if not select:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update({name: value for name, value in self.params.items()
                        if pattern.match(name)})
        for cld in self._children.values():
            ret.update(cld.collect_params(select=select))
        return ret

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + key: val for key, val in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def save_parameters(self, filename):
        params = self._collect_params_with_prefix()
        from .. import ndarray as nd
        arg_dict = {key: val._reduce() for key, val in params.items()}
        nd.save(filename, arg_dict)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False):
        from .. import ndarray as nd
        loaded = nd.load(filename)
        params = self._collect_params_with_prefix()
        if not loaded and not params:
            return
        if not any("." in i for i in loaded.keys()):
            # legacy collect_params().save format
            del loaded
            self.collect_params().load(filename, ctx, allow_missing,
                                       ignore_extra, self.prefix)
            return
        if not allow_missing:
            for name in params.keys():
                assert name in loaded, \
                    "Parameter '%s' is missing in file '%s'" % (name, filename)
        for name in loaded:
            if not ignore_extra and name not in params:
                raise ValueError(
                    "Parameter '%s' loaded from file '%s' is not present in this "
                    "block" % (name, filename))
            if name in params:
                params[name]._load_init(loaded[name], ctx)

    # compat aliases (reference deprecated names)
    save_params = save_parameters

    def load_params(self, filename, ctx=None, allow_missing=False,
                    ignore_extra=False):
        self.load_parameters(filename, ctx, allow_missing, ignore_extra)

    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block

    def register_forward_pre_hook(self, hook):
        handle = _HookHandle(self._forward_pre_hooks)
        self._forward_pre_hooks[handle._id] = hook
        return handle

    def register_forward_hook(self, hook):
        handle = _HookHandle(self._forward_hooks)
        self._forward_hooks[handle._id] = hook
        return handle

    def apply(self, fn):
        for cld in self._children.values():
            cld.apply(fn)
        fn(self)
        return self

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        from ..initializer import Uniform
        self.collect_params().initialize(init or Uniform(), ctx, verbose,
                                         force_reinit)

    def hybridize(self, active=True, **kwargs):
        for cld in self._children.values():
            cld.hybridize(active, **kwargs)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for _, param in self.params.items():
            param.cast(dtype)

    def __call__(self, *args):
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        out = self.forward(*args)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def forward(self, *args):
        raise NotImplementedError

    def summary(self, *inputs):
        summary_lines = []
        params = self.collect_params()
        n_params = 0
        for name, p in params.items():
            if p.shape and all(s > 0 for s in p.shape):
                cnt = 1
                for s in p.shape:
                    cnt *= s
                n_params += cnt
                summary_lines.append("%-60s %s" % (name, str(p.shape)))
        summary_lines.append("Total params: %d" % n_params)
        print("\n".join(summary_lines))


class _HookHandle:
    _id_counter = 0

    def __init__(self, hooks_dict):
        self._hooks_dict = hooks_dict
        _HookHandle._id_counter += 1
        self._id = _HookHandle._id_counter

    def detach(self):
        self._hooks_dict.pop(self._id, None)


def _indent(s_, num_spaces):
    lines = s_.split("\n")
    first = lines.pop(0)
    lines = [(num_spaces * " ") + line for line in lines]
    return "\n".join([first] + lines)


_REMAT_REGION = threading.local()


def _being_traced(args):
    """Whether a block's inputs are values of an enclosing jax trace."""
    import jax
    return any(isinstance(getattr(a, "_data", None), jax.core.Tracer)
               for a in args)


class HybridBlock(Block):
    """Block with a compile-on-demand forward (reference block.py:673)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_op = None
        self._flags = {}
        self._in_hybrid_forward = False

    def __setattr__(self, name, value):
        super().__setattr__(name, value)
        if isinstance(value, HybridBlock):
            self._clear_cached_op()

    def register_child(self, block, name=None):
        if not isinstance(block, HybridBlock):
            if not isinstance(block, Block):
                raise ValueError("Children of HybridBlock must also be HybridBlock")
        super().register_child(block, name)
        self._clear_cached_op()

    def hybridize(self, active=True, **kwargs):
        """Compile this block's forward as one program (a CachedOp, or part
        of the program of whoever traces it).  Flags, for the children too:
        ``remat=True`` recomputes the forward in the backward pass in place
        of keeping its activations (else MXNET_BACKWARD_DO_MIRROR);
        ``remat_policy`` says what is kept all the same: a
        jax.checkpoint_policies name ('dots_saveable', ...; else
        MXNET_REMAT_POLICY), 'full' for nothing, or a tuple of names, which
        keeps the values that the forward passes through
        ``jax.ad_checkpoint.checkpoint_name`` under one of them and nothing
        else.  A kept value costs its bytes from the forward pass to this
        block's backward and saves the operations that only it needs: the
        attention call names its kernel's output and log-sum-exp
        (``ops.pallas_ops.ATTENTION_RESIDUALS``), ``batch * heads * rows *
        (head_dim + 1)`` float32 values for the forward kernel's second run;
        the same tuple's third name keeps the held experts' slot table
        (``parallel.moe.moe_held_apply``: three sorts' results).
        ``donate_params``: see CachedOp."""
        self._active = active
        self._flags = kwargs
        self._clear_cached_op()
        for cld in self._children.values():
            cld.hybridize(active, **kwargs)

    def cast(self, dtype):
        self._clear_cached_op()
        super().cast(dtype)

    def _clear_cached_op(self):
        self._cached_op = None

    def infer_shape(self, *args):
        """Finish deferred parameter init by running shape hooks on leaves."""
        self._deferred_infer(*args)

    def _deferred_infer(self, *args):
        # run the eager forward with deferred handling: leaf layers override
        # _shape_hook to fill parameter shapes from inputs.
        pass

    def _build_cache(self):
        """Create the CachedOp over this block's full forward
        (analog of block.py:787 _build_cache)."""
        self._cached_op, self._cached_params = build_cached_op(self,
                                                              self._flags)

    def _call_cached_op(self, *args):
        if self._cached_op is None:
            # ensure params are initialized (run one eager call path for
            # deferred shapes)
            try:
                for p in self.collect_params().values():
                    if p._deferred_init:
                        raise DeferredInitializationError("deferred")
                    p.data()
            except (DeferredInitializationError, RuntimeError):
                out = self.hybrid_call(*args)
                self._build_cache()
                return out
            self._build_cache()
        param_dict = {n: p.data() for n, p in self._cached_params.items()}
        return self._cached_op(param_dict, *args)

    def __call__(self, *args):
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        from ..symbol import Symbol
        if args and isinstance(args[0], Symbol):
            # symbolic tracing takes priority over the hybridized CachedOp
            # (reference HybridBlock.__call__ dispatches on input type)
            out = self._build_symbol(*args)
        elif self._active and not self._in_hybrid_forward:
            out = (self._call_traced(*args) if _being_traced(args)
                   else self._call_cached_op(*args))
        else:
            out = self.forward(*args)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def _call_traced(self, *args):
        """A hybridized block called inside someone else's trace
        (``functional_call`` under a compiled train step, a parent's
        CachedOp): the outer program compiles it, so no CachedOp of its
        own; what still holds is ``hybridize(remat=...)``: the block's
        forward is wrapped in ``jax.checkpoint`` (policy as for a CachedOp),
        so that the backward pass recomputes it from its inputs and
        parameters instead of keeping its activations.  The outermost such
        block decides: inside a recomputed region nothing is wrapped
        again."""
        from ..cached_op import remat_policy
        remat, policy = remat_policy(self._flags)
        if not remat or getattr(_REMAT_REGION, "inside", False):
            return self.forward(*args)
        import jax
        params = {p.name: p for p in self.collect_params().values()}
        names = sorted(params)
        outer = {n: params[n].data() for n in names}
        aux_names = [n for n in names if params[n].grad_req == "null"]
        tree = []

        def pure(param_vals, input_vals):
            nds = {n: NDArray(v) for n, v in zip(names, param_vals)}
            _REMAT_REGION.inside = True
            try:
                out = _with_param_override(
                    self, params, nds,
                    lambda: self.forward(*[NDArray(v) for v in input_vals]))
            finally:
                _REMAT_REGION.inside = False
            tree.append(isinstance(out, (list, tuple)))
            outs = list(out) if tree[-1] else [out]
            return (tuple(o._data for o in outs),
                    tuple(nds[n]._data for n in aux_names))

        outs, aux = jax.checkpoint(pure, policy=policy)(
            tuple(outer[n]._data for n in names),
            tuple(a._data for a in args))
        for n, v in zip(aux_names, aux):
            outer[n]._set_data(v)
        outs = [NDArray(o) for o in outs]
        return outs if tree[-1] else outs[0]

    def hybrid_call(self, *args):
        """Run the eager (unhybridized) forward regardless of _active."""
        return self.forward(*args)

    def forward(self, x, *args):
        """Eager path: resolve params on x's context and call hybrid_forward.

        Symbol inputs build the symbolic graph instead (reference
        HybridBlock.forward symbol branch)."""
        from ..symbol import Symbol
        if isinstance(x, Symbol):
            return self._build_symbol(x, *args)
        ctx = x.context if isinstance(x, NDArray) else current_context()
        try:
            params = {k: v.data(ctx) for k, v in self._reg_params.items()}
        except DeferredInitializationError:
            self._finish_deferred(x, *args)
            params = {k: v.data(ctx) for k, v in self._reg_params.items()}
        self._in_hybrid_forward = True
        try:
            return self.hybrid_forward(nd_mod, x, *args, **params)
        finally:
            self._in_hybrid_forward = False

    def _finish_deferred(self, *args):
        """Infer unknown param dims from inputs and finish deferred init."""
        if hasattr(self, "_shape_hook"):
            self._shape_hook(*args)
        for p in self._reg_params.values():
            if p._deferred_init:
                p._finish_deferred_init()

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    # ------------------------------------------------------------------
    def export(self, path, epoch=0, remove_amp_cast=True):
        """Export as symbol json + params (reference block.py export)."""
        from .. import symbol as sym_mod
        from .. import ndarray as nd
        inputs = [sym_mod.var("data")]
        out = self._build_symbol(*inputs)
        if isinstance(out, (list, tuple)):
            out = sym_mod.Group(list(out))
        out.save("%s-symbol.json" % path)
        arg_dict = {}
        for name, param in self.collect_params().items():
            prefix = "aux:" if param.grad_req == "null" and (
                "running" in name or "moving" in name) else "arg:"
            arg_dict[prefix + name] = param._reduce()
        nd.save("%s-%04d.params" % (path, epoch), arg_dict)

    def _build_symbol(self, *inputs):
        """Run hybrid_forward with F=symbol to build a graph; params enter
        as their ``var()`` placeholders.  Node names are namespaced by this
        block's prefix (reference: symbol composition inside the block's
        name scope) and deduplicated across the whole trace, so repeated
        layers get unique graph names."""
        from .. import symbol as sym_mod
        params = {k: v.var() for k, v in self._reg_params.items()}
        self._in_hybrid_forward = True
        try:
            with _TraceNames.nested(self._prefix):
                return self.hybrid_forward(sym_mod, *inputs, **params)
        finally:
            self._in_hybrid_forward = False


class SymbolBlock(HybridBlock):
    """Wrap a Symbol graph as a Block (reference block.py:954)."""

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from .. import symbol as sym_mod
        from .. import ndarray as nd
        sym = sym_mod.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [sym_mod.var(i) for i in input_names]
        ret = SymbolBlock(sym, inputs)
        if param_file is not None:
            arg_dict = nd.load(param_file)
            params = {}
            for k, v in arg_dict.items():
                if k.startswith(("arg:", "aux:")):
                    params[k.split(":", 1)[1]] = v
                else:
                    params[k] = v
            for name, param in ret.collect_params().items():
                if name in params:
                    param._load_init(params[name], ctx)
        if ctx is not None:
            ret.collect_params().reset_ctx(ctx)
        return ret

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix=None, params=params)
        # graph arg names ARE the parameter names: an auto "symbolblock0_"
        # prefix would break both imports() param matching and forward()'s
        # arg_dict binding (reference block.py:1010 resets prefix to '')
        self._prefix = ""
        self._name = ""
        self._params = ParameterDict("", params)
        from .. import symbol as sym_mod
        if isinstance(inputs, sym_mod.Symbol):
            inputs = [inputs]
        if isinstance(outputs, (list, tuple)):
            outputs = sym_mod.Group(list(outputs))
        self._output_sym = outputs
        self._input_names = [i.name for i in inputs]
        arg_names = outputs.list_arguments()
        aux_names = set(outputs.list_auxiliary_states())
        for name in arg_names:
            if name not in self._input_names:
                self.params.get(name, allow_deferred_init=True)
        for name in outputs.list_auxiliary_states():
            self.params.get(name, grad_req="null", allow_deferred_init=True)

    def forward(self, *args):
        from ..executor import Executor
        arg_dict = {}
        for name, v in zip(self._input_names, args):
            arg_dict[name] = v
        for name, p in self.params.items():
            try:
                arg_dict[name] = p.data()
            except (DeferredInitializationError, RuntimeError):
                raise MXNetError("SymbolBlock parameter %s is not initialized"
                                 % name)
        aux_names = set(self._output_sym.list_auxiliary_states())
        aux_dict = {k: v for k, v in arg_dict.items() if k in aux_names}
        args_only = {k: v for k, v in arg_dict.items() if k not in aux_names}
        ex = Executor(self._output_sym, None, args_only, None, "null", aux_dict)
        outs = ex.forward(is_train=autograd.is_training())
        if len(outs) == 1:
            return outs[0]
        return outs

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError


def build_cached_op(block, flags=None):
    """CachedOp over ``block``'s full forward + its {name: Parameter} map.

    The single construction point for whole-block compilation — used by
    ``HybridBlock._build_cache`` (hybridize) and the serving registry (which
    wants its own inference-mode instance without touching the block's
    hybridize cache).  Keeps the aux-state detection heuristic in ONE place:
    grad_req=='null' params whose name marks running/moving statistics are
    captured as extra outputs and written back after training calls."""
    params = {p.name: p for p in block.collect_params().values()}
    aux_names = [name for name, p in params.items() if p.grad_req == "null"
                 and ("running" in name or "moving" in name)]

    def forward_fn(param_nds, *input_nds):
        # substitute each Parameter's data with the provided handle for the
        # duration of the call
        call = (block.hybrid_call if isinstance(block, HybridBlock)
                else block.forward)
        return _with_param_override(block, params, param_nds,
                                    lambda: call(*input_nds))

    cop = CachedOp(forward_fn, {n: params[n].data() for n in params},
                   aux_names, flags)
    return cop, params


def functional_call(block, param_vals, *input_vals, training=False, rng_key=None):
    """Run a Block's forward as a pure function of (param values, inputs).

    param_vals: dict name -> jax array;  input_vals: jax arrays.
    Returns (output jax values tuple, updated aux values dict).  Jittable —
    this is the building block __graft_entry__ and the benchmark's family
    comparisons use to run whole gluon models as single XLA modules."""
    import jax
    from .. import random as _random
    from ..ndarray import NDArray
    params = {p.name: p for p in block.collect_params().values()}
    param_nds = {n: NDArray(v) for n, v in param_vals.items()}
    input_nds = [NDArray(v) for v in input_vals]
    if rng_key is None:
        rng_key = jax.random.PRNGKey(0)
    with autograd._RecordingStateScope(False, training), \
            _random.key_override(rng_key):
        out = _with_param_override(block, params, param_nds,
                                   lambda: block.hybrid_call(*input_nds)
                                   if isinstance(block, HybridBlock)
                                   else block.forward(*input_nds))
    outs = out if isinstance(out, (list, tuple)) else [out]
    aux = {n: param_nds[n]._data for n in param_vals
           if params[n].grad_req == "null"}
    return tuple(o._data for o in outs), aux


def split_param_names(block):
    """(trainable, frozen) parameter-name split for whole-block capture.

    ``frozen`` is every ``grad_req == 'null'`` parameter (BatchNorm running
    stats and explicitly frozen weights): whole-program train steps
    (module.compiled_step) thread those through the trace
    unchanged/functionally while differentiating only the trainable set.
    Both lists are sorted for a stable trace signature."""
    params = block.collect_params()
    frozen = sorted(n for n, p in params.items() if p.grad_req == "null")
    frozen_set = set(frozen)
    train = sorted(n for n in params if n not in frozen_set)
    return train, frozen


def param_values(block, dtype=None):
    """Extract {name: jax array} from an initialized Block."""
    import jax.numpy as jnp
    vals = {}
    for name, p in block.collect_params().items():
        v = p.data()._data
        if dtype is not None and jnp.issubdtype(v.dtype, jnp.floating):
            v = v.astype(dtype)
        vals[name] = v
    return vals


def _with_param_override(block, params, param_nds, thunk):
    """Temporarily substitute Parameter data handles with given NDArrays for
    all parameters of ``block`` (used during CachedOp tracing)."""
    saved = []
    try:
        for name, p in params.items():
            saved.append((p, p._data))
            nd_handle = param_nds[name]
            p._data = [nd_handle]
        return thunk()
    finally:
        for p, data in saved:
            # capture any aux mutation back into the traced handle before
            # restoring (handled by CachedOp via param_nds contents)
            p._data = data
