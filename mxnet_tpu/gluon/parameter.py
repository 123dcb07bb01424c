"""Gluon Parameter / ParameterDict.

Reference: python/mxnet/gluon/parameter.py — ``Parameter`` with deferred shape
init (:43), per-context data copies, grad_req handling; ``ParameterDict``
(:632) with prefix scoping and shared params.

TPU-native: a Parameter owns one NDArray per context (replicated copies for
the executor-group style path; the pjit path shards one array over the mesh
instead).  Deferred init works by letting layers fill in unknown (0) dims at
first forward.
"""
from __future__ import annotations

import warnings
from collections import OrderedDict

import numpy as _np

from ..base import MXNetError
from ..context import Context, current_context
from ..ndarray import NDArray, zeros, array
from .. import autograd
from .. import initializer as init_mod


class DeferredInitializationError(MXNetError):
    pass


class Parameter:
    def __init__(self, name, grad_req="write", shape=None, dtype=_np.float32,
                 lr_mult=1.0, wd_mult=1.0, init=None, allow_deferred_init=False,
                 differentiable=True, stype="default", grad_stype="default",
                 init_perm=None):
        # storage: one NDArray per context, plus matching grad buffers;
        # all unset until initialize()/deferred materialization runs
        self._var = self._data = self._grad = None
        self._ctx_list = self._ctx_map = self._trainer = None
        self._deferred_init = ()
        self._differentiable = differentiable
        self._allow_deferred_init = allow_deferred_init
        self._grad_req = None
        self._shape = tuple(shape) if shape is not None else None
        self.name = name
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.grad_req = grad_req
        self.init = init
        self._stype = stype
        self._grad_stype = grad_stype
        # stored = canonical.transpose(init_perm): initializers compute
        # fan-in/fan-out from the canonical (O, I, *kernel) axis order, so
        # alternate storage layouts (channel-last conv weights) draw in
        # canonical shape and are permuted into place
        self.init_perm = tuple(init_perm) if init_perm is not None else None

    def __repr__(self):
        s = "Parameter {name} (shape={shape}, dtype={dtype})"
        return s.format(name=self.name, shape=self.shape, dtype=self.dtype)

    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        if req not in ("write", "add", "null"):
            raise ValueError("grad_req must be 'write', 'add' or 'null', "
                             "got %r" % (req,))
        if not self._differentiable:
            req = "null"
        if req == self._grad_req:
            return
        self._grad_req = req
        if self._data is None:
            return  # buffers don't exist yet; _init_impl applies req later
        if req == "null":
            self._grad = None
            for d in self._data:
                d.grad = None
        else:
            self._init_grad()

    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new_shape):
        if self._shape is None:
            self._shape = tuple(new_shape)
            return
        assert len(self._shape) == len(new_shape) and \
            all(j in (0, i) for i, j in zip(new_shape, self._shape)), \
            "Expected shape %s is incompatible with given shape %s." % (
                str(new_shape), str(self._shape))
        self._shape = tuple(new_shape)

    @property
    def stype(self):
        return self._stype

    # ------------------------------------------------------------------
    def _check_and_get(self, arr_list, ctx):
        if arr_list is not None:
            if ctx is list:
                return arr_list
            if ctx is None:
                if len(arr_list) == 1:
                    return arr_list[0]
                ctx = current_context()
            for a in arr_list:
                if a.context == ctx:
                    return a
            # fall back to first copy (TPU/CPU flexibility)
            return arr_list[0]
        if self._deferred_init:
            raise DeferredInitializationError(
                "Parameter '%s' has not been initialized yet because "
                "initialization was deferred. Actual initialization happens "
                "during the first forward pass." % self.name)
        raise RuntimeError(
            "Parameter '%s' has not been initialized. You should initialize "
            "parameters and create Trainer with Block.collect_params() instead "
            "of Block.params." % self.name)

    def _load_init(self, data, ctx, prefer_canonical=False):
        """Set this parameter from checkpoint ``data``.

        ``prefer_canonical``: the data is known to be in the canonical
        (reference NCHW) layout — permute it into the stored layout whenever
        this param has an ``init_perm``, even if the raw shape happens to
        fit directly (a kernel whose spatial dims equal its in-channels fits
        both ways; the model-zoo pretrained path passes True because
        reference checkpoints are always canonical)."""
        if self.shape:
            def _fits(shape):
                # 0 entries in self.shape are still-unknown (deferred) dims
                return (len(shape) == len(self.shape) and
                        all(s in (0, d) for s, d in zip(self.shape, shape)))
            perm = self.init_perm
            permuted_fits = perm is not None and _fits(
                tuple(data.shape[j] for j in perm))
            if permuted_fits and (prefer_canonical or not _fits(data.shape)):
                # canonical-layout checkpoint (e.g. a reference NCHW OIHW
                # conv weight) loading into a channel-last param: apply the
                # stored-layout permutation on the way in
                data = data.transpose(perm)
            elif not _fits(data.shape):
                raise AssertionError(
                    "Failed loading Parameter '%s' from saved params: "
                    "shape incompatibility (%s vs %s)"
                    % (self.name, self.shape, data.shape))
            if any(s == 0 for s in self.shape):
                self.shape = data.shape
        if isinstance(ctx, Context):
            ctx = [ctx]
        if self._data is None:
            self._deferred_init = ()
            self._init_impl(data, ctx or [current_context()])
        else:
            for d in self._data:
                d._set_data(data.as_in_context(d.context)._data)
        self._deferred_init = ()

    def _finish_deferred_init(self):
        if not self._deferred_init:
            return
        init_, ctx, default_init, data = self._deferred_init
        self._deferred_init = ()
        assert self.shape is not None and all(s > 0 for s in self.shape), \
            "Cannot initialize Parameter '%s' because it has invalid shape: %s." \
            % (self.name, str(self.shape))
        with autograd.pause():
            if data is None:
                draw_shape = self.shape
                if self.init_perm is not None:
                    draw_shape = tuple(self.shape[self.init_perm.index(j)]
                                       for j in range(len(self.shape)))
                data = zeros(draw_shape, dtype=self.dtype)
                initializer = init_ if init_ is not None else (self.init or default_init)
                initializer = init_mod.create(initializer)
                desc = init_mod.InitDesc(self.name)
                initializer(desc, data)
                if self.init_perm is not None:
                    data = data.transpose(self.init_perm)
            self._init_impl(data, ctx)

    def _init_impl(self, data, ctx_list):
        self._ctx_list = list(ctx_list)
        self._data = [data.copyto(ctx) if data.context != ctx else data
                      for ctx in self._ctx_list]
        # ensure distinct buffers per ctx
        if len(self._data) > 1:
            self._data = [d.copy() if i > 0 and d is self._data[0] else d
                          for i, d in enumerate(self._data)]
        self._init_grad()

    def _init_grad(self):
        if self.grad_req == "null":
            self._grad = None
            return
        if self._grad_stype == "row_sparse":
            # sparse grad buffers: backward writes only the touched rows
            # (SparseEmbedding / Embedding sparse_grad path)
            from ..ndarray import sparse as _sp
            self._grad = [_sp.zeros("row_sparse", d.shape, ctx=d.context,
                                    dtype=str(d.dtype)) for d in self._data]
        else:
            self._grad = [zeros(d.shape, ctx=d.context, dtype=str(d.dtype))
                          for d in self._data]
        for d, g in zip(self._data, self._grad):
            d._ag_is_leaf = True
            d._ag_grad_req = self.grad_req
            d.grad = g
            d._ag_entry = None
            autograd.mark_variables([d], [g], self.grad_req)

    def _reduce(self):
        """Average copies across devices (for get/save)."""
        block = self.list_data()
        if len(block) == 1:
            return block[0]
        acc = block[0].copy()
        for b in block[1:]:
            acc += b.as_in_context(acc.context)
        return acc / len(block)

    # ------------------------------------------------------------------
    def initialize(self, init=None, ctx=None, default_init=init_mod.Uniform(),
                   force_reinit=False):
        if self._data is not None and not force_reinit:
            warnings.warn("Parameter '%s' is already initialized, ignoring. "
                          "Set force_reinit=True to re-initialize." % self.name,
                          stacklevel=2)
            return
        self._data = self._grad = None
        if ctx is None:
            ctx = [current_context()]
        if isinstance(ctx, Context):
            ctx = [ctx]
        if init is None:
            init = default_init if self.init is None else self.init
        if self.shape is None or any(s == 0 for s in self.shape):
            if self._allow_deferred_init:
                self._deferred_init = (init, ctx, default_init, None)
                return
            raise ValueError("Cannot initialize Parameter '%s' because it has "
                             "invalid shape: %s." % (self.name, str(self.shape)))
        self._deferred_init = (init, ctx, default_init, None)
        self._finish_deferred_init()

    def reset_ctx(self, ctx):
        if isinstance(ctx, Context):
            ctx = [ctx]
        if self._data:
            data = self._reduce()
            with autograd.pause():
                self._init_impl(data, ctx)
        elif self._deferred_init:
            init_, _, default_init, data = self._deferred_init
            self._deferred_init = (init_, ctx, default_init, data)
        else:
            raise ValueError("Cannot reset context for Parameter '%s' because it "
                             "has not been initialized." % self.name)

    def set_data(self, data):
        self.shape = data.shape
        if self._data is None:
            assert self._deferred_init, \
                "Parameter '%s' has not been initialized" % self.name
            self._deferred_init = self._deferred_init[:3] + (data,)
            return
        for arr in self._data:
            arr._set_data(data.as_in_context(arr.context)._data
                          if data.context != arr.context else data._data)

    def row_sparse_data(self, row_id):
        return self.data(ctx=row_id.context)

    def list_row_sparse_data(self, row_id):
        return self.list_data()

    def data(self, ctx=None):
        return self._check_and_get(self._data, ctx)

    def list_data(self):
        return self._check_and_get(self._data, list)

    def grad(self, ctx=None):
        if self._data is not None and self._grad is None:
            raise RuntimeError(
                "Cannot get gradient array for Parameter '%s' because grad_req='null'"
                % self.name)
        return self._check_and_get(self._grad, ctx)

    def list_grad(self):
        if self._data is not None and self._grad is None:
            raise RuntimeError(
                "Cannot get gradient array for Parameter '%s' because grad_req='null'"
                % self.name)
        return self._check_and_get(self._grad, list)

    def list_ctx(self):
        if self._data is None:
            if self._deferred_init:
                return self._deferred_init[1]
            raise RuntimeError("Parameter '%s' has not been initialized" % self.name)
        return self._ctx_list

    def zero_grad(self):
        if self._grad is None:
            return
        from ..ndarray.sparse import BaseSparseNDArray
        from ..ndarray import sparse as _sp
        for g in self._grad:
            if isinstance(g, BaseSparseNDArray):
                # reset to empty aux fields — writing 0 through the dense
                # path would materialize the full table
                empty = _sp.zeros(g.stype, g.shape, ctx=g.context,
                                  dtype=str(g.dtype))
                empty.copyto(g)
            else:
                g[:] = 0

    def var(self):
        from .. import symbol
        if self._var is None:
            extra = {}
            # BN-style running statistics are auxiliary states in symbol
            # graphs (same criterion HybridBlock.export uses to choose the
            # "aux:" slot) — mark the var so list_auxiliary_states() and
            # executor aux binding classify the exported graph correctly
            if self.grad_req == "null" and ("running" in self.name
                                            or "moving" in self.name):
                extra["__is_aux__"] = True
            self._var = symbol.var(self.name, shape=self.shape,
                                   lr_mult=self.lr_mult, wd_mult=self.wd_mult,
                                   init=self.init, **extra)
        return self._var

    def cast(self, dtype):
        self.dtype = dtype
        if self._data is None:
            return
        with autograd.pause():
            self._data = [i.astype(dtype) for i in self._data]
            if self._grad is not None:
                self._grad = [i.astype(dtype) for i in self._grad]
                for d, g in zip(self._data, self._grad):
                    d.grad = g
                    autograd.mark_variables([d], [g], self.grad_req)


class Constant(Parameter):
    """A constant parameter (not updated by the trainer)."""

    def __init__(self, name, value):
        if not isinstance(value, NDArray):
            value = array(value)
        self.value = value

        class Init(init_mod.Initializer):
            def _init_weight(self_, _, arr):
                value.copyto(arr)
            _init_default = init_mod.Initializer._init_weight

        init_name = "Constant_{}_{}".format(name, id(self))
        init_mod._INITIALIZER_REGISTRY[init_name.lower()] = Init
        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=value.dtype, init=Init())


class ParameterDict:
    """Dictionary of Parameters with prefix scoping (reference :632)."""

    def __init__(self, prefix="", shared=None):
        self._prefix = prefix
        self._params = OrderedDict()
        self._shared = shared

    def __getitem__(self, key):
        return self._params[key]

    def __repr__(self):
        s = "{name}(\n{content}\n)"
        name = self._prefix + " " if self._prefix else ""
        return s.format(name=name, content="\n".join(
            [" " + repr(v) for v in self.values()]))

    # mapping surface delegates straight to the backing OrderedDict
    def __iter__(self):
        return iter(self._params)

    def items(self):
        """View of (fully-prefixed name, Parameter) pairs."""
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        """View of the Parameters in registration order."""
        return self._params.values()

    @property
    def prefix(self):
        """Scope string prepended to every name handed to get()."""
        return self._prefix

    def _get_impl(self, name):
        if name in self._params:
            return self._params[name]
        if self._shared is not None and name in self._shared._params:
            self._params[name] = self._shared._params[name]
            return self._params[name]
        return None

    @staticmethod
    def _merge_shapes(requested, stored):
        """Unify two partially-known shapes (0 = unknown dim).  Returns the
        merged tuple, or None when a known dim disagrees."""
        if requested is None or len(requested) != len(stored):
            return None
        merged = []
        for want, have in zip(requested, stored):
            if 0 in (want, have):
                merged.append(want or have)
            elif want == have:
                merged.append(want)
            else:
                return None
        return tuple(merged)

    def get(self, name, **kwargs):
        """Fetch-or-create: an existing Parameter (here or in the shared dict)
        is revalidated against the requested attributes, with partially-known
        shapes unified; otherwise a new one is created from ``kwargs``."""
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            param = self._params[name] = Parameter(name, **kwargs)
            return param
        for attr, want in kwargs.items():
            have = getattr(param, attr, None)
            if have is None:
                setattr(param, attr, want)
                continue
            if attr == "shape":
                merged = self._merge_shapes(want, have)
                if merged is not None:
                    param._shape = merged
                    continue
            if want is not None and want != have:
                raise AssertionError(
                    "Parameter '%s' already exists with %s=%s; cannot "
                    "re-request it with %s=%s." % (name, attr, have,
                                                   attr, want))
        return param

    def get_constant(self, name, value=None):
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            if value is None:
                raise KeyError("No constant named '{}'.".format(name))
            param = Constant(name, value)
            self._params[name] = param
        return param

    def update(self, other):
        for k, v in other.items():
            if k in self._params:
                assert self._params[k] is v, \
                    "Cannot update self with other because they have different " \
                    "Parameters with the same name '%s'" % k
            else:
                self._params[k] = v

    def initialize(self, init=init_mod.Uniform(), ctx=None, verbose=False,
                   force_reinit=False):
        if verbose:
            init.set_verbosity(verbose=verbose)
        for _, v in self.items():
            v.initialize(None, ctx, init, force_reinit=force_reinit)

    def zero_grad(self):
        for i in self.values():
            i.zero_grad()

    def reset_ctx(self, ctx):
        for i in self.values():
            i.reset_ctx(ctx)

    def setattr(self, name, value):
        for i in self.values():
            setattr(i, name, value)

    def save(self, filename, strip_prefix=""):
        from .. import ndarray as nd
        arg_dict = {}
        for param in self.values():
            weight = param._reduce()
            if not param.name.startswith(strip_prefix):
                raise ValueError(
                    "Prefix '%s' is to be stripped before saving, but Parameter's "
                    "name '%s' does not start with '%s'." % (
                        strip_prefix, param.name, strip_prefix))
            arg_dict[param.name[len(strip_prefix):]] = weight
        nd.save(filename, arg_dict)

    def load(self, filename, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix=""):
        from .. import ndarray as nd
        if restore_prefix:
            for name in self.keys():
                assert name.startswith(restore_prefix), \
                    "restore_prefix is '%s' but Parameter name '%s' does not start " \
                    "with it" % (restore_prefix, name)
        lprefix = len(restore_prefix)
        loaded = nd.load(filename)
        arg_dict = {restore_prefix + k.split(":", 1)[-1]: v
                    for k, v in loaded.items()}
        if not allow_missing:
            for name in self.keys():
                assert name in arg_dict, \
                    "Parameter '%s' is missing in file '%s'" % (name[lprefix:], filename)
        for name in arg_dict:
            if name not in self._params:
                assert ignore_extra, \
                    "Parameter '%s' loaded from file '%s' is not present in this " \
                    "ParameterDict" % (name[lprefix:], filename)
                continue
            self[name]._load_init(arg_dict[name], ctx)
