"""NDArray: a mutable device-array handle over an immutable jax.Array.

Reference: ``include/mxnet/ndarray.h`` + ``src/ndarray/`` — a ref-counted Chunk
with an engine variable enforcing read/write ordering, plus an autograd entry
per array (ndarray.h:98).

TPU-native redesign: jax arrays are immutable and XLA dispatch is already
asynchronous (calls return ahead of completion; ``block_until_ready`` is the
``WaitForVar`` analog — engine.h:116-315 semantics for free).  Mutability — the
part XLA does not give us — is a Python-level handle: ``NDArray._data`` is
swapped on in-place ops, and views created by basic slicing write back through
a (base, index) link, reproducing the reference's aliasing semantics without a
versioned-variable scheduler.  The autograd tape snapshots values at record
time, so later mutation cannot corrupt recorded history.

Every operator is dispatched through :func:`invoke`, the analog of
``Imperative::Invoke`` (src/imperative/imperative.cc:87): look up the op,
jit-cached apply, wrap outputs, record on the tape when autograd is active.
"""
from __future__ import annotations


import numpy as _np

from ..base import MXNetError, numeric_types, integer_types
from ..context import Context, current_context
from ..ops.registry import get_op
from .. import autograd
from .. import profiler

__all__ = ["NDArray", "invoke", "array", "zeros", "ones", "full", "empty",
           "arange", "moveaxis", "concat", "stack", "_wrap", "from_jax", "waitall"]

_DTYPE_ALIASES = {
    "float32": _np.float32, "float64": _np.float64, "float16": _np.float16,
    "bfloat16": "bfloat16",
    "uint8": _np.uint8, "int8": _np.int8, "int32": _np.int32, "int64": _np.int64,
}


def _jnp():
    import jax.numpy as jnp
    return jnp


def _as_dtype(dtype):
    if dtype is None:
        return _np.float32
    if isinstance(dtype, str):
        if dtype == "bfloat16":
            import jax.numpy as jnp
            return jnp.bfloat16
        return _np.dtype(dtype)
    if str(dtype) == "bfloat16":
        return dtype
    return _np.dtype(dtype)


def _ctx_of(value, ctx=None):
    if ctx is not None:
        return ctx if isinstance(ctx, Context) else Context(ctx)
    return current_context()


class NDArray:
    """Mutable multi-dimensional array handle on a device context."""

    __slots__ = ("_data_buf", "_version", "_base_version", "_ctx", "grad",
                 "_ag_entry", "_ag_is_leaf", "_ag_grad_req", "_base",
                 "_base_index", "_stype", "__weakref__")

    # numpy should defer to our reflected operators
    __array_priority__ = 100.0

    # _data is a property so that basic-index views observe later mutation
    # of their base (the reference NDArray's bidirectional aliasing through
    # the shared Chunk, include/mxnet/ndarray.h:98): reads re-slice from the
    # base whenever the base's version counter moved — the same version-
    # counted Var discipline as the reference engine (engine.h:45-62).
    @property
    def _data(self):
        b = self._base
        if b is not None:
            # touch the base's property FIRST: a stale chain refreshes
            # root-down, bumping each version, before we compare ours
            base_data = b._data
            if b._version != self._base_version:
                # assign through the setter so our own version bumps and
                # views-of-this-view refresh transitively
                self._data = base_data[self._base_index]
                self._base_version = b._version
        return self._data_buf

    @_data.setter
    def _data(self, value):
        self._data_buf = value
        self._version = getattr(self, "_version", 0) + 1

    def __init__(self, data, ctx=None):
        self._version = 0
        self._base = None           # view write-back target
        self._base_version = 0
        self._data = data
        self._ctx = _ctx_of(None, ctx)
        self.grad = None
        self._ag_entry = None
        self._ag_is_leaf = False
        self._ag_grad_req = "null"
        self._base_index = None
        self._stype = "default"

    # ------------------------------------------------------------------
    # basic properties
    # ------------------------------------------------------------------
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        dt = self._data.dtype
        try:
            return _np.dtype(dt)
        except TypeError:
            return dt  # bfloat16

    @property
    def size(self):
        n = 1
        for s in self.shape:
            n *= s
        return n

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def context(self):
        return self._ctx

    ctx = context

    @property
    def stype(self):
        return self._stype

    def __len__(self):
        if not self.shape:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __repr__(self):
        return "\n%s\n<NDArray %s @%s>" % (
            str(self.asnumpy()), "x".join(map(str, self.shape)), self._ctx)

    def __bool__(self):
        if self.size == 1:
            return bool(self.asnumpy().reshape(-1)[0])
        raise ValueError("The truth value of an NDArray with multiple elements "
                         "is ambiguous.")

    # ------------------------------------------------------------------
    # sync / transfer
    # ------------------------------------------------------------------
    def wait_to_read(self):
        """Block until the pending computation writing this array completes.

        Analog of Engine WaitForVar (include/mxnet/engine.h:229)."""
        self._data.block_until_ready()

    def asnumpy(self):
        import jax
        return _np.asarray(jax.device_get(self._data))

    def asscalar(self):
        if self.size != 1:
            raise ValueError("The current array is not a scalar")
        return self.asnumpy().reshape(-1)[0]

    def item(self):
        return self.asscalar()

    def astype(self, dtype, copy=True):
        out = self._data.astype(_as_dtype(dtype))
        return _wrap(out, ctx=self._ctx)

    def copyto(self, other):
        import jax
        if isinstance(other, NDArray):
            if other is self:
                return other
            # other.dtype, not other._data.dtype: reading _data on a lazy
            # sparse target would densify it just to learn the dtype
            other._set_data(jax.device_put(self._data, other._ctx.jax_device())
                            .astype(other.dtype))
            return other
        if isinstance(other, Context):
            return _wrap(jax.device_put(self._data, other.jax_device()), ctx=other)
        raise TypeError("copyto does not support type %s" % str(type(other)))

    def copy(self):
        return _wrap(self._data + 0 if False else self._data, ctx=self._ctx).astype(self.dtype) \
            if False else _wrap(_jnp().array(self._data), ctx=self._ctx)

    def as_in_context(self, context):
        if self._ctx == context:
            return self
        return self.copyto(context)

    as_in_ctx = as_in_context

    def to_dlpack_for_read(self):
        import jax.dlpack
        return jax.dlpack.to_dlpack(self._data)

    # ------------------------------------------------------------------
    # mutation plumbing
    # ------------------------------------------------------------------
    def _set_data(self, value):
        """Replace the underlying buffer; propagate into base if this is a view."""
        self._data = value
        if self._base is not None:
            b = self._base
            b._set_data(b._data.at[self._base_index].set(value.astype(b._data.dtype)))
            self._base_version = b._version  # our buffer already matches

    # ------------------------------------------------------------------
    # autograd
    # ------------------------------------------------------------------
    def attach_grad(self, grad_req="write", stype=None):
        self._ag_is_leaf = True
        self._ag_grad_req = grad_req
        if stype in ("row_sparse", "csr"):
            # sparse grad buffer: backward writes touched rows only (the
            # reference Embedding sparse_grad path); never densified unless
            # a dense cotangent actually arrives
            from . import sparse as _sp
            self.grad = _sp.zeros(stype, self.shape, ctx=self._ctx,
                                  dtype=self.dtype)
        else:
            self.grad = _wrap(_jnp().zeros_like(self._data), ctx=self._ctx)
        self._ag_entry = None

    def detach(self):
        out = _wrap(self._data, ctx=self._ctx)
        return out

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    # ------------------------------------------------------------------
    # indexing
    # ------------------------------------------------------------------
    def _convert_index(self, key):
        if isinstance(key, NDArray):
            return key._data
        if isinstance(key, tuple):
            return tuple(k._data if isinstance(k, NDArray) else k for k in key)
        return key

    def __getitem__(self, key):
        key_c = self._convert_index(key)
        data = self._data[key_c]
        out = _wrap(data, ctx=self._ctx)
        # basic (non-advanced) indexing yields a writeable view
        if not isinstance(key, NDArray) and not (
                isinstance(key, tuple) and any(isinstance(k, (NDArray, list, _np.ndarray)) for k in key)) \
                and not isinstance(key, (list, _np.ndarray)):
            out._base = self
            out._base_index = key_c
            out._base_version = self._version
        if autograd.is_recording():
            autograd.record_op(lambda v: v[key_c], [self], [out], name="slice")
        return out

    def __setitem__(self, key, value):
        if isinstance(key, slice) and key == slice(None):
            idx = slice(None)
        else:
            idx = self._convert_index(key)
        jnp = _jnp()
        if isinstance(value, NDArray):
            v = value._data
        elif isinstance(value, numeric_types):
            v = value
        else:
            v = jnp.asarray(value)
        if isinstance(idx, slice) and idx == slice(None):
            if isinstance(v, (int, float)):
                new = jnp.full_like(self._data, v)
            else:
                new = jnp.broadcast_to(jnp.asarray(v, dtype=self._data.dtype),
                                       self.shape).astype(self._data.dtype)
            import jax.core as _jcore
            if not isinstance(self._data, _jcore.Tracer) and \
                    getattr(self._data, "committed", False):
                # in-place writes keep the array on its device (the reference
                # NDArray's context is sticky; matters for group2ctx).
                # Tracers (whole-step capture: compiled_step traces python
                # optimizers through here) have no .committed — probing it
                # raises ConcretizationTypeError, and inside a trace XLA
                # owns placement anyway.
                import jax
                new = jax.device_put(new, list(self._data.devices())[0])
        else:
            v = jnp.asarray(v).astype(self._data.dtype)
            new = self._data.at[idx].set(v)
        self._set_data(new)

    # ------------------------------------------------------------------
    # arithmetic operators (dispatch through the op registry so autograd sees them)
    # ------------------------------------------------------------------
    def _binop(self, other, op_arr, op_scalar, reverse=False):
        if isinstance(other, NDArray):
            a, b = (other, self) if reverse else (self, other)
            return invoke(op_arr, [a, b], {})
        if isinstance(other, numeric_types):
            return invoke(op_scalar, [self], {"scalar": float(other), "reverse": reverse})
        if isinstance(other, _np.ndarray):
            return self._binop(array(other, ctx=self._ctx, dtype=other.dtype), op_arr, op_scalar, reverse)
        if _is_jax_value(other):
            # raw jax arrays/tracers mix with NDArrays during whole-step
            # capture (compiled_step threads lr/t as traced scalars through
            # python optimizer math like ``lr * state``): python dispatches
            # to our reflected op after the tracer's returns NotImplemented
            return self._binop(_wrap(other, ctx=self._ctx), op_arr,
                               op_scalar, reverse)
        return NotImplemented

    def __add__(self, o):  return self._binop(o, "broadcast_add", "_plus_scalar")
    def __radd__(self, o): return self._binop(o, "broadcast_add", "_plus_scalar", True)
    def __sub__(self, o):  return self._binop(o, "broadcast_sub", "_minus_scalar")
    def __rsub__(self, o): return self._binop(o, "broadcast_sub", "_minus_scalar", True)
    def __mul__(self, o):  return self._binop(o, "broadcast_mul", "_mul_scalar")
    def __rmul__(self, o): return self._binop(o, "broadcast_mul", "_mul_scalar", True)
    def __truediv__(self, o):  return self._binop(o, "broadcast_div", "_div_scalar")
    def __rtruediv__(self, o): return self._binop(o, "broadcast_div", "_div_scalar", True)
    def __mod__(self, o):  return self._binop(o, "broadcast_mod", "_mod_scalar")
    def __matmul__(self, o):
        if not isinstance(o, NDArray):
            o = array(_np.asarray(o), ctx=self._ctx)
        return invoke("dot", [self, o], {})

    def __rmatmul__(self, o):
        if not isinstance(o, NDArray):
            o = array(_np.asarray(o), ctx=self._ctx)
        return invoke("dot", [o, self], {})
    def __rmod__(self, o): return self._binop(o, "broadcast_mod", "_mod_scalar", True)
    def __pow__(self, o):  return self._binop(o, "broadcast_power", "_power_scalar")
    def __rpow__(self, o): return self._binop(o, "broadcast_power", "_power_scalar", True)
    def __neg__(self):     return invoke("negative", [self], {})
    def __abs__(self):     return invoke("abs", [self], {})

    def __eq__(self, o):
        if o is None:
            return False
        return self._binop(o, "broadcast_equal", "_equal_scalar")

    def __ne__(self, o):
        if o is None:
            return True
        return self._binop(o, "broadcast_not_equal", "_not_equal_scalar")

    def __gt__(self, o): return self._binop(o, "broadcast_greater", "_greater_scalar")
    def __ge__(self, o): return self._binop(o, "broadcast_greater_equal", "_greater_equal_scalar")
    def __lt__(self, o): return self._binop(o, "broadcast_lesser", "_lesser_scalar")
    def __le__(self, o): return self._binop(o, "broadcast_lesser_equal", "_lesser_equal_scalar")

    __hash__ = object.__hash__

    def _inplace(self, other, op_arr, op_scalar):
        res = self._binop(other, op_arr, op_scalar)
        self._set_data(res._data.astype(self._data.dtype))
        return self

    def __iadd__(self, o): return self._inplace(o, "broadcast_add", "_plus_scalar")
    def __isub__(self, o): return self._inplace(o, "broadcast_sub", "_minus_scalar")
    def __imul__(self, o): return self._inplace(o, "broadcast_mul", "_mul_scalar")
    def __itruediv__(self, o): return self._inplace(o, "broadcast_div", "_div_scalar")

    # ------------------------------------------------------------------
    # method aliases onto registered ops (subset mirrored from ndarray.py)
    # ------------------------------------------------------------------
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        if kwargs.get("shape"):
            shape = tuple(kwargs["shape"])
        return invoke("Reshape", [self], {"shape": shape})

    def reshape_like(self, other):
        return invoke("reshape_like", [self, other], {})

    def transpose(self, axes=None):
        return invoke("transpose", [self], {"axes": axes})

    @property
    def T(self):
        return self.transpose()

    def flatten(self):
        return invoke("Flatten", [self], {})

    def expand_dims(self, axis):
        return invoke("expand_dims", [self], {"axis": axis})

    def squeeze(self, axis=None):
        return invoke("squeeze", [self], {"axis": axis})

    def broadcast_to(self, shape):
        return invoke("broadcast_to", [self], {"shape": tuple(shape)})

    def broadcast_like(self, other):
        return invoke("broadcast_like", [self, other], {})

    def slice(self, begin, end, step=None):
        return invoke("slice", [self], {"begin": begin, "end": end, "step": step})

    def slice_axis(self, axis, begin, end):
        return invoke("slice_axis", [self], {"axis": axis, "begin": begin, "end": end})

    def take(self, indices, axis=0, mode="clip"):
        return invoke("take", [self, indices], {"axis": axis, "mode": mode})

    def pick(self, index, axis=-1, keepdims=False):
        return invoke("pick", [self, index], {"axis": axis, "keepdims": keepdims})

    def one_hot(self, depth, on_value=1.0, off_value=0.0, dtype="float32"):
        return invoke("one_hot", [self], {"depth": depth, "on_value": on_value,
                                          "off_value": off_value, "dtype": dtype})

    def repeat(self, repeats, axis=None):
        return invoke("repeat", [self], {"repeats": repeats, "axis": axis})

    def tile(self, reps):
        return invoke("tile", [self], {"reps": tuple(reps)})

    def pad(self, mode, pad_width, constant_value=0.0):
        return invoke("Pad", [self], {"mode": mode, "pad_width": tuple(pad_width),
                                      "constant_value": constant_value})

    def clip(self, a_min, a_max):
        return invoke("clip", [self], {"a_min": a_min, "a_max": a_max})

    def abs(self): return invoke("abs", [self], {})
    def sign(self): return invoke("sign", [self], {})
    def exp(self): return invoke("exp", [self], {})
    def log(self): return invoke("log", [self], {})
    def sqrt(self): return invoke("sqrt", [self], {})
    def square(self): return invoke("square", [self], {})
    def relu(self): return invoke("relu", [self], {})
    def sigmoid(self): return invoke("sigmoid", [self], {})
    def tanh(self): return invoke("tanh", [self], {})
    def softmax(self, axis=-1): return invoke("softmax", [self], {"axis": axis})
    def log_softmax(self, axis=-1): return invoke("log_softmax", [self], {"axis": axis})
    def round(self): return invoke("round", [self], {})
    def floor(self): return invoke("floor", [self], {})
    def ceil(self): return invoke("ceil", [self], {})

    def _reduce(self, name, axis=None, keepdims=False, **kw):
        attrs = {"axis": axis, "keepdims": keepdims}
        attrs.update(kw)
        return invoke(name, [self], attrs)

    def sum(self, axis=None, keepdims=False): return self._reduce("sum", axis, keepdims)
    def mean(self, axis=None, keepdims=False): return self._reduce("mean", axis, keepdims)
    def max(self, axis=None, keepdims=False): return self._reduce("max", axis, keepdims)
    def min(self, axis=None, keepdims=False): return self._reduce("min", axis, keepdims)
    def prod(self, axis=None, keepdims=False): return self._reduce("prod", axis, keepdims)
    def nansum(self, axis=None, keepdims=False): return self._reduce("nansum", axis, keepdims)
    def norm(self, ord=2, axis=None, keepdims=False):
        return invoke("norm", [self], {"ord": ord, "axis": axis, "keepdims": keepdims})

    def argmax(self, axis=None, keepdims=False):
        return invoke("argmax", [self], {"axis": axis, "keepdims": keepdims})

    def argmin(self, axis=None, keepdims=False):
        return invoke("argmin", [self], {"axis": axis, "keepdims": keepdims})

    def argsort(self, axis=-1, is_ascend=True):
        return invoke("argsort", [self], {"axis": axis, "is_ascend": is_ascend})

    def sort(self, axis=-1, is_ascend=True):
        return invoke("sort", [self], {"axis": axis, "is_ascend": is_ascend})

    def topk(self, axis=-1, k=1, ret_typ="indices", is_ascend=False):
        return invoke("topk", [self], {"axis": axis, "k": k, "ret_typ": ret_typ,
                                       "is_ascend": is_ascend})

    def dot(self, other, transpose_a=False, transpose_b=False):
        return invoke("dot", [self, other], {"transpose_a": transpose_a,
                                             "transpose_b": transpose_b})

    def tostype(self, stype):
        if stype == "default":
            return self
        from . import sparse
        return sparse.cast_storage(self, stype)

    def as_nd_ndarray(self):
        return self


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

import weakref as _weakref

# live-array registry for waitall's WaitForAll semantics
_LIVE_ARRAYS = _weakref.WeakSet()


def _is_jax_value(obj):
    """Is ``obj`` a raw jax array or tracer (not an NDArray/numpy/scalar)?"""
    import jax
    return isinstance(obj, (jax.Array, jax.core.Tracer))


def _wrap(jax_value, ctx=None):
    arr = NDArray(jax_value, ctx=ctx)
    _LIVE_ARRAYS.add(arr)
    return arr


def from_jax(jax_value, ctx=None):
    return NDArray(jax_value, ctx=ctx)


def invoke(op_name, inputs, attrs, out=None):
    """Imperative op invocation — the analog of Imperative::Invoke
    (src/imperative/imperative.cc:87): resolve op, apply (jit-cached),
    wrap/record/write-out.  While profiling, every dispatch — including
    the sparse/FComputeEx early returns — becomes a span + aggregate row
    (ProfileOperator analog, src/profiler/profiler.h)."""
    if profiler.profiling_imperative():
        with profiler.span(op_name, cat="operator"):
            return _invoke(op_name, inputs, attrs, out)
    return _invoke(op_name, inputs, attrs, out)


def _invoke(op_name, inputs, attrs, out=None):
    if (op_name == "Embedding" and out is None and autograd.is_recording()
            and str(attrs.get("sparse_grad", False)).lower() in ("true", "1")):
        # sparse_grad: record a row-sparse weight cotangent instead of the
        # dense scatter jax.vjp would produce
        from .sparse import sparse_embedding
        return sparse_embedding(inputs[0], inputs[1])
    op = get_op(op_name)
    attrs = dict(attrs)
    if op.mode_for(attrs):
        attrs["_training"] = bool(autograd.is_training())
    if op.rng_for(attrs):
        from .. import random as _random
        attrs["_rng_key"] = _random.next_key()

    # FComputeEx dispatch: a sparse-aware implementation consumes NDArray
    # inputs directly (aux fields, no densification).  Skipped while the
    # tape records — sparse handlers aren't traceable, so gradients route
    # through the dense fallback (the reference's storage fallback,
    # src/common/exec_utils.h).
    if op.fcompute_ex is not None and not autograd.is_recording() and any(
            getattr(i, "_stype", "default") != "default" for i in inputs):
        ex_result = op.fcompute_ex(attrs, *inputs)
        if ex_result is not NotImplemented:
            ex_outputs = (list(ex_result) if isinstance(ex_result, (tuple, list))
                          else [ex_result])
            if out is not None:
                outs = out if isinstance(out, (list, tuple)) else [out]
                for o, r in zip(outs, ex_outputs):
                    if getattr(r, "_stype", "default") != "default":
                        r.copyto(o)
                    else:
                        # o.dtype, not o._data.dtype — the latter would
                        # densify a lazy sparse out target just to read it
                        o._set_data(r._data.astype(o.dtype))
                return out
            return ex_outputs if isinstance(ex_result, (tuple, list)) else ex_result

    vals = [(i._data if isinstance(i, NDArray) else i) for i in inputs]
    result = op.apply(attrs, *vals)
    multi = isinstance(result, (tuple, list))
    results = list(result) if multi else [result]

    ctx = inputs[0]._ctx if inputs and isinstance(inputs[0], NDArray) else current_context()
    outputs = [_wrap(r, ctx=ctx) for r in results]

    if autograd.is_recording():
        nd_inputs = [i for i in inputs if isinstance(i, NDArray)]
        if len(nd_inputs) == len(inputs):
            # rng ops take the key as a trailing tape input so the cached
            # traceable (and its jitted backward) is shared across calls
            extra = (attrs["_rng_key"],) if "_rng_key" in attrs else ()
            autograd.record_op(op._traceable(attrs), nd_inputs, outputs,
                               name=op_name, extra_input_vals=extra)

    if out is not None:
        outs = out if isinstance(out, (list, tuple)) else [out]
        for o, r in zip(outs, outputs):
            # o.dtype, not o._data.dtype (densifies a lazy sparse target)
            o._set_data(r._data.astype(o.dtype))
            o._ag_entry = r._ag_entry
        return out
    if multi:
        return outputs
    return outputs[0]


def waitall():
    """Block until all pending computation completes (Engine::WaitForAll).

    XLA dispatch is async; fencing means blocking on every live array's
    pending computation.  We track live NDArrays weakly and
    block_until_ready each — plus an effects barrier for callbacks."""
    import jax
    jax.effects_barrier()
    for arr in list(_LIVE_ARRAYS):
        data = getattr(arr, "_data_buf", None)
        if data is not None and hasattr(data, "block_until_ready"):
            try:
                data.block_until_ready()
            except Exception:
                pass  # deleted buffers (donated args) are already settled


# ---------------------------------------------------------------------------
# creation functions
# ---------------------------------------------------------------------------

def array(source_array, ctx=None, dtype=None):
    import jax
    ctx = _ctx_of(None, ctx)
    if isinstance(source_array, NDArray):
        src = source_array._data
        if dtype is not None:
            src = src.astype(_as_dtype(dtype))
        return _wrap(jax.device_put(src, ctx.jax_device()), ctx=ctx)
    np_arr = _np.asarray(source_array)
    if dtype is None:
        dtype = _np.float32 if np_arr.dtype == _np.float64 else np_arr.dtype
    np_arr = np_arr.astype(_as_dtype(dtype)) if np_arr.dtype != dtype else np_arr
    return _wrap(jax.device_put(np_arr, ctx.jax_device()), ctx=ctx)


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx=None, dtype=None, **kwargs):
    import jax
    ctx = _ctx_of(None, ctx)
    if isinstance(shape, int):
        shape = (shape,)
    v = _jnp().zeros(shape, dtype=_as_dtype(dtype))
    return _wrap(jax.device_put(v, ctx.jax_device()), ctx=ctx)


def ones(shape, ctx=None, dtype=None, **kwargs):
    import jax
    ctx = _ctx_of(None, ctx)
    if isinstance(shape, int):
        shape = (shape,)
    v = _jnp().ones(shape, dtype=_as_dtype(dtype))
    return _wrap(jax.device_put(v, ctx.jax_device()), ctx=ctx)


def full(shape, val, ctx=None, dtype=None, out=None):
    import jax
    ctx = _ctx_of(None, ctx)
    if isinstance(shape, int):
        shape = (shape,)
    v = _jnp().full(shape, val, dtype=_as_dtype(dtype))
    r = _wrap(jax.device_put(v, ctx.jax_device()), ctx=ctx)
    if out is not None:
        out._set_data(r._data)
        return out
    return r


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None):
    jnp = _jnp()
    v = jnp.arange(start, stop, step, dtype=_as_dtype(dtype))
    if repeat > 1:
        v = jnp.repeat(v, repeat)
    return array(v, ctx=ctx, dtype=dtype)


def moveaxis(tensor, source, destination):
    return _wrap(_jnp().moveaxis(tensor._data, source, destination), ctx=tensor._ctx)


def concat(*data, dim=1, out=None):
    return invoke("Concat", list(data), {"dim": dim}, out=out)


def stack(*data, axis=0, out=None):
    return invoke("stack", list(data), {"axis": axis}, out=out)
