"""Weight initializers (reference: python/mxnet/initializer.py).

Full strategy set: Zero/One/Constant/Uniform/Normal/Orthogonal/Xavier/MSRAPrelu/
Bilinear/LSTMBias/FusedRNN, plus the registry + ``InitDesc``/pattern-matching
``Mixed`` initializer.
"""
from __future__ import annotations

import json
import re
import threading

import numpy as _np

from . import random as _rand

from .base import string_types

_INITIALIZER_REGISTRY = {}
_INITIALIZER_REGISTRY_LOCK = threading.Lock()


def register(klass):
    with _INITIALIZER_REGISTRY_LOCK:
        _INITIALIZER_REGISTRY[klass.__name__.lower()] = klass
    return klass


class InitDesc(str):
    """Name (with attrs) describing the parameter to initialize."""
    def __new__(cls, name, attrs=None, global_init=None):
        ret = super().__new__(cls, name)
        ret.attrs = attrs or {}
        ret.global_init = global_init
        return ret


class Initializer:
    """Base initializer; callable on (InitDesc/name, NDArray)."""

    def __init__(self, **kwargs):
        self._kwargs = kwargs
        self._verbose = False
        self._print_func = None

    def set_verbosity(self, verbose=False, print_func=None):
        self._verbose = verbose
        self._print_func = print_func
        return self

    def dumps(self):
        return json.dumps([self.__class__.__name__.lower(), self._kwargs])

    def __call__(self, desc, arr):
        if not isinstance(desc, string_types):
            raise TypeError("desc must be a string or InitDesc")
        if isinstance(desc, InitDesc) and desc.global_init is None:
            desc.global_init = self
        init = getattr(desc, "attrs", {}).get("__init__", "")
        if init:
            # a Variable's own initializer: dumps() JSON, or a bare registry
            # name ("ones" — what gluon Parameter.var() records)
            klass, kwargs = json.loads(init) if init.startswith("[") \
                else (init, {})
            _INITIALIZER_REGISTRY[klass.lower()](**kwargs)._init_weight(desc, arr)
            return
        name = str(desc)
        if name.endswith("weight"):
            self._init_weight(name, arr)
        elif name.endswith("bias"):
            self._init_bias(name, arr)
        elif name.endswith("gamma"):
            self._init_gamma(name, arr)
        elif name.endswith("beta"):
            self._init_beta(name, arr)
        elif name.endswith("running_mean") or name.endswith("moving_mean"):
            self._init_zero(name, arr)
        elif name.endswith("running_var") or name.endswith("moving_var"):
            self._init_one(name, arr)
        elif name.endswith("moving_inv_var") or name.endswith("moving_avg"):
            self._init_zero(name, arr)
        elif name.endswith("min") or name.endswith("max"):
            self._init_zero(name, arr)
        elif name.endswith("parameters"):
            # fused-RNN packed blob; FusedRNN initializer does the structured
            # per-matrix init, any other initializer gets a flat uniform
            self._init_rnn_packed(name, arr)
        else:
            self._init_default(name, arr)

    def _init_rnn_packed(self, name, arr):
        if isinstance(self, FusedRNN):
            self._init_weight(name, arr)
        else:
            self._set(arr, _rand.derived_numpy_rng().uniform(-0.07, 0.07, arr.shape))

    def _set(self, arr, np_value):
        arr[:] = np_value.astype(_np.float32) if np_value.dtype == _np.float64 else np_value

    def _init_weight(self, name, arr):
        raise NotImplementedError("must override _init_weight")

    def _init_bias(self, name, arr):
        self._init_zero(name, arr)

    def _init_gamma(self, name, arr):
        self._init_one(name, arr)

    def _init_beta(self, name, arr):
        self._init_zero(name, arr)

    def _init_zero(self, name, arr):
        arr[:] = 0.0

    def _init_one(self, name, arr):
        arr[:] = 1.0

    def _init_default(self, name, arr):
        raise ValueError(
            "Unknown initialization pattern for %s. Default initialization is now "
            "limited to \"weight\", \"bias\", \"gamma\", and \"beta\". Either use "
            "mx.sym.Variable(init=mx.init.*) or name your params with those "
            "suffixes." % name)


@register
class Zero(Initializer):
    def _init_weight(self, name, arr):
        arr[:] = 0.0
    _init_default = _init_weight


@register
class One(Initializer):
    def _init_weight(self, name, arr):
        arr[:] = 1.0
    _init_default = _init_weight


@register
class Constant(Initializer):
    def __init__(self, value=0.0):
        super().__init__(value=value)
        self.value = value

    def _init_weight(self, name, arr):
        arr[:] = self.value
    _init_default = _init_weight


@register
class Uniform(Initializer):
    def __init__(self, scale=0.07):
        super().__init__(scale=scale)
        self.scale = scale

    def _init_weight(self, name, arr):
        self._set(arr, _rand.derived_numpy_rng().uniform(-self.scale, self.scale, arr.shape))


@register
class Normal(Initializer):
    def __init__(self, sigma=0.01):
        super().__init__(sigma=sigma)
        self.sigma = sigma

    def _init_weight(self, name, arr):
        self._set(arr, _rand.derived_numpy_rng().normal(0, self.sigma, arr.shape))


@register
class Orthogonal(Initializer):
    def __init__(self, scale=1.414, rand_type="uniform"):
        super().__init__(scale=scale, rand_type=rand_type)
        self.scale = scale
        self.rand_type = rand_type

    def _init_weight(self, name, arr):
        nout = arr.shape[0]
        nin = int(_np.prod(arr.shape[1:]))
        if self.rand_type == "uniform":
            tmp = _rand.derived_numpy_rng().uniform(-1.0, 1.0, (nout, nin))
        else:
            tmp = _rand.derived_numpy_rng().normal(0.0, 1.0, (nout, nin))
        u, _, v = _np.linalg.svd(tmp, full_matrices=False)
        res = u if u.shape == tmp.shape else v
        self._set(arr, (self.scale * res).reshape(arr.shape))


@register
class Xavier(Initializer):
    def __init__(self, rnd_type="uniform", factor_type="avg", magnitude=3):
        super().__init__(rnd_type=rnd_type, factor_type=factor_type,
                         magnitude=magnitude)
        self.rnd_type = rnd_type
        self.factor_type = factor_type
        self.magnitude = float(magnitude)

    def _init_weight(self, name, arr):
        shape = arr.shape
        hw_scale = 1.0
        if len(shape) < 2:
            raise ValueError("Xavier initializer cannot be applied to vector %s" % name)
        if len(shape) > 2:
            hw_scale = _np.prod(shape[2:])
        fan_in, fan_out = shape[1] * hw_scale, shape[0] * hw_scale
        try:
            factor = {"avg": (fan_in + fan_out) / 2.0,
                      "in": fan_in,
                      "out": fan_out}[self.factor_type]
        except KeyError:
            raise ValueError("Incorrect factor type %r" % (self.factor_type,))
        scale = _np.sqrt(self.magnitude / factor)
        if self.rnd_type == "uniform":
            self._set(arr, _rand.derived_numpy_rng().uniform(-scale, scale, shape))
        elif self.rnd_type == "gaussian":
            self._set(arr, _rand.derived_numpy_rng().normal(0, scale, shape))
        else:
            raise ValueError("Unknown random type")


@register
class MSRAPrelu(Xavier):
    def __init__(self, factor_type="avg", slope=0.25):
        magnitude = 2.0 / (1 + slope ** 2)
        super().__init__("gaussian", factor_type, magnitude)
        self._kwargs = {"factor_type": factor_type, "slope": slope}


@register
class Bilinear(Initializer):
    def _init_weight(self, name, arr):
        weight = _np.zeros(arr.shape, dtype=_np.float32)
        shape = arr.shape
        f = _np.ceil(shape[3] / 2.0)
        c = (2 * f - 1 - f % 2) / (2.0 * f)
        for i in range(int(_np.prod(shape))):
            x = i % shape[3]
            y = (i // shape[3]) % shape[2]
            weight.flat[i] = (1 - abs(x / f - c)) * (1 - abs(y / f - c))
        self._set(arr, weight)


@register
class LSTMBias(Initializer):
    def __init__(self, forget_bias=1.0):
        super().__init__(forget_bias=forget_bias)
        self.forget_bias = forget_bias

    def _init_weight(self, name, arr):
        b = _np.zeros(arr.shape, dtype=_np.float32)
        num_hidden = int(b.shape[0] / 4)
        b[num_hidden:2 * num_hidden] = self.forget_bias
        self._set(arr, b)
    _init_default = _init_weight


@register
class FusedRNN(Initializer):
    """Initialize the packed parameter blob of the fused RNN op."""

    def __init__(self, init, num_hidden, num_layers, mode, bidirectional=False,
                 forget_bias=1.0):
        if isinstance(init, str):
            klass, kwargs = json.loads(init)
            init = _INITIALIZER_REGISTRY[klass.lower()](**kwargs)
        super().__init__(init=init.dumps() if init else None, num_hidden=num_hidden,
                         num_layers=num_layers, mode=mode, bidirectional=bidirectional,
                         forget_bias=forget_bias)
        self._init = init
        self._num_hidden = num_hidden
        self._num_layers = num_layers
        self._mode = mode
        self._bidirectional = bidirectional
        self._forget_bias = forget_bias

    def _init_weight(self, desc, arr):
        ngates = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[self._mode]
        ndir = 2 if self._bidirectional else 1
        H = self._num_hidden
        # reference semantics: fall back to the global initializer when no
        # per-matrix init was given (initializer.py FusedRNN docstring)
        sub_init = self._init
        if sub_init is None:
            sub_init = getattr(desc, "global_init", None) or Uniform(0.07)
        np_arr = _np.array(arr.asnumpy())  # asnumpy views are read-only
        # input size inferred from total length
        # total = sum_l sum_d (G*H*in_l + G*H*H) + 2*L*D*G*H
        L, D, G = self._num_layers, ndir, ngates
        n_bias = 2 * L * D * G * H
        n_w = np_arr.size - n_bias
        # solve for I: layer0 in = I, others in = H*D
        rest = (L - 1) * D * (G * H * H * D + G * H * H)
        I = (n_w - rest - D * G * H * H) // (D * G * H)
        offset = 0
        from .ndarray import array as _nd_array
        for layer in range(L):
            in_size = int(I) if layer == 0 else H * D
            for d in range(D):
                for wname, wshape in (("i2h_weight", (G * H, in_size)),
                                      ("h2h_weight", (G * H, H))):
                    size = wshape[0] * wshape[1]
                    block = _np.empty(wshape, dtype=_np.float32)
                    tmp = _nd_array(block)
                    sub_init("%s_l%d_%s" % (str(desc), layer, wname), tmp)
                    np_arr[offset:offset + size] = tmp.asnumpy().reshape(-1)
                    offset += size
        for layer in range(L):
            for d in range(D):
                for bname in ("i2h_bias", "h2h_bias"):
                    block = _np.zeros(G * H, dtype=_np.float32)
                    if self._mode == "lstm":
                        block[H:2 * H] = self._forget_bias / 2.0
                    np_arr[offset:offset + G * H] = block
                    offset += G * H
        arr[:] = np_arr
    _init_default = _init_weight


@register
class Mixed(Initializer):
    """Dispatch by regex on parameter name."""

    def __init__(self, patterns, initializers):
        super().__init__()
        if len(patterns) != len(initializers):
            raise ValueError("patterns and initializers must have same length")
        self.map = list(zip([re.compile(p) for p in patterns], initializers))

    def __call__(self, name, arr):
        for prog, init in self.map:
            if prog.match(str(name)):
                init(name, arr)
                return
        raise ValueError("Parameter name %s did not match any pattern" % name)


@register
class Load:
    """Initialize from existing arrays (reference initializer.Load)."""

    def __init__(self, param, default_init=None, verbose=False):
        qualified = {}
        for name, arr in param.items():
            if name.startswith("arg:") or name.startswith("aux:"):
                qualified[name[4:]] = arr
            else:
                qualified[name] = arr
        self.param = qualified
        self.default_init = default_init
        self.verbose = verbose

    def __call__(self, name, arr):
        if name in self.param:
            if arr.shape != self.param[name].shape:
                raise ValueError("Parameter %s has wrong shape" % name)
            arr[:] = self.param[name]
        else:
            if self.default_init is None:
                raise ValueError("Cannot init parameter %s (not in loaded params)" % name)
            self.default_init(name, arr)


# string aliases used across gluon layer definitions
_INITIALIZER_REGISTRY["zeros"] = Zero
_INITIALIZER_REGISTRY["ones"] = One


def create(name, **kwargs):
    if isinstance(name, Initializer):
        return name
    return _INITIALIZER_REGISTRY[name.lower()](**kwargs)
