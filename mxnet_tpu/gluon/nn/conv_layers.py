"""Convolution and pooling layers (reference: python/mxnet/gluon/nn/conv_layers.py)."""
from __future__ import annotations

import contextvars
from contextlib import contextmanager

import numpy as _np

from ..block import HybridBlock

# construction-time default data layout: channel-first matches the
# reference; the channels_last() scope flips every conv/pool/batchnorm
# BUILT inside it to the TPU-preferred channel-last layout without
# per-layer plumbing (explicit layout=/axis= arguments always win)
_channels_last_scope = contextvars.ContextVar("mxnet_tpu_channels_last",
                                              default=False)

_CHANNEL_FIRST = {1: "NCW", 2: "NCHW", 3: "NCDHW"}
_CHANNEL_LAST = {1: "NWC", 2: "NHWC", 3: "NDHWC"}


@contextmanager
def channels_last(active=True):
    """Scope under which conv/pool layers default to channel-last layouts
    and BatchNorm to axis=-1 — build any model (the whole model_zoo
    included) channel-last::

        with nn.channels_last():
            net = vision.mobilenet1_0()

    Channel-last is the layout XLA prefers on TPU (no edge transposes
    around the convs); weights store as (O, *kernel, I) and initializers
    draw in canonical order, so results match the channel-first build.
    Transposed convs keep channel-first (op limitation, documented)."""
    token = _channels_last_scope.set(bool(active))
    try:
        yield
    finally:
        _channels_last_scope.reset(token)


def _resolve_layout(layout, rank, channel_last_ok=True):
    if layout is not None:
        return layout
    if _channels_last_scope.get():
        if not channel_last_ok:
            # silent channel-first inside the scope would convolve over the
            # wrong axes downstream; make the limitation loud
            raise ValueError(
                "transposed convolutions do not support channel-last "
                "layouts; pass an explicit layout= (e.g. 'NCHW') to build "
                "one inside nn.channels_last()")
        return _CHANNEL_LAST[rank]
    return _CHANNEL_FIRST[rank]


def default_batchnorm_axis():
    """1 (reference default) or -1 inside a channels_last() scope."""
    return -1 if _channels_last_scope.get() else 1


def _pair(v, n):
    """Normalize int-or-sequence to an n-tuple of ints (shared with the
    contrib ConvRNN cells)."""
    if isinstance(v, (list, tuple)):
        assert len(v) == n, "expected %d-tuple, got %r" % (n, v)
        return tuple(int(x) for x in v)
    return (int(v),) * n


class _Conv(HybridBlock):
    def __init__(self, channels, kernel_size, strides, padding, dilation,
                 groups, layout, in_channels=0, activation=None, use_bias=True,
                 weight_initializer=None, bias_initializer="zeros", op_name=None,
                 adj=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self._channels = channels
            self._in_channels = in_channels
            if isinstance(kernel_size, int):
                kernel_size = (kernel_size,)
            self._kernel = tuple(kernel_size)
            layout = _resolve_layout(
                layout, len(self._kernel),
                channel_last_ok=(op_name or "Convolution") == "Convolution")
            nd_ = len(self._kernel)
            self._strides = _pair(strides, nd_)
            self._padding = _pair(padding, nd_)
            self._dilation = _pair(dilation, nd_)
            self._groups = groups
            self._layout = layout
            self._op_name = op_name or "Convolution"
            self._kwargs = {
                "kernel": self._kernel, "stride": self._strides,
                "dilate": self._dilation, "pad": self._padding,
                "num_filter": channels, "num_group": groups,
                "no_bias": not use_bias, "layout": layout}
            if adj is not None:
                self._kwargs["adj"] = _pair(adj, nd_)
            self._channel_last = not layout.startswith("NC")
            if self._op_name == "Convolution":
                in_per_group = in_channels // groups if in_channels else 0
                # channel-last keeps the op's (O, spatial..., I) weight layout
                # so the compiled graph needs no weight transposes either
                wshape = ((channels,) + self._kernel + (in_per_group,)
                          if self._channel_last
                          else (channels, in_per_group) + self._kernel)
            else:  # Deconvolution: (in, out/g, *k)
                wshape = (in_channels, channels // groups) + self._kernel
            init_perm = None
            if self._op_name == "Convolution" and self._channel_last:
                nd_ = len(self._kernel)
                init_perm = (0,) + tuple(range(2, 2 + nd_)) + (1,)
            self.weight = self.params.get("weight", shape=wshape,
                                          init=weight_initializer,
                                          allow_deferred_init=True,
                                          init_perm=init_perm)
            if use_bias:
                self.bias = self.params.get("bias", shape=(channels,),
                                            init=bias_initializer,
                                            allow_deferred_init=True)
            else:
                self.bias = None
            if activation is not None:
                from .activations import Activation
                self.act = Activation(activation, prefix=activation + "_")
            else:
                self.act = None

    def _shape_hook(self, x, *args):
        if self._op_name == "Convolution":
            if self._channel_last:
                in_channels = x.shape[-1]
                self.weight.shape = (self._channels,) + self._kernel \
                    + (in_channels // self._groups,)
            else:
                in_channels = x.shape[1]
                self.weight.shape = (self._channels,
                                     in_channels // self._groups) + self._kernel
        else:
            self.weight.shape = (x.shape[1], self._channels // self._groups) \
                + self._kernel

    def hybrid_forward(self, F, x, weight, bias=None):
        # num_filter rides along: symbolic shape inference deduces a
        # deferred weight's in_channels from it (ops/nn_ops.py param shapes)
        attrs = {k: v for k, v in self._kwargs.items() if k != "no_bias"}
        op = getattr(F, self._op_name)
        if bias is None:
            act = op(x, weight, no_bias=True, **attrs)
        else:
            act = op(x, weight, bias, no_bias=False, **attrs)
        if self.act is not None:
            act = self.act(act) if not F.__name__.endswith("symbol") \
                else self.act._build_symbol(act)
        return act

    def __repr__(self):
        s = "{name}({mapping}, kernel_size={kernel}, stride={stride}"
        len_kernel_size = len(self._kwargs["kernel"])
        if self._kwargs["pad"] != (0,) * len_kernel_size:
            s += ", padding={pad}"
        if self._kwargs["dilate"] != (1,) * len_kernel_size:
            s += ", dilation={dilate}"
        if self._kwargs["num_group"] != 1:
            s += ", groups={num_group}"
        if self.bias is None:
            s += ", bias=False"
        s += ")"
        shape = self.weight.shape
        return s.format(name=self.__class__.__name__,
                        mapping="{0} -> {1}".format(shape[1] if shape[1] else None,
                                                    shape[0]),
                        **self._kwargs)


class Conv1D(_Conv):
    def __init__(self, channels, kernel_size, strides=1, padding=0, dilation=1,
                 groups=1, layout=None, activation=None, use_bias=True,
                 weight_initializer=None, bias_initializer="zeros",
                 in_channels=0, **kwargs):
        if isinstance(kernel_size, int):
            kernel_size = (kernel_size,)
        super().__init__(channels, kernel_size, strides, padding, dilation,
                         groups, layout, in_channels, activation, use_bias,
                         weight_initializer, bias_initializer, **kwargs)


class Conv2D(_Conv):
    def __init__(self, channels, kernel_size, strides=(1, 1), padding=(0, 0),
                 dilation=(1, 1), groups=1, layout=None, activation=None,
                 use_bias=True, weight_initializer=None,
                 bias_initializer="zeros", in_channels=0, **kwargs):
        if isinstance(kernel_size, int):
            kernel_size = (kernel_size,) * 2
        super().__init__(channels, kernel_size, strides, padding, dilation,
                         groups, layout, in_channels, activation, use_bias,
                         weight_initializer, bias_initializer, **kwargs)


class Conv3D(_Conv):
    def __init__(self, channels, kernel_size, strides=(1, 1, 1),
                 padding=(0, 0, 0), dilation=(1, 1, 1), groups=1,
                 layout=None, activation=None, use_bias=True,
                 weight_initializer=None, bias_initializer="zeros",
                 in_channels=0, **kwargs):
        if isinstance(kernel_size, int):
            kernel_size = (kernel_size,) * 3
        super().__init__(channels, kernel_size, strides, padding, dilation,
                         groups, layout, in_channels, activation, use_bias,
                         weight_initializer, bias_initializer, **kwargs)


class Conv1DTranspose(_Conv):
    def __init__(self, channels, kernel_size, strides=1, padding=0,
                 output_padding=0, dilation=1, groups=1, layout=None,
                 activation=None, use_bias=True, weight_initializer=None,
                 bias_initializer="zeros", in_channels=0, **kwargs):
        if isinstance(kernel_size, int):
            kernel_size = (kernel_size,)
        super().__init__(channels, kernel_size, strides, padding, dilation,
                         groups, layout, in_channels, activation, use_bias,
                         weight_initializer, bias_initializer,
                         op_name="Deconvolution", adj=output_padding, **kwargs)


class Conv2DTranspose(_Conv):
    def __init__(self, channels, kernel_size, strides=(1, 1), padding=(0, 0),
                 output_padding=(0, 0), dilation=(1, 1), groups=1,
                 layout=None, activation=None, use_bias=True,
                 weight_initializer=None, bias_initializer="zeros",
                 in_channels=0, **kwargs):
        if isinstance(kernel_size, int):
            kernel_size = (kernel_size,) * 2
        super().__init__(channels, kernel_size, strides, padding, dilation,
                         groups, layout, in_channels, activation, use_bias,
                         weight_initializer, bias_initializer,
                         op_name="Deconvolution", adj=output_padding, **kwargs)


class Conv3DTranspose(_Conv):
    def __init__(self, channels, kernel_size, strides=(1, 1, 1),
                 padding=(0, 0, 0), output_padding=(0, 0, 0), dilation=(1, 1, 1),
                 groups=1, layout=None, activation=None, use_bias=True,
                 weight_initializer=None, bias_initializer="zeros",
                 in_channels=0, **kwargs):
        if isinstance(kernel_size, int):
            kernel_size = (kernel_size,) * 3
        super().__init__(channels, kernel_size, strides, padding, dilation,
                         groups, layout, in_channels, activation, use_bias,
                         weight_initializer, bias_initializer,
                         op_name="Deconvolution", adj=output_padding, **kwargs)


class _Pooling(HybridBlock):
    def __init__(self, pool_size, strides, padding, ceil_mode, global_pool,
                 pool_type, layout, count_include_pad=None, **kwargs):
        super().__init__(**kwargs)
        layout = _resolve_layout(layout, len(pool_size))
        if strides is None:
            strides = pool_size
        if isinstance(strides, int):
            strides = (strides,) * len(pool_size)
        if isinstance(padding, int):
            padding = (padding,) * len(pool_size)
        self._kwargs = {
            "kernel": pool_size, "stride": strides, "pad": padding,
            "global_pool": global_pool, "pool_type": pool_type,
            "layout": layout,
            "pooling_convention": "full" if ceil_mode else "valid"}
        if count_include_pad is not None:
            self._kwargs["count_include_pad"] = count_include_pad

    def _alias(self):
        return "pool"

    def hybrid_forward(self, F, x):
        return F.Pooling(x, name="fwd", **self._kwargs)

    def __repr__(self):
        return "{name}(size={kernel}, stride={stride}, padding={pad}, " \
               "ceil_mode={ceil_mode})".format(
                   name=self.__class__.__name__,
                   ceil_mode=self._kwargs["pooling_convention"] == "full",
                   **self._kwargs)


class MaxPool1D(_Pooling):
    def __init__(self, pool_size=2, strides=None, padding=0, layout=None,
                 ceil_mode=False, **kwargs):
        super().__init__((pool_size,) if isinstance(pool_size, int) else pool_size,
                         strides, padding, ceil_mode, False, "max", layout, **kwargs)


class MaxPool2D(_Pooling):
    def __init__(self, pool_size=(2, 2), strides=None, padding=0, layout=None,
                 ceil_mode=False, **kwargs):
        if isinstance(pool_size, int):
            pool_size = (pool_size,) * 2
        super().__init__(pool_size, strides, padding, ceil_mode, False, "max",
                         layout, **kwargs)


class MaxPool3D(_Pooling):
    def __init__(self, pool_size=(2, 2, 2), strides=None, padding=0,
                 layout=None, ceil_mode=False, **kwargs):
        if isinstance(pool_size, int):
            pool_size = (pool_size,) * 3
        super().__init__(pool_size, strides, padding, ceil_mode, False, "max",
                         layout, **kwargs)


class AvgPool1D(_Pooling):
    def __init__(self, pool_size=2, strides=None, padding=0, layout=None,
                 ceil_mode=False, count_include_pad=True, **kwargs):
        super().__init__((pool_size,) if isinstance(pool_size, int) else pool_size,
                         strides, padding, ceil_mode, False, "avg", layout,
                         count_include_pad, **kwargs)


class AvgPool2D(_Pooling):
    def __init__(self, pool_size=(2, 2), strides=None, padding=0, layout=None,
                 ceil_mode=False, count_include_pad=True, **kwargs):
        if isinstance(pool_size, int):
            pool_size = (pool_size,) * 2
        super().__init__(pool_size, strides, padding, ceil_mode, False, "avg",
                         layout, count_include_pad, **kwargs)


class AvgPool3D(_Pooling):
    def __init__(self, pool_size=(2, 2, 2), strides=None, padding=0,
                 layout=None, ceil_mode=False, count_include_pad=True, **kwargs):
        if isinstance(pool_size, int):
            pool_size = (pool_size,) * 3
        super().__init__(pool_size, strides, padding, ceil_mode, False, "avg",
                         layout, count_include_pad, **kwargs)


class GlobalMaxPool1D(_Pooling):
    def __init__(self, layout=None, **kwargs):
        super().__init__((1,), None, 0, True, True, "max", layout, **kwargs)


class GlobalMaxPool2D(_Pooling):
    def __init__(self, layout=None, **kwargs):
        super().__init__((1, 1), None, 0, True, True, "max", layout, **kwargs)


class GlobalMaxPool3D(_Pooling):
    def __init__(self, layout=None, **kwargs):
        super().__init__((1, 1, 1), None, 0, True, True, "max", layout, **kwargs)


class GlobalAvgPool1D(_Pooling):
    def __init__(self, layout=None, **kwargs):
        super().__init__((1,), None, 0, True, True, "avg", layout, **kwargs)


class GlobalAvgPool2D(_Pooling):
    def __init__(self, layout=None, **kwargs):
        super().__init__((1, 1), None, 0, True, True, "avg", layout, **kwargs)


class GlobalAvgPool3D(_Pooling):
    def __init__(self, layout=None, **kwargs):
        super().__init__((1, 1, 1), None, 0, True, True, "avg", layout, **kwargs)


class ReflectionPad2D(HybridBlock):
    def __init__(self, padding=0, **kwargs):
        super().__init__(**kwargs)
        if isinstance(padding, int):
            padding = (0, 0, 0, 0, padding, padding, padding, padding)
        self._padding = padding

    def hybrid_forward(self, F, x):
        return F.Pad(x, mode="reflect", pad_width=self._padding)
