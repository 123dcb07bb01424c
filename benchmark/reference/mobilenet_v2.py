"""MobileNetV2 (Sandler et al., "MobileNetV2: Inverted Residuals and Linear
Bottlenecks", arXiv:1801.04381, Table 2), as MXNet's model zoo builds it.

Departures from the paper, all MXNet's: the first bottleneck (t = 1) keeps
its 1x1 expansion convolution; the classifier is a 1x1 convolution without
bias on the pooled features.  No convolution carries a bias.

Configuration keys read here: ``multiplier``, ``classes``.  Parameter names
are the zoo's without the network's own prefix.
"""
from __future__ import annotations

from collections import OrderedDict

# Table 2: (expansion t, output channels c, repeats n, stride s)
TABLE_2 = [(1, 16, 1, 1), (6, 24, 2, 2), (6, 32, 3, 2), (6, 64, 4, 2),
           (6, 96, 3, 1), (6, 160, 3, 2), (6, 320, 1, 1)]


def _bottlenecks(config):
    """(index, in, out, t, stride) for every bottleneck, in forward order."""
    m = config["multiplier"]
    fan_in, index = int(32 * m), 0
    for t, c, n, s in TABLE_2:
        for j in range(n):
            yield index, fan_in, int(c * m), t, s if j == 0 else 1
            fan_in, index = int(c * m), index + 1


def _last_channels(config):
    m = config["multiplier"]
    return int(1280 * m) if m > 1.0 else 1280


def _conv_bn(shapes, prefix, k, out, inp, size):
    shapes["%sconv2d%d_weight" % (prefix, k)] = (out, inp, size, size)
    for leaf in ("gamma", "beta", "running_mean", "running_var"):
        shapes["%sbatchnorm%d_%s" % (prefix, k, leaf)] = (out,)


def param_shapes(config):
    shapes = OrderedDict()
    stem = int(32 * config["multiplier"])
    _conv_bn(shapes, "features_", 0, stem, 3, 3)
    last = stem
    for index, fan_in, out, t, _ in _bottlenecks(config):
        p = "features_linearbottleneck%d_" % index
        _conv_bn(shapes, p, 0, fan_in * t, fan_in, 1)
        _conv_bn(shapes, p, 1, fan_in * t, 1, 3)
        _conv_bn(shapes, p, 2, out, fan_in * t, 1)
        last = out
    _conv_bn(shapes, "features_", 1, _last_channels(config), last, 1)
    shapes["output_pred_weight"] = (config["classes"],
                                    _last_channels(config), 1, 1)
    return shapes


def forward(config, ops, params, aux, x, train):
    """Logits and the new BatchNorm statistics."""
    new_aux = {}

    def conv_bn(p, k, h, stride=1, pad=0, groups=1):
        h = ops.conv(h, params["%sconv2d%d_weight" % (p, k)], stride, pad,
                     groups)
        bn = "%sbatchnorm%d" % (p, k)
        h, stats = ops.batch_norm(
            h, params[bn + "_gamma"], params[bn + "_beta"],
            (aux[bn + "_running_mean"], aux[bn + "_running_var"]), train)
        new_aux[bn + "_running_mean"], new_aux[bn + "_running_var"] = stats
        return h

    h = ops.relu6(conv_bn("features_", 0, x, 2, 1))
    for index, fan_in, out, t, stride in _bottlenecks(config):
        p = "features_linearbottleneck%d_" % index
        y = ops.relu6(conv_bn(p, 0, h))
        y = ops.relu6(conv_bn(p, 1, y, stride, 1, groups=fan_in * t))
        y = conv_bn(p, 2, y)
        h = y + h if stride == 1 and fan_in == out else y
    h = ops.relu6(conv_bn("features_", 1, h))
    h = ops.global_avg_pool(h)[:, :, None, None]
    return ops.conv(h, params["output_pred_weight"])[:, :, 0, 0], new_aux
