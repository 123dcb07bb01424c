"""ResNet v1 (He et al., "Deep Residual Learning for Image Recognition",
arXiv:1512.03385, Table 1), as MXNet's model zoo builds it.

Departures from the paper, all MXNet's: the stride of a bottleneck sits in
its first 1x1 convolution; the first and the third convolution of a
bottleneck carry a bias (which the BatchNorm that follows cancels, so its
gradient is zero); the shortcut of a stage's first block is a 1x1
convolution with BatchNorm wherever the width changes.

Configuration keys read here: ``block`` (``bottle_neck`` or
``basic_block``), ``layers``, ``channels``, ``classes``.  Parameter names
are the zoo's without the network's own prefix.
"""
from __future__ import annotations

from collections import OrderedDict


def _blocks(config):
    """(stage, counter of the block's first conv, in, out, stride,
    downsample) for every residual block, in forward order."""
    per_block = 3 if config["block"] == "bottle_neck" else 2
    channels = config["channels"]
    for i, count in enumerate(config["layers"]):
        width, fan_in = channels[i + 1], channels[i]
        n = 0
        for j in range(count):
            down = j == 0 and width != fan_in
            yield (i + 1, n, fan_in if j == 0 else width, width,
                   (1 if i == 0 else 2) if j == 0 else 1, down)
            n += per_block + (1 if down else 0)


def _bn(shapes, prefix, width):
    for leaf in ("gamma", "beta", "running_mean", "running_var"):
        shapes["%s_%s" % (prefix, leaf)] = (width,)


def param_shapes(config):
    shapes = OrderedDict()
    channels, bottle = config["channels"], config["block"] == "bottle_neck"
    shapes["conv2d0_weight"] = (channels[0], 3, 7, 7)
    _bn(shapes, "batchnorm0", channels[0])
    for stage, n, fan_in, width, _, down in _blocks(config):
        p = "stage%d_" % stage
        if bottle:
            mid = width // 4
            convs = [(mid, fan_in, 1, True), (mid, mid, 3, False),
                     (width, mid, 1, True)]
        else:
            convs = [(width, fan_in, 3, False), (width, width, 3, False)]
        if down:
            convs.append((width, fan_in, 1, False))
        for k, (out, inp, size, bias) in enumerate(convs):
            shapes["%sconv2d%d_weight" % (p, n + k)] = (out, inp, size, size)
            if bias:
                shapes["%sconv2d%d_bias" % (p, n + k)] = (out,)
            _bn(shapes, "%sbatchnorm%d" % (p, n + k), out)
    shapes["dense0_weight"] = (config["classes"], channels[-1])
    shapes["dense0_bias"] = (config["classes"],)
    return shapes


def forward(config, ops, params, aux, x, train):
    """Logits and the new BatchNorm statistics."""
    new_aux = {}

    def bn(prefix, h):
        h, stats = ops.batch_norm(
            h, params[prefix + "_gamma"], params[prefix + "_beta"],
            (aux[prefix + "_running_mean"], aux[prefix + "_running_var"]),
            train)
        new_aux[prefix + "_running_mean"], new_aux[prefix + "_running_var"] \
            = stats
        return h

    def conv_bn(p, k, h, stride, pad):
        name = "%sconv2d%d" % (p, k)
        h = ops.conv(h, params[name + "_weight"], stride, pad,
                     bias=params.get(name + "_bias"))
        return bn("%sbatchnorm%d" % (p, k), h)

    bottle = config["block"] == "bottle_neck"
    h = ops.relu(conv_bn("", 0, x, 2, 3))
    h = ops.max_pool(h, 3, 2, 1)
    for stage, n, _, _, stride, down in _blocks(config):
        p = "stage%d_" % stage
        if bottle:
            out = ops.relu(conv_bn(p, n, h, stride, 0))
            out = ops.relu(conv_bn(p, n + 1, out, 1, 1))
            out = conv_bn(p, n + 2, out, 1, 0)
            last = n + 3
        else:
            out = ops.relu(conv_bn(p, n, h, stride, 1))
            out = conv_bn(p, n + 1, out, 1, 1)
            last = n + 2
        if down:
            h = conv_bn(p, last, h, stride, 0)
        h = ops.relu(h + out)
    h = ops.global_avg_pool(h)
    return ops.dense(h, params["dense0_weight"], params["dense0_bias"]), \
        new_aux
