"""The FLOPs a model requires, from the configuration's shapes.

The family's own reference forward pass is walked once under
``jax.eval_shape`` with an ``Ops`` that counts: every convolution and dense
layer adds its multiply-accumulates (output elements x kernel area x input
channels per group).  Nothing is taken from a compiled program or a trace,
so the count is the same whatever implements the layers, and nothing that a
compiler recomputes is counted.

Convention: one multiply-accumulate is 2 FLOPs, and a training step costs 3
times the forward pass (forward, the gradient of the input, the gradient of
the weights).  BatchNorm, activations, pooling, the loss and the optimizer
are not counted: they are bandwidth, not FLOPs that an MXU could do.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from benchmark.reference import common

FLOPS_PER_MAC = 2
TRAIN_PASSES = 3


class CountingOps(common.Ops):
    def __init__(self):
        super().__init__(jnp.float32, None)
        self.macs = 0

    def conv(self, x, w, stride=1, pad=0, groups=1, bias=None):
        y = super().conv(x, w, stride, pad, groups, bias)
        self.macs += y.size * w.shape[1] * w.shape[2] * w.shape[3]
        return y

    def dense(self, x, w, bias=None):
        y = super().dense(x, w, bias)
        self.macs += y.size * w.shape[1]
        return y


@functools.lru_cache(maxsize=None)
def _forward_macs(config_json):
    config = json.loads(config_json)
    family = common.family(config)
    shapes = family.param_shapes(config)
    tree = {k: jax.ShapeDtypeStruct(s, jnp.float32) for k, s in shapes.items()}
    size = config["image_size"]
    x = jax.ShapeDtypeStruct((1, 3, size, size), jnp.float32)
    ops = CountingOps()
    jax.eval_shape(lambda p, x: family.forward(config, ops, p, p, x, True)[0],
                   tree, x)
    return ops.macs


def forward_macs(config):
    """Multiply-accumulates of one forward pass over one image."""
    return _forward_macs(json.dumps(config, sort_keys=True))


def train_flops_per_image(config):
    return TRAIN_PASSES * FLOPS_PER_MAC * forward_macs(config)


if __name__ == "__main__":      # python3 -m benchmark.flops
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    for name in sorted(os.listdir(os.path.join(here, "configs"))):
        with open(os.path.join(here, "configs", name)) as f:
            cfg = json.load(f)
        print("%s: forward %.4f GMAC per image, training %.3f GFLOP per image"
              % (name, forward_macs(cfg) / 1e9,
                 train_flops_per_image(cfg) / 1e9))
