"""The FLOPs a model requires, from the configuration's shapes.

The family's own reference forward pass is walked once under
``jax.eval_shape``, over one sample of the cell's traffic (the family's
``example_input``, else one image), with an ``Ops`` that counts every product
the reference makes through it: a convolution or a dense layer adds its
multiply-accumulates (output elements x kernel area x input channels per
group), an ``einsum`` the product of the sizes of all its subscripts.  Nothing
is taken from a compiled program or a trace, so the count is the same whatever
implements the layers, and nothing that a compiler recomputes is counted.

Convention: one multiply-accumulate is 2 FLOPs, and a training step costs 3
times the forward pass (forward, the gradient of the input, the gradient of
the weights).  BatchNorm, activations, pooling, an embedding's lookup, the loss
and the optimizer are not counted: they are bandwidth, not FLOPs that an MXU
could do.
"""
from __future__ import annotations

import functools
import json
import math

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

    def einsum(self, spec, a, b):
        y = super().einsum(spec, a, b)
        sizes = {}
        for letters, operand in zip(spec.split("->")[0].split(","), (a, b)):
            sizes.update(zip(letters.strip(), operand.shape))
        self.macs += math.prod(sizes.values())
        return y


@functools.lru_cache(maxsize=None)
def _forward_macs(cell_json):
    config, traffic = json.loads(cell_json)
    family = common.family(config)
    shapes = family.param_shapes(config)
    tree = {k: jax.ShapeDtypeStruct(s, jnp.float32) for k, s in shapes.items()}
    ops = CountingOps()
    jax.eval_shape(
        lambda p, *xs: family.forward(config, ops, p, p, *xs, True)[0],
        tree, *common.example_input(config, traffic))
    return ops.macs


def forward_macs(cell):
    """Multiply-accumulates of one forward pass over one sample of ``cell``
    (a harness.Cell)."""
    return _forward_macs(json.dumps([cell.config, cell.traffic],
                                    sort_keys=True))


def train_flops_per_sample(cell):
    return TRAIN_PASSES * FLOPS_PER_MAC * forward_macs(cell)


if __name__ == "__main__":      # python3 -m benchmark.flops
    import os

    from benchmark import harness
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for entry in harness.load_json(root, "BENCHMARK.json")["workloads"]:
        cell = harness.Cell(entry["name"], root)
        print("%s: forward %.4f GMAC per sample, training %.3f GFLOP per "
              "sample" % (cell.name, forward_macs(cell) / 1e9,
                          train_flops_per_sample(cell) / 1e9))
