#!/usr/bin/env python
"""Inference throughput benchmark (reference:
example/image-classification/benchmark_score.py)."""
from __future__ import annotations

import argparse
import logging
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np


def score(network, batch_size, image_shape=(3, 224, 224), dtype="float32",
          iters=20):
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.gluon.block import functional_call, param_values

    net = vision.get_model(network, classes=1000)
    net.initialize(mx.init.Xavier())
    net(nd.zeros((1,) + image_shape))
    jdtype = jnp.bfloat16 if dtype in ("float16", "bfloat16") else jnp.float32
    params = {n: (v.astype(jdtype) if jnp.issubdtype(v.dtype, jnp.floating)
                  else v)
              for n, v in param_values(net).items()}

    @jax.jit
    def forward(p, x):
        outs, _ = functional_call(net, p, x, training=False)
        return outs[0]

    x = jnp.asarray(np.random.uniform(-1, 1, (batch_size,) + image_shape)
                    .astype(np.float32)).astype(jdtype)
    forward(params, x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = forward(params, x)
    out.block_until_ready()
    dt = time.perf_counter() - t0
    return batch_size * iters / dt


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--networks", type=str,
                        default="resnet50_v1")
    parser.add_argument("--batch-sizes", type=str, default="1,32")
    parser.add_argument("--dtype", type=str, default="float32")
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO)
    for net_name in args.networks.split(","):
        for bs in (int(b) for b in args.batch_sizes.split(",")):
            speed = score(net_name, bs, dtype=args.dtype)
            logging.info("network: %s batch: %d dtype: %s images/sec: %.2f",
                         net_name, bs, args.dtype, speed)
