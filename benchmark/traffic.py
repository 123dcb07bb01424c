"""The one generator of a training cell's input: a small pool of distinct
batches, made on the host from ``--seed``, which the cell's entry cycles.

A traffic file (``traffic/<name>.json``) holds only parameters: ``entry``,
``batch``, ``pool`` (how many distinct batches), ``warmup_steps``,
``steps_per_call``, the optimizer's ``lr`` and ``momentum``, and
``trace_seconds`` (how much of the window's end a traced run traces).  Every
seed gives the same sizes: only the pixels, the labels and the weights differ.
"""
from __future__ import annotations

import numpy as np


def make_pool(config, traffic, seed, count=None):
    """``count`` (default the traffic's ``pool``) batches of images in
    [-1, 1) and integer class labels held as float32, as MXNet's iterators
    give them.  Batch i is the same for a given seed however many are made."""
    size = config["image_size"]
    pool = []
    for i in range(traffic["pool"] if count is None else count):
        rng = np.random.default_rng([int(seed), i])
        x = rng.random((traffic["batch"], 3, size, size), dtype=np.float32)
        x *= np.float32(2)      # in place: a fresh array of this size costs
        x -= np.float32(1)      # more to allocate than to fill
        y = rng.integers(0, config["classes"], traffic["batch"])
        pool.append((x, y.astype(np.float32)))
    return pool
