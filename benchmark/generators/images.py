"""Generator ``images``: batches of images and class labels, the input of a
convolutional classifier.  The one a traffic file gets that names no other."""
from __future__ import annotations

import numpy as np


def make_pool(config, traffic, seed, count):
    """``count`` batches ``(x, y)``: images in [-1, 1) and integer class
    labels held as float32, as MXNet's iterators give them."""
    size = config["image_size"]
    pool = []
    for i in range(count):
        rng = np.random.default_rng([int(seed), i])
        x = rng.random((traffic["batch"], 3, size, size), dtype=np.float32)
        x *= np.float32(2)      # in place: a fresh array of this size costs
        x -= np.float32(1)      # more to allocate than to fill
        y = rng.integers(0, config["classes"], traffic["batch"])
        pool.append((x, y.astype(np.float32)))
    return pool
