"""Generator ``tokens``: batches for a sequence model trained on marked
positions.  The traffic file gives ``batch``, ``seq_len`` and ``mask_share``
(the share of positions whose target counts in the loss); the configuration
gives ``vocab``."""
from __future__ import annotations

import numpy as np


def make_pool(config, traffic, seed, count):
    """``count`` batches ``(tokens, targets, mask)``: token ids and target
    ids ``[batch, seq_len]`` int32, uniform over the vocabulary, and a
    float32 mask that marks each position with probability ``mask_share``
    and at least one position of every row."""
    shape = (traffic["batch"], traffic["seq_len"])
    pool = []
    for i in range(count):
        rng = np.random.default_rng([int(seed), i])
        tokens = rng.integers(0, config["vocab"], shape, dtype=np.int32)
        targets = rng.integers(0, config["vocab"], shape, dtype=np.int32)
        mask = rng.random(shape) < traffic["mask_share"]
        mask[np.arange(shape[0]), rng.integers(0, shape[1], shape[0])] = True
        pool.append((tokens, targets, mask.astype(np.float32)))
    return pool
