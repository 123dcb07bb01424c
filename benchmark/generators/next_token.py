"""Generator ``next_token``: batches for a causal language model trained on
the next token.  The traffic file gives ``batch`` and ``seq_len`` (one
document a sequence); the configuration gives ``vocab_size`` (the slice of the
vocabulary held)."""
from __future__ import annotations

import numpy as np


def make_pool(config, traffic, seed, count):
    """``count`` batches ``(tokens, targets, weight)``: ``tokens``
    ``[batch, L]`` int32, ids uniform over ``[0, vocab_size)``; ``targets``
    ``[batch, L]`` int32, the next token (the last position's is 0 and does
    not count); ``weight`` ``[batch, L]`` float32, 1 but 0 at the last
    position."""
    batch, length = traffic["batch"], traffic["seq_len"]
    pool = []
    for i in range(count):
        rng = np.random.default_rng([int(seed), i])
        tokens = rng.integers(0, config["vocab_size"], (batch, length),
                              dtype=np.int32)
        targets = np.concatenate(
            [tokens[:, 1:], np.zeros((batch, 1), np.int32)], axis=1)
        weight = np.ones((batch, length), np.float32)
        weight[:, -1] = 0.0
        pool.append((tokens, targets, weight))
    return pool
