"""Generator ``block_diffusion``: batches for a language model trained by
block diffusion.  The traffic file gives ``batch``, ``seq_len`` (``L`` clean
tokens, one document a sequence) and ``t_min``; the configuration gives
``vocab_size`` (the slice of the vocabulary held; its last id is the mask id)
and ``block_length``."""
from __future__ import annotations

import numpy as np


def make_pool(config, traffic, seed, count):
    """``count`` batches ``(tokens, targets, weight)``: ``tokens``
    ``[batch, 2L]`` int32, the noised copy ``xt`` of each sequence followed by
    the clean ``x0``; ``targets`` ``[batch, L]`` int32, ``x0``; ``weight``
    ``[batch, L]`` float32, ``masked / t_b``.  Clean ids are uniform over
    ``[0, vocab_size - 1)``; each block of ``block_length`` positions draws
    ``t_b`` uniform on ``[t_min, 1]`` and each of its positions is replaced in
    ``xt`` by the mask id ``vocab_size - 1`` with probability ``t_b``."""
    batch, length = traffic["batch"], traffic["seq_len"]
    block, mask_id = config["block_length"], config["vocab_size"] - 1
    pool = []
    for i in range(count):
        rng = np.random.default_rng([int(seed), i])
        clean = rng.integers(0, mask_id, (batch, length), dtype=np.int32)
        t = rng.uniform(traffic["t_min"], 1.0, (batch, length // block))
        t = np.repeat(t, block, axis=1)
        masked = rng.random((batch, length)) < t
        noised = np.where(masked, np.int32(mask_id), clean)
        pool.append((np.concatenate([noised, clean], axis=1), clean,
                     (masked / t).astype(np.float32)))
    return pool
