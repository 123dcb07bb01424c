"""A training cell's input: a small pool of distinct batches, made on the host
from ``--seed``, which the cell's entry cycles.

A traffic file (``traffic/<name>.json``) holds only parameters: ``entry``,
``batch``, ``pool`` (how many distinct batches), ``warmup_steps``,
``steps_per_call``, the optimizer's parameters (``lr``, ``momentum``),
``trace_seconds`` (how much of the window's end a traced run traces) and,
where the defaults do not fit, ``generator`` (the file under ``generators/``
that makes the batches; default ``images``) and ``compare`` (the file under
``comparisons/`` that decides ``correct``; default ``train_norms``).  Every
seed gives the same sizes: only the values and the weights differ.
"""
from __future__ import annotations

import importlib


def make_pool(config, traffic, seed, count=None):
    """``count`` (default the traffic's ``pool``) batches, each a tuple of
    host arrays, from the generator the traffic file names.  Batch i is the
    same for a given seed however many are made."""
    generator = importlib.import_module(
        "benchmark.generators." + traffic.get("generator", "images"))
    return generator.make_pool(config, traffic, seed,
                               traffic["pool"] if count is None else count)
