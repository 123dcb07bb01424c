"""Global PRNG state.

Reference: src/resource.cc:160-174 global seeding + per-device kRandom/
kParallelRandom resources; python/mxnet/random.py ``mx.random.seed``.

TPU-native: one framework-global counter-based key; each random-op invocation
receives a fresh split (threaded by the dispatch layer as attrs['_rng_key']),
so eager random ops are reproducible under ``mx.random.seed(n)`` yet
jit-friendly (key is an ordinary array input, shapes static).
"""
from __future__ import annotations

import threading

_state = threading.local()
_DEFAULT_SEED = 0


def _get():
    if not hasattr(_state, "key"):
        import jax
        _state.key = jax.random.PRNGKey(_DEFAULT_SEED)
    return _state.key


def seed(seed_state, ctx="all"):
    """Seed the framework-global generator (python/mxnet/random.py seed)."""
    import jax
    _state.key = jax.random.PRNGKey(int(seed_state))


def next_key():
    import jax
    if getattr(_state, "override", None) is not None:
        key, sub = jax.random.split(_state.override)
        _state.override = key
        return sub
    key = _get()
    key, sub = jax.random.split(key)
    _state.key = key
    return sub


class key_override:
    """Scope that sources keys by splitting from ``base`` instead of the global
    state.  Used by CachedOp so that, under tracing, keys derive from a
    function *argument* (fresh randomness per compiled call) rather than being
    baked into the XLA module as constants."""

    def __init__(self, base):
        self._base = base
        self._prev = None

    def __enter__(self):
        self._prev = getattr(_state, "override", None)
        _state.override = self._base
        return self

    def __exit__(self, *a):
        _state.override = self._prev


# `mx.random.*` sampling front-ends live in ndarray/random.py; re-exported here
def __getattr__(name):
    from .ndarray import random as _ndrandom
    return getattr(_ndrandom, name)


def derived_numpy_rng():
    """A numpy RandomState seeded from a fresh split of the framework key.

    The reference's initializers draw through mx random ops, so
    ``mx.random.seed(n)`` makes INITIALIZATION reproducible too
    (python/mxnet/initializer.py over src/resource.cc seeding).  Here the
    initializers fill with numpy for convenience; sourcing their
    RandomState from the framework stream restores that contract — before
    round 5 they used numpy's GLOBAL entropy-seeded state, so two runs
    with identical mx.random.seed produced different networks."""
    import jax
    import numpy as _np
    sub = next_key()
    data = jax.random.key_data(sub)
    # seed with EVERY key word (RandomState accepts array seeds): folding
    # to one 31-bit word would give ~2^-32 per-pair collision odds between
    # independently-initialized parameters — silent perfectly-correlated
    # weight tensors on a collision
    words = _np.asarray(data).ravel().astype(_np.uint32)
    return _np.random.RandomState(words)
