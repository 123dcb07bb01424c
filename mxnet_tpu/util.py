"""Misc utilities (reference: python/mxnet/util.py, libinfo.py) plus the
robustness primitives every recoverable boundary shares: :func:`retry`
(bounded attempts, exponential backoff, jitter) and :func:`write_atomic`
(tmp + fsync + ``os.replace`` crash-consistent file writes).  See
docs/ROBUSTNESS.md for the policy table of which sites use which."""
from __future__ import annotations

import functools
import os
import random as _random
import time as _time


def is_np_array():
    return False


def makedirs(d):
    os.makedirs(d, exist_ok=True)


def getenv(name, default=None):
    return os.environ.get(name, default)


def get_gpu_count():
    from .context import num_tpus
    return num_tpus()


def get_gpu_memory(dev_id=0):
    """(bytes in use, bytes limit) of the ``dev_id``-th accelerator."""
    from .base import MXNetError
    from .context import tpu
    dev = tpu(dev_id).jax_device()
    stats = dev.memory_stats()
    if stats is None:
        raise MXNetError("%s reports no memory statistics" % (dev,))
    return stats["bytes_in_use"], stats["bytes_limit"]


def bytes_by_device(arrays):
    """{device id: bytes held there} over the shards of ``arrays``
    (NDArrays or jax arrays): where memory is, not where it was meant."""
    out = {}
    for arr in arrays:
        for shard in getattr(arr, "_data", arr).addressable_shards:
            out[shard.device.id] = out.get(shard.device.id, 0) \
                + shard.data.nbytes
    return out


def compile_cache_dir():
    """Directory of JAX's persistent compilation cache for this process.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set in code, so the cache can be placed from outside.
    Otherwise the cache goes to ``.jax_cache`` beside the package: a fixed
    path, because the path is part of what the cache is keyed on and a
    directory that moves never hits."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------------------
# retry: the one backoff policy for every recoverable site
# ---------------------------------------------------------------------------

# instance RNG (not the global random module): jitter draws must not perturb
# seeded test streams, and the lint RNG-discipline pass bans global draws
_JITTER_RNG = _random.Random(0x5EED)


def retry(attempts=3, backoff=0.01, jitter=0.5, retryable=None, on_retry=None):
    """Decorator: re-run the wrapped callable on retryable failures.

    ``attempts`` total tries; sleep ``backoff * 2**i`` (exponential) with up
    to ``jitter`` fractional randomization between tries; ``retryable`` is
    an exception class/tuple (default: :class:`faults.TransientFault` — the
    injected-transient class; opt real exception types in explicitly).
    ``on_retry(exc, attempt)`` is called before each re-try (stats hooks).

    :class:`faults.SimulatedCrash` is a ``BaseException`` and is never
    retried — after a crash there is nobody left to run the next attempt.
    The last failure re-raises unchanged once attempts are exhausted.
    """
    if attempts < 1:
        raise ValueError("retry needs attempts >= 1, got %r" % attempts)

    def decorate(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            kinds = retryable
            if kinds is None:
                from .faults import TransientFault
                kinds = TransientFault
            for attempt in range(attempts):
                try:
                    return fn(*args, **kwargs)
                except kinds as exc:
                    if attempt == attempts - 1:
                        raise
                    if on_retry is not None:
                        on_retry(exc, attempt)
                    delay = backoff * (2 ** attempt)
                    if jitter:
                        delay *= 1.0 + jitter * _JITTER_RNG.random()
                    if delay > 0:
                        _time.sleep(delay)
        return wrapped
    return decorate


# ---------------------------------------------------------------------------
# atomic file writes: no caller may leave a torn checkpoint artifact
# ---------------------------------------------------------------------------

_ATOMIC_CHUNK = 4 << 20


def write_atomic(path, data):
    """All-or-nothing whole-file write: tmp + fsync + ``os.replace``.

    ``data`` is bytes (or str, utf-8 encoded).  The payload lands in a
    sibling tmp file first (same directory, so the final rename never
    crosses a filesystem), is fsynced, and only then atomically replaces
    ``path`` — a crash at ANY point leaves either the old complete file or
    the new complete file, never a torn one.  Writes are chunked and pass
    ``faults.fault_point`` between chunks (sites ``checkpoint.write`` /
    ``checkpoint.replace`` / ``checkpoint.replaced``) so the crash sweeps
    can kill at every byte-level stage; a simulated crash leaves the tmp
    file behind exactly as ``kill -9`` would (restore must tolerate strays).
    """
    import threading
    from . import faults
    if isinstance(data, str):
        data = data.encode("utf-8")
    path = os.fspath(path)
    # pid + thread id: two threads racing on one path must not interleave
    # writes into a shared tmp inode (the torn file this function exists
    # to rule out); last os.replace wins with a complete payload either way
    tmp = "%s.tmp-%d-%d" % (path, os.getpid(), threading.get_ident())
    f = open(tmp, "wb")
    try:
        total = len(data)
        written = 0
        while True:
            chunk = data[written:written + _ATOMIC_CHUNK]
            if chunk:
                f.write(chunk)
                written += len(chunk)
            faults.fault_point("checkpoint.write", path=path, fileobj=f,
                               written=written, total=total)
            if written >= total:
                break
        f.flush()
        os.fsync(f.fileno())
    except BaseException as exc:
        f.close()
        if not isinstance(exc, faults.SimulatedCrash):
            # an ordinary failure cleans up; a simulated crash leaves the
            # torn tmp on disk (a real SIGKILL would)
            try:
                os.remove(tmp)
            except OSError:
                pass
        raise
    f.close()
    faults.fault_point("checkpoint.replace", path=path)
    os.replace(tmp, path)
    # fsync the parent directory too: the rename IS the commit, and without
    # this a power loss can undo it even though the tmp payload was synced
    try:
        dirfd = os.open(os.path.dirname(path) or ".", os.O_RDONLY)
        try:
            os.fsync(dirfd)
        finally:
            os.close(dirfd)
    except OSError:
        pass   # platform/filesystem without directory fsync support
    faults.fault_point("checkpoint.replaced", path=path)


def sha256_file(path, chunk=1 << 20):
    """Hex content hash of a file (checkpoint manifest integrity checks)."""
    import hashlib
    h = hashlib.sha256()
    with open(path, "rb") as f:
        while True:
            block = f.read(chunk)
            if not block:
                break
            h.update(block)
    return h.hexdigest()
