"""Engine-semantics shims.

Reference: src/engine/ — the async dependency scheduler (ThreadedEngine with
versioned vars, threaded_engine.cc:51-142) plus the python ``mx.engine.bulk``
bulking context (python/mxnet/engine.py).

TPU-native: XLA's async dispatch provides the engine's semantics — every op
call returns before the device finishes, ordering is by data dependence, and
reads synchronize (``NDArray.wait_to_read`` = ``block_until_ready``).  Bulking
(batching many small ops into one engine segment, threaded_engine.h:411) is
superseded by jit: the ``bulk`` context is kept as API but XLA fusion already
bulk-compiles any jitted region.  ``set_bulk_size`` is accepted and recorded
for compatibility.

Every eager op is a dispatch of its own: a chain of small ops (an unrolled
LSTM cell, say) pays one dispatch per op, where a hybridized block pays one
for the whole chain.  What a dispatch costs on the chip's host is PERF.md's
to say; no number from a CPU is kept here.
So for small-op chains the
bulking question is real, and the framework's answer is ``hybridize()``:
the whole region traces into ONE cached XLA module, which is strictly
stronger than the reference's engine bulking (segments still launch one
kernel per op; XLA fuses).  Making ``bulk()`` itself collect eager ops into
a deferred trace would duplicate CachedOp for at most the same win, so it
stays a no-op; eager mode remains the flexible/debug path, hybridize the
fast one (same split the reference documents for Gluon)."""
from __future__ import annotations

import contextlib
import threading


class _BulkState(threading.local):
    """Per-thread bulking config.

    The reference's bulk size is engine-global, but this runtime is
    multi-threaded (serving batcher workers share the process with user
    threads): a process-global here would let one worker's ``bulk()``
    scope stomp another's.  Thread-local keeps ``bulk()`` a correct
    dynamic scope per thread of control."""

    def __init__(self):
        self.size = 15


_bulk = _BulkState()


def set_bulk_size(size):
    prev = _bulk.size
    _bulk.size = size
    return prev


def bulk_size():
    """The calling thread's current bulk size."""
    return _bulk.size


@contextlib.contextmanager
def bulk(size):
    prev = set_bulk_size(size)
    try:
        yield
    finally:
        set_bulk_size(prev)
