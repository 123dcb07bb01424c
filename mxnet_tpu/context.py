"""Device context.

Reference: ``include/mxnet/base.h:135-139`` defines Context with device types
kCPU/kGPU/kCPUPinned/kCPUShared; ``python/mxnet/context.py`` exposes
``mx.cpu()``/``mx.gpu()`` and a thread-local current-context stack.

TPU-native redesign: a Context names a JAX device.  ``mx.tpu(i)`` is the
first-class accelerator; ``mx.gpu(i)`` is kept as a compatibility alias that
resolves to the i-th accelerator so reference scripts run unchanged.  There is
no pinned/shared distinction — host staging is managed by XLA transfers and
DataLoader workers ship numpy through shared memory at the Python level.

The default context is the first local device of JAX's default backend: the
chip where there is one, the host CPU under ``JAX_PLATFORMS=cpu``.  It is the
same on every thread; ``with ctx:`` overrides it for the enclosing thread only.
"""
from __future__ import annotations

import functools
import threading

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus", "num_tpus"]


class Context:
    """A device context.  devtype in {'cpu', 'tpu'}; 'gpu' aliases 'tpu'."""

    devtype2str = {1: "cpu", 2: "tpu", 3: "cpu_pinned", 5: "cpu_shared"}
    devstr2type = {"cpu": 1, "tpu": 2, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5}
    # per-thread `with ctx:` override; unset means the process default
    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        # copy-construction from another Context is allowed (reference API)
        if isinstance(device_type, Context):
            device_id = device_type.device_id
            device_type = device_type.device_type
        self.device_typeid = Context.devstr2type[device_type]
        self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return Context.devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_typeid == other.device_typeid
                and self.device_id == other.device_id)

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __repr__ = __str__

    def __enter__(self):
        self._old_ctx = getattr(Context._default_ctx, "value", None)
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx

    # --- JAX resolution -------------------------------------------------
    def jax_device(self):
        """Resolve to a concrete jax.Device.

        Only ADDRESSABLE devices are eligible: under multi-process
        jax.distributed, jax.devices() includes other workers' devices and
        placing an array there raises (each process owns its local shard —
        the reference's one-Context-per-worker model, kvstore_dist.h:50).

        ``cpu(i)`` always means the host, also where the chip is JAX's
        default backend (check_consistency depends on this).  ``tpu(i)``
        names exactly the i-th local accelerator and raises when there is
        none: a missing chip is an error, never a CPU device or another
        chip."""
        import jax
        if self.device_type != "tpu":
            devs = jax.local_devices(backend="cpu")
            return devs[min(self.device_id, len(devs) - 1)]
        accel = [d for d in jax.local_devices() if d.platform != "cpu"]
        if not 0 <= self.device_id < len(accel):
            raise MXNetError(
                "%s: this process has %d local accelerator device(s) "
                "(JAX default backend: %s)"
                % (self, len(accel), jax.default_backend()))
        return accel[self.device_id]


@functools.lru_cache(maxsize=None)
def _default_device_type():
    """Device type of JAX's default backend (fixed once backends exist)."""
    import jax
    return "cpu" if jax.default_backend() == "cpu" else "tpu"


def cpu(device_id=0):
    return Context("cpu", device_id)


def gpu(device_id=0):
    """Compatibility alias: resolves to the i-th accelerator (TPU) device."""
    return Context("tpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def current_context():
    ctx = getattr(Context._default_ctx, "value", None)
    return ctx if ctx is not None else Context(_default_device_type(), 0)


def num_gpus():
    return num_tpus()


def num_tpus():
    import jax
    return len([d for d in jax.local_devices() if d.platform != "cpu"])
