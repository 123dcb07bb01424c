"""Planted device-memory violations for the mxmem pass.

Every violation below is pinned to an exact (rule, line) pair in
tests/test_mxmem.py, and ``drive()`` executes the planted allocations and
the sharded gather so the same test cross-checks the static site inventory
against the runtime byte-accountant deltas (GROUND_TRUTH) — the
static/dynamic twin contract.  Keep line numbers stable or update the
test pins.
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from mxnet_tpu import memory_accounting
from mxnet_tpu.parallel.collectives import allgather


def fixture_mesh():
    return Mesh(np.array(jax.devices()[:1]).reshape(1), ("tp",))


def runtime_donation(step, donate):
    # MEM001 below: the donation branch resolves at dispatch time
    return jax.jit(step, donate_argnums=(0,) if donate() else ())


def undonated_carry(state):
    step = jax.jit(lambda s: s + 1)  # MEM001: carried state, no donation
    state = step(state)
    return state


def donate_then_read(state):
    step = jax.jit(lambda s: s * 2.0, donate_argnums=(0,))
    out = step(state)
    return out + state  # MEM002: `state` was donated to the call above


# the planted budget: 4KB declared, 16KB allocated (MEM003 on the tag line)
# mxmem: budget(hbm=4KB)
def budget_blow():
    x = jnp.zeros((64, 64), jnp.float32)  # 16384B > the 4KB budget above
    memory_accounting.record_alloc(int(x.size) * x.dtype.itemsize)
    memory_accounting.record_free(int(x.size) * x.dtype.itemsize)
    return x


# mxflow: hot
def hot_alloc(n_tokens):
    buf = np.zeros((8, 8), "float32")  # MEM004: hot path, no reserve()
    memory_accounting.record_alloc(buf.nbytes)
    memory_accounting.record_free(buf.nbytes)
    return buf


def sharded_gather(x):
    mesh = fixture_mesh()

    def body(v):
        return allgather(v, "tp")  # MEM005: full-shape temp, no budget

    fn = shard_map(body, mesh=mesh, in_specs=(P("tp"),), out_specs=P("tp"),
                   check_vma=False)
    return fn(x)


def documented():
    # mxmem: fullshape-ok()
    x = jnp.ones((4,))  # MEM006 above: sanction with an empty reason
    # mxmem: reserve-ok(nothing to sanction on the next line)
    return x * 2.0  # MEM006 above: stale tag, no alloc site on that line


#: what one drive() must leave in the accountant's active region — and the
#: static site inventory must count the very same sites.  The two
#: instrumented allocations mirror the engine/KV-cache hook contract
#: (record_alloc/record_free beside the real allocation); the gather's
#: output temp is recorded by the collective wrapper itself.
GROUND_TRUTH = {
    "sites": {"compile": 3, "gather": 1, "alloc": 4},
    "temps": 1,                   # the allgather output in sharded_gather
    "temp_bytes": 16,             # (4,) float32 over a 1-device "tp" axis
    "allocs": 2,                  # budget_blow + hot_alloc, instrumented
    "frees": 2,
    "alloc_bytes": 16384 + 256,
    "peak_bytes": 16384,          # budget_blow's page, freed before the next
}


def drive():
    """Execute the planted allocations and the sharded gather once (the
    dynamic half; the donation plants are static-only)."""
    budget_blow()
    hot_alloc(8)
    sharded_gather(jnp.ones((4,), jnp.float32))
