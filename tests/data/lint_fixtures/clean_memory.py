"""Sanctioned/fixed twins of bad_memory.py's plants: mxmem must stay quiet.

Every construct here is the repaired form of a bad_memory.py violation —
static donation, documented nodonate, a budget that covers its closure, a
reserve() on the admission path, and well-formed sanction tags.  The mem
pass must report zero findings on this file (tests/test_mxmem.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from mxnet_tpu.parallel.collectives import allgather


def donated_carry(step0, state):
    step = jax.jit(step0, donate_argnums=(0,))
    new_state = step(state)
    return new_state


def documented_nodonate(step0, state):
    step = jax.jit(step0)  # mxmem: nodonate(the caller's checkpoint hook re-reads state after every step)
    state = step(state)
    return state


# declared worst case: one full (64, 64) fp32 page, well under the cap
# mxmem: budget(hbm=1MB)
def budgeted_alloc():
    return jnp.zeros((64, 64), jnp.float32)


# mxmem: budget(hbm=1MB)
def budgeted_gather(x):
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("tp",))

    def body(v):
        return allgather(v, "tp")  # covered by the budget above

    fn = shard_map(body, mesh=mesh, in_specs=(P("tp"),), out_specs=P("tp"),
                   check_vma=False)
    return fn(x)


def sanctioned_gather(x):
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("tp",))

    def body(v):
        return allgather(v, "tp")  # mxmem: fullshape-ok(the gathered operand is one scalar row per shard)

    return shard_map(body, mesh=mesh, in_specs=(P("tp"),),
                     out_specs=P("tp"), check_vma=False)(x)


# mxflow: hot
def hot_with_reserve(pool, seq_id, n_blocks):
    if not pool.reserve(seq_id, n_blocks):
        return None
    return np.zeros((8, 8), "float32")  # covered: reserve() on this path


# mxflow: hot
def hot_sanctioned():
    return np.zeros((4, 4), "float32")  # mxmem: reserve-ok(signature-bounded probe buffer, independent of stream length)
