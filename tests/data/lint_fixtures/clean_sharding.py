"""A properly sanctioned SPMD region: the spd pass must stay silent.

Mirror of bad_sharding.py with every planted violation repaired the
sanctioned way: the gather carries a justification tag, the psum is
covered by the region budget, the shard_map owner validates divisibility
eagerly, and every axis named anywhere is declared by the mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from mxnet_tpu.parallel.collectives import allgather, allreduce


def make_mesh():
    devs = np.array(jax.devices()[:2])
    return Mesh(devs, ("tp",))


def partition_specs():
    return (P(), P(None, "tp"))


# mxshard: budget(psum=1)
def block(x, w):
    full = allgather(w, "tp", axis=1)  # mxshard: gather-ok(fixture: documented weight regather for the replicated matmul)
    y = x @ full
    return allreduce(y, "tp")  # covered by the region budget(psum=1)


def run_block(x, w):
    mesh = make_mesh()
    n = int(mesh.shape["tp"])
    if w.shape[1] % n:
        raise ValueError(
            "block: weight columns of %d are not divisible by the mesh "
            "'tp' axis extent %d" % (w.shape[1], n))
    fn = shard_map(block, mesh=mesh, in_specs=partition_specs(),
                   out_specs=P(), check_vma=False)
    return fn(x, w)


def drive():
    d = 4
    x = jnp.ones((2, d), jnp.float32)
    w = jnp.ones((d, d), jnp.float32)
    return run_block(x, w)
