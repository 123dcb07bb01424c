"""Planted SPMD sharding violations for the mxshard spd pass.

Every violation below is pinned to an exact (rule, line) pair in
tests/test_mxshard.py, and ``drive()`` executes the planted collectives so
the same test cross-checks the static site counts against the runtime
collective-counter deltas (GROUND_TRUTH) — the static/dynamic twin
contract.  Keep line numbers stable or update the test pins.
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from mxnet_tpu.parallel.collectives import allgather, allreduce, ppermute


def bad_mesh():
    devs = np.array(jax.devices()[:2]).reshape(2, 1)
    return Mesh(devs, ("tp", "zz"))  # SPD003: declared axis "zz" never used


def partition_specs():
    return (P(), P(None, "tp"))


def output_specs():
    return P(None, "xx")  # SPD003: axis "xx" not declared by any mesh


# mxshard: budget(psum=1)
def block(x, w):
    full = allgather(w, "tp", axis=1)  # SPD001: gather feeds the matmul
    y = x @ full
    y = allreduce(y, "tp")  # covered by the region budget(psum=1)
    y = allreduce(y, "tp")  # SPD002: second psum breaches the budget
    return y


def run_block(x, w):
    mesh = bad_mesh()
    fn = shard_map(block, mesh=mesh, in_specs=partition_specs(),  # SPD004
                   out_specs=P(), check_vma=False)
    return fn(x, w)


# mxshard: bitwise
def scan_reshard(x):
    mesh = bad_mesh()

    def shifted(v):
        def body(i, c):
            return ppermute(c, "tp", [(0, 1), (1, 0)])  # SPD006: per-step
        out = jax.lax.fori_loop(0, 1, body, v)
        return allreduce(out, "tp")  # SPD005: psum on a bitwise path

    fn = shard_map(shifted, mesh=mesh, in_specs=(P(),), out_specs=P(),
                   check_vma=False)
    return fn(x)


def documented():
    # mxshard: gather-ok()
    x = jnp.ones((4,))  # SPD007 above: sanction with an empty reason
    # mxshard: reshard-ok(nothing to sanction on the next line)
    return x * 2.0  # SPD007 above: stale tag, no collective site


#: runtime collective-counter deltas one drive() must produce — and the
#: spd static site inventory must count the very same sites
#: (fori_loop traces its body once, so the ppermute registers once).
GROUND_TRUTH = {"all_gather": 1, "psum": 3, "ppermute": 1}


def drive():
    """Execute every planted collective once (the dynamic half)."""
    d = 4
    x = jnp.ones((2, d), jnp.float32)
    w = jnp.ones((d, d), jnp.float32)
    run_block(x, w)
    scan_reshard(jnp.ones((d,), jnp.float32))
