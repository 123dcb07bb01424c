"""Multi-process FUSED-step data-parallel training (worker).

The kvstore dist tests cover the eager per-key push/pull path; this worker
proves the compiled-step path — the one docs/MIGRATION.md steers multi-host
users to — across REAL processes: a 2-process global mesh, the whole
train step (fwd+bwd+cross-host grad psum+sgd) as ONE XLA module via
``make_data_parallel_train_step``, batch sharded one half per process.

Each rank then recomputes the identical trajectory single-process over the
full batch and asserts the distributed params match to float tolerance —
the distributed analog of test_module's bitwise multi-device check.

Launch:  python tools/launch.py -n 2 --launcher local \\
             python tests/dist/dist_fused_step.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import jax

import numpy as np


def main():
    import mxnet_tpu as mx
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.experimental import multihost_utils
    import jax.numpy as jnp
    from mxnet_tpu.parallel import make_data_parallel_train_step

    # rendezvous via the kvstore's jax.distributed bootstrap
    kv = mx.kv.create("dist_sync")
    rank, nworker = kv.rank, kv.num_workers
    assert nworker == 2, "run through tools/launch.py -n 2"

    devs = np.array(jax.devices())
    mesh = Mesh(devs, ("dp",))

    # identical fixed problem on every rank
    rng = np.random.RandomState(5)
    W0 = jnp.asarray(rng.normal(0, 0.1, (8, 4)).astype(np.float32))
    b0 = jnp.zeros((4,), jnp.float32)
    X = rng.normal(0, 1, (16, 8)).astype(np.float32)
    Y = rng.randint(0, 4, (16,)).astype(np.int32)
    lr = 0.1

    def loss_fn(params, batch):
        x, y = batch
        logits = x @ params["w"] + params["b"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    def sgd(grads, opt_state, params):
        new = {k: params[k] - lr * grads[k] for k in params}
        return new, opt_state

    step = make_data_parallel_train_step(loss_fn, sgd, mesh,
                                         donate_params=False)

    params = {"w": W0, "b": b0}
    half = 16 // nworker
    my_x = X[rank * half:(rank + 1) * half]
    my_y = Y[rank * half:(rank + 1) * half]
    opt_state = ()
    for _ in range(3):
        gx = multihost_utils.host_local_array_to_global_array(
            my_x, mesh, P("dp"))
        gy = multihost_utils.host_local_array_to_global_array(
            my_y, mesh, P("dp"))
        params, opt_state, loss = step(params, opt_state, (gx, gy))
    # params are replicated over the global mesh; pull the local copy
    dist_w = np.asarray(multihost_utils.global_array_to_host_local_array(
        params["w"], mesh, P()))
    dist_b = np.asarray(multihost_utils.global_array_to_host_local_array(
        params["b"], mesh, P()))

    # single-process reference trajectory over the FULL batch
    ref = {"w": W0, "b": b0}
    grad_fn = jax.jit(jax.grad(loss_fn))
    for _ in range(3):
        g = grad_fn(ref, (jnp.asarray(X), jnp.asarray(Y)))
        ref = {k: ref[k] - lr * g[k] for k in ref}

    np.testing.assert_allclose(dist_w, np.asarray(ref["w"]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(dist_b, np.asarray(ref["b"]),
                               rtol=1e-5, atol=1e-6)
    kv.barrier()
    print("dist_fused_step rank %d/%d: OK" % (rank, nworker), flush=True)


if __name__ == "__main__":
    main()
