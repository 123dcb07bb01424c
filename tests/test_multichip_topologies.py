"""dryrun_multichip at non-default topologies.

The driver validates the multi-chip path at n=8; these tests guard the
dp×tp factorization (tp=2 whenever n is even -> dp = n/2), the ring/
pipeline schedules, and the expert/checkpoint paths against axis-size
assumptions by exercising n=4 and n=16 virtual-CPU meshes in fresh
subprocesses (device count must be fixed before backend init).
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_dryrun(n, timeout):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=%d" % n)
    res = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(%d); "
         "print('DRYRUN_OK %d')" % (n, n)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=timeout)
    assert res.returncode == 0, res.stderr[-3000:]
    assert ("DRYRUN_OK %d" % n) in res.stdout


def test_dryrun_multichip_4_devices():
    _run_dryrun(4, timeout=900)


# ---------------------------------------------------------------------------
# ZeRO sharded-vs-replicated parity on the 8-virtual-device mesh
# ---------------------------------------------------------------------------

def _parity_fixture():
    import numpy as np
    import jax.numpy as jnp
    rng = np.random.RandomState(42)
    params = {"w": jnp.asarray(rng.randn(3, 5).astype(np.float32)),
              "b": jnp.asarray(rng.randn(5).astype(np.float32))}
    x = rng.randn(16, 3).astype(np.float32)
    y = rng.randn(16, 5).astype(np.float32)

    def loss_fn(p, batch):
        xb, yb = batch
        return jnp.mean((xb @ p["w"] + p["b"] - yb) ** 2)

    return params, (x, y), loss_fn


def _run_parity(optimizer_update, make_opt_state, steps, assert_fn):
    """Drive make_data_parallel_train_step sharded vs replicated over the
    SAME 8-device mesh and batches; both variants are built from the same
    loss, so the replicated-pinned gradients are identical and only the
    update placement differs."""
    import numpy as np
    import jax
    from mxnet_tpu.parallel import (make_mesh, make_data_parallel_train_step,
                                    init_shard_update_state, shard_batch)

    mesh = make_mesh()
    assert int(mesh.shape["dp"]) == 8, \
        "conftest must provide the 8-virtual-device mesh"
    params, batch, loss_fn = _parity_fixture()
    opt = make_opt_state(params)
    rep = make_data_parallel_train_step(loss_fn, optimizer_update, mesh,
                                        donate_params=False)
    shr = make_data_parallel_train_step(loss_fn, optimizer_update, mesh,
                                        donate_params=False,
                                        shard_update=True)
    b = shard_batch(mesh, batch)
    p_r, o_r = params, opt
    p_s, s_s = params, init_shard_update_state(mesh, params, opt)
    for _ in range(steps):
        p_r, o_r, loss_r = rep(p_r, o_r, b)
        p_s, s_s, loss_s = shr(p_s, s_s, b)
    for k in p_r:
        assert_fn(k, np.asarray(p_r[k]), np.asarray(p_s[k]))
    # the loss reduction is structurally different (global-batch mean vs
    # per-shard mean + pmean), so it gets allclose, never bitwise
    np.testing.assert_allclose(np.asarray(loss_r), np.asarray(loss_s),
                               rtol=1e-6)


def test_sharded_update_bitwise_parity_sgd():
    import numpy as np
    import jax

    def sgd(grads, state, p):
        return (jax.tree_util.tree_map(
            lambda w, g: w - 0.1 * g, p, grads), state)

    def zeros(p):
        return jax.tree_util.tree_map(lambda l: l[..., :0], p)  # stateless

    def must_equal(name, a, b):
        assert np.array_equal(a, b), \
            "%s not bitwise between replicated and sharded" % name

    _run_parity(sgd, zeros, steps=5, assert_fn=must_equal)


def test_sharded_update_bitwise_parity_sgd_momentum():
    import numpy as np
    import jax

    # MXNet's kernel form (optimizer.py SGD): lr folds into the momentum
    # buffer, the weight update is a bare add — one FMA candidate per
    # statement, which LLVM contracts identically in both modules
    def sgd_momentum(grads, state, p):
        new_m = jax.tree_util.tree_map(
            lambda m, g: 0.9 * m - 0.1 * g, state, grads)
        return (jax.tree_util.tree_map(
            lambda w, m: w + m, p, new_m), new_m)

    def zeros(p):
        import jax.numpy as jnp
        return jax.tree_util.tree_map(jnp.zeros_like, p)

    def must_equal(name, a, b):
        assert np.array_equal(a, b), \
            "%s not bitwise between replicated and sharded" % name

    _run_parity(sgd_momentum, zeros, steps=5, assert_fn=must_equal)


def test_sharded_update_allclose_parity_adam():
    """Adam's rsqrt/bias-correction chain is gated allclose per the
    acceptance criteria (elementwise, so the sharded slices see the same
    math, but the transcendental fusion order may differ per module)."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    def adam(grads, state, p):
        t = state["t"] + 1.0
        m = jax.tree_util.tree_map(
            lambda mm, g: 0.9 * mm + 0.1 * g, state["m"], grads)
        v = jax.tree_util.tree_map(
            lambda vv, g: 0.999 * vv + 0.001 * g * g, state["v"], grads)
        lr_t = 0.01 * jnp.sqrt(1 - 0.999 ** t) / (1 - 0.9 ** t)
        new_p = jax.tree_util.tree_map(
            lambda w, mm, vv: w - lr_t * mm / (jnp.sqrt(vv) + 1e-8),
            p, m, v)
        return new_p, {"m": m, "v": v, "t": t}

    def zeros(p):
        z = jax.tree_util.tree_map(jnp.zeros_like, p)
        return {"m": z,
                "v": jax.tree_util.tree_map(jnp.zeros_like, p),
                "t": jnp.zeros(())}

    def close(name, a, b):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7,
                                   err_msg=name)

    _run_parity(adam, zeros, steps=5, assert_fn=close)


@pytest.mark.skipif(os.environ.get("MXNET_TEST_FAST") == "1",
                    reason="16-device CPU dryrun is the slow variant")
def test_dryrun_multichip_16_devices():
    _run_dryrun(16, timeout=1500)
