"""Activation-memory cost of backward mirroring — the reference's
example/memcost (docs/architecture/note_memory.md: measure training
memory under MXNET_BACKWARD_DO_MIRROR), reproduced with the compiler's
own numbers: XLA's CompiledMemoryStats for the full training step
(fwd+bwd) of the same hybridized net with and without
``hybridize(remat=True)``.

The remat build must (a) cut the step's temp (activation) memory ON TPU
and (b) produce the same gradients — memory is traded for recompute
FLOPs, not for correctness.  Gradient parity is asserted everywhere; the
memory ratio only on a TPU backend: XLA:CPU's memory stats do not
reflect the transform (this script measures ratio 1.000 on CPU, and a
pure-jax 24-layer toy even INVERTS — 1.0 MiB plain vs 12.5 MiB remat —
because the barriers that protect recompute from CSE pin buffers the
CPU scheduler would otherwise reuse), so CPU numbers say nothing about
HBM behavior.
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.gluon import nn


def make_net(depth, width, remat, seed=0):
    net = nn.HybridSequential()
    with net.name_scope():
        for _ in range(depth):
            net.add(nn.Dense(width, activation="relu"))
        net.add(nn.Dense(8))
    mx.random.seed(seed)  # init draws from the framework stream (r5)
    net.initialize(mx.init.Xavier(), force_reinit=True)
    # explicit remat=False, not an omitted flag: omission falls back to
    # the MXNET_BACKWARD_DO_MIRROR env knob (cached_op.py:98), which
    # would silently turn the baseline into a second remat build
    net.hybridize(remat=remat)
    return net


def step_memory_and_grads(net, x_np):
    """Lower grad(loss) of the CachedOp's traceable as ONE XLA module and
    read the compiler's memory stats; also run it for the gradients."""
    import jax

    x = nd.array(x_np)
    net(x)  # build the CachedOp (deferred shapes)
    co = net._cached_op
    fn = co._make_lowerable(training=True)
    params = {n: p.data()._data for n, p in net._cached_params.items()}
    pvals = tuple(params[n] for n in co._param_names)
    key = jax.random.PRNGKey(0)

    def loss_fn(*vals):
        out = fn(*vals)
        out0 = out[0] if isinstance(out, (list, tuple)) else out
        return (out0.astype("float32") ** 2).sum()

    grad_fn = jax.jit(jax.grad(loss_fn, argnums=tuple(range(len(pvals)))))
    compiled = grad_fn.lower(*pvals, x._data, key).compile()
    stats = compiled.memory_analysis()
    grads = compiled(*pvals, x._data, key)
    return stats, {n: np.asarray(g) for n, g in zip(co._param_names, grads)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--depth", type=int, default=24)
    ap.add_argument("--width", type=int, default=512)
    ap.add_argument("--batch", type=int, default=256)
    args = ap.parse_args()

    rng = np.random.RandomState(1)
    x = rng.uniform(-1, 1, (args.batch, args.width)).astype(np.float32)

    rows = []
    grads = {}
    for remat in (False, True):
        stats, g = step_memory_and_grads(
            make_net(args.depth, args.width, remat), x)
        rows.append((remat, stats.temp_size_in_bytes,
                     stats.argument_size_in_bytes))
        grads[remat] = g

    import jax
    platform = jax.devices()[0].platform
    print("%-18s %14s %14s" % ("config", "temp (MiB)", "args (MiB)"))
    for remat, temp, arg in rows:
        print("%-18s %14.2f %14.2f"
              % ("remat" if remat else "plain", temp / 2**20, arg / 2**20))
    ratio = rows[1][1] / max(rows[0][1], 1)
    print("temp-memory ratio remat/plain: %.3f (platform=%s)"
          % (ratio, platform))

    # prefixes differ between the two builds (gluon's global name
    # counter); parameter ORDER is structural, so compare positionally
    for (n0, g0), (n1, g1) in zip(grads[False].items(), grads[True].items()):
        np.testing.assert_allclose(g0, g1, rtol=1e-5, atol=1e-5,
                                   err_msg="%s vs %s" % (n0, n1))
    if platform == "tpu":
        assert ratio < 0.7, ("remat did not shed activation memory "
                             "(ratio %.3f)" % ratio)
    import json
    print(json.dumps({"metric": "remat_temp_memory_ratio", "value": ratio,
                      "unit": "x", "vs_baseline": ratio,
                      "platform": platform}))
    print("MEMCOST OK")


if __name__ == "__main__":
    main()
