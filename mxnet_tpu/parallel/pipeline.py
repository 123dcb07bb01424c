"""Pipeline parallelism: layers sharded across a 'pp' mesh axis.

The reference's only model-splitting mechanism is manual per-device
placement (`group2ctx` + _CrossDeviceCopy, src/executor/graph_executor.cc:908,
docs/faq/model_parallel_lstm.md) — a static assignment with no microbatch
overlap.  This module is the TPU-native replacement: a GPipe-style SPMD
pipeline expressed as ONE program on every device.

Design (the scaling-book / praxis collective-pipeline recipe):
  * stage parameters carry a leading stage axis sharded over 'pp' — inside
    ``shard_map`` each device holds exactly its stage's weights;
  * the schedule runs M + S - 1 ticks (M microbatches, S stages); at each
    tick every device applies its stage to the activation it holds, then a
    non-cyclic ``ppermute`` shifts activations one stage forward — XLA
    overlaps the permute with the next tick's compute on ICI;
  * stage 0 injects microbatch t at tick t; the last stage's results are
    written into an output buffer and ``psum``'d so every shard returns the
    full output (the gradient of psum is the identity, so the backward
    pipeline flows stage-to-stage in reverse over the same ring).
  * the tick loop is a ``lax.scan`` — reverse-differentiable, so
    ``jax.grad`` through the pipeline yields the backward pipeline with no
    extra code.

Constraint (inherent to SPMD pipelining): every stage maps activations of
one fixed shape to the same shape; embed/readout live outside the pipeline.
"""
from __future__ import annotations

import functools


def pipeline_apply(stage_fn, stage_params, x_microbatches, axis_name="pp"):
    """Run inside shard_map: apply an S-stage pipeline to M microbatches.

    stage_fn(params_for_one_stage, h) -> h  (same shape in/out).
    stage_params: pytree whose leaves have a leading LOCAL stage axis of 1
        (the 'pp'-sharded global stage axis); squeezed before stage_fn.
    x_microbatches: (M, ...) replicated microbatch stack.
    Returns (M, ...) outputs (replicated via psum).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from .collectives import allreduce, axis_size, ppermute

    S = axis_size(axis_name)
    stage_idx = lax.axis_index(axis_name)
    M = x_microbatches.shape[0]
    p_local = jax.tree_util.tree_map(lambda l: l[0], stage_params)

    out0 = jnp.zeros_like(x_microbatches)
    state0 = jnp.zeros_like(x_microbatches[0])
    # shift activations one stage forward; stage 0 receives zeros (its
    # input comes from the microbatch stream instead)
    perm = [(j, j + 1) for j in range(S - 1)]

    def tick(carry, t):
        state, out = carry
        x_t = lax.dynamic_index_in_dim(
            x_microbatches, jnp.clip(t, 0, M - 1), 0, keepdims=False)
        inp = jnp.where(stage_idx == 0, x_t, state)
        y = stage_fn(p_local, inp)
        widx = jnp.clip(t - (S - 1), 0, M - 1)
        write = (stage_idx == S - 1) & (t >= S - 1)
        out = jnp.where(write,
                        lax.dynamic_update_index_in_dim(out, y, widx, 0),
                        out)
        state_next = ppermute(y, axis_name, perm)  # mxshard: reshard-ok(pipeline tick: shift activations one stage forward, overlapped with compute)
        return (state_next, out), None

    (_, out), _ = lax.scan(tick, (state0, out0),
                           jnp.arange(M + S - 1, dtype=jnp.int32))
    # only the last stage wrote; replicate to all shards
    return allreduce(out, axis_name)  # mxshard: reduce-ok(replicate the last stage's outputs; psum gradient is identity, carrying the backward pipeline)


def make_pipeline_step(stage_fn, mesh, n_microbatches, axis_name="pp",
                       loss_fn=None):
    """Build a jitted pipelined forward (or forward+loss+grad) function.

    Returns ``run(stage_params, x)`` where stage_params' leaves have leading
    global stage axis (sharded over ``axis_name``) and x is (B, ...);
    the batch is split into ``n_microbatches`` equal microbatches.

    With ``loss_fn(y_microbatches, labels) -> scalar`` given, returns
    ``run(stage_params, x, labels) -> (loss, grads)`` — the full backward
    pipeline in the same compiled module.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    def p_specs(params):
        return jax.tree_util.tree_map(
            lambda l: P(axis_name, *([None] * (l.ndim - 1))), params)

    S = int(mesh.shape[axis_name])

    def to_micro(x):
        B = x.shape[0]
        if B % n_microbatches:
            raise ValueError(
                "pipeline: batch of %d is not divisible into %d "
                "microbatches" % (B, n_microbatches))
        mb = B // n_microbatches
        return x.reshape((n_microbatches, mb) + x.shape[1:])

    def forward(params, x_micro):
        leaves = jax.tree_util.tree_leaves(params)
        if leaves and leaves[0].shape[0] % S:
            raise ValueError(
                "pipeline: leading stage axis of %d is not divisible by "
                "the mesh %r axis extent %d"
                % (leaves[0].shape[0], axis_name, S))
        fn = shard_map(
            functools.partial(pipeline_apply, stage_fn, axis_name=axis_name),
            mesh=mesh,
            in_specs=(p_specs(params), P()),
            out_specs=P(), check_vma=False)
        return fn(params, x_micro)

    if loss_fn is None:
        @jax.jit
        def run(params, x):
            y = forward(params, to_micro(x))
            return y.reshape((-1,) + y.shape[2:])
        return run

    @jax.jit
    def run(params, x, labels):
        def lossf(p):
            y = forward(p, to_micro(x))
            return loss_fn(y.reshape((-1,) + y.shape[2:]), labels)
        return jax.value_and_grad(lossf)(params)
    return run
