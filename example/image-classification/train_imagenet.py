#!/usr/bin/env python
"""Train an ImageNet model (reference: example/image-classification/
train_imagenet.py — the north-star configuration:
``train_imagenet.py --kv-store dist_tpu_sync`` trains ResNet-50 end-to-end on
a TPU pod).

Two execution paths:
  * default: gluon hybridized loop with a kvstore-backed Trainer (API parity
    with the reference's Module fit).
  * --fused-step 1: the TPU-performance path — the whole train step
    (fwd+bwd+allreduce+SGD) compiles to ONE XLA module over the device mesh
    (parallel/data_parallel.py); gradients psum over ICI inside the graph.
"""
from __future__ import annotations

import argparse
import logging
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import common


def main():
    parser = common.add_fit_args(argparse.ArgumentParser())
    parser.add_argument("--data-train", type=str, default=None,
                        help="path to ImageNet train.rec (synthetic if absent)")
    parser.add_argument("--image-shape", type=str, default="3,224,224")
    parser.add_argument("--fused-step", type=int, default=1,
                        help="compile fwd+bwd+update as one XLA module")
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO)

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision

    image_shape = tuple(int(x) for x in args.image_shape.split(","))
    net = vision.get_model(args.network, classes=args.num_classes)

    if args.data_train and os.path.exists(args.data_train):
        train_iter = mx.io.ImageRecordIter(
            path_imgrec=args.data_train, data_shape=image_shape,
            batch_size=args.batch_size, shuffle=True, rand_crop=True,
            rand_mirror=True)
    else:
        logging.warning("no --data-train staged; using synthetic data")
        train_iter = common.get_synthetic_iter(args, image_shape)

    if args.fused_step:
        fit_fused(args, net, train_iter, image_shape)
    else:
        common.fit_gluon(args, net, train_iter)


def fit_fused(args, net, train_iter, image_shape):
    """One-XLA-module training step over the mesh (kvstore collapses into an
    in-graph psum, SURVEY §3.4 TPU mapping)."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.block import functional_call, param_values
    from mxnet_tpu.parallel import make_mesh, shard_batch

    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    net.initialize(mx.init.Xavier())
    net(nd.zeros((1,) + image_shape))
    params = param_values(net)
    aux_names = {n for n, p in net.collect_params().items()
                 if p.grad_req == "null"}
    train_names = sorted(n for n in params if n not in aux_names)

    mesh = make_mesh()  # 1-D dp mesh over every visible device
    n_dev = int(np.prod(mesh.devices.shape))
    logging.info("mesh: %s devices, kv-store=%s (in-graph allreduce)",
                 n_dev, args.kv_store)

    def loss_fn(tp, aux, x, y):
        p = dict(aux)
        p.update({n: v.astype(dtype) for n, v in tp.items()})
        outs, new_aux = functional_call(net, p, x.astype(dtype), training=True)
        logits = outs[0].astype(jnp.float32)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1)), new_aux

    lr, mom, wd = args.lr, args.mom, args.wd

    @jax.jit
    def step(tp, m, aux, x, y):
        (loss, new_aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            tp, aux, x, y)
        new_m = {n: mom * m[n] + grads[n] + wd * tp[n] for n in tp}
        new_tp = {n: tp[n] - lr * new_m[n] for n in tp}
        aux2 = dict(aux)
        aux2.update(new_aux)
        return new_tp, new_m, aux2, loss

    tp = {n: params[n] for n in train_names}
    m = {n: jnp.zeros_like(params[n]) for n in train_names}
    aux = {n: params[n] for n in aux_names}
    if n_dev > 1:
        # replicate params/optimizer state over the mesh (batch stays sharded)
        from mxnet_tpu.parallel import replicated_spec
        repl = replicated_spec(mesh)
        put = lambda t: {k: jax.device_put(v, repl) for k, v in t.items()}
        tp, m, aux = put(tp), put(m), put(aux)

    for epoch in range(args.num_epochs):
        tic = time.time()
        nsamples = 0
        for i, batch in enumerate(train_iter):
            x = batch.data[0]._data
            y = batch.label[0]._data.astype(jnp.int32)
            if n_dev > 1:
                x, y = shard_batch(mesh, (x, y))
            tp, m, aux, loss = step(tp, m, aux, x, y)
            nsamples += args.batch_size
            if (i + 1) % args.disp_batches == 0:
                jax.block_until_ready(loss)
                logging.info("Epoch[%d] Batch [%d] Speed: %.2f samples/sec "
                             "loss=%.4f", epoch, i + 1,
                             nsamples / (time.time() - tic), float(loss))
        train_iter.reset()
        logging.info("Epoch[%d] done in %.1fs", epoch, time.time() - tic)


if __name__ == "__main__":
    main()
