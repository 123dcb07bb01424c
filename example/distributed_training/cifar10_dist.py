#!/usr/bin/env python
"""Distributed data-parallel CIFAR-10 training (reference:
example/distributed_training/cifar10_dist.py).

Each worker trains on its shard of the data; gradients synchronize through
the dist_sync kvstore (in-graph cross-host allreduce over the jax.distributed
mesh).  Simulate N workers on the CPU of one host with:

    JAX_PLATFORMS=cpu python tools/launch.py -n 2 --launcher local \
        python example/distributed_training/cifar10_dist.py --num-epochs 2

Runs on synthetic CIFAR-shaped data when the dataset is not staged under
$MXNET_HOME/datasets/cifar10 (this environment has no network egress).
"""
from __future__ import annotations

import argparse
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np


def load_cifar(batch_size, rank, num_workers, seed=0):
    """Per-worker shard of CIFAR-10 (synthetic stand-in when not staged)."""
    import mxnet_tpu as mx
    root = os.path.join(os.environ.get("MXNET_HOME",
                                       os.path.expanduser("~/.mxnet")),
                        "datasets", "cifar10")
    if os.path.isdir(root) and os.listdir(root):
        from mxnet_tpu.gluon.data.vision import CIFAR10
        train = CIFAR10(root=root, train=True)
        imgs, labels = zip(*((np.asarray(im.asnumpy()), int(l))
                             for im, l in train))
        x = np.stack(imgs).transpose(0, 3, 1, 2).astype(np.float32) / 255.0
        y = np.array(labels, dtype=np.float32)
        shard = slice(rank * len(x) // num_workers,
                      (rank + 1) * len(x) // num_workers)
        return mx.io.NDArrayIter(x[shard], y[shard], batch_size=batch_size,
                                 shuffle=True)
    logging.warning("CIFAR-10 not staged under %s; using synthetic data", root)
    rng = np.random.RandomState(seed)
    n = 512
    centers = rng.randn(10, 3, 1, 1).astype(np.float32) * 2
    y = rng.randint(0, 10, n)
    x = (rng.randn(n, 3, 32, 32).astype(np.float32) * 0.5
         + centers[y])
    # each worker sees a disjoint shard (reference SplitSampler)
    shard = slice(rank * n // num_workers, (rank + 1) * n // num_workers)
    return mx.io.NDArrayIter(x[shard], y[shard].astype(np.float32),
                             batch_size=batch_size, shuffle=True)


def build_net(classes=10):
    from mxnet_tpu import sym
    data = sym.Variable("data")
    net = sym.Convolution(data, name="conv1", kernel=(3, 3), num_filter=16,
                          pad=(1, 1))
    net = sym.Activation(net, act_type="relu", name="relu1")
    net = sym.Pooling(net, kernel=(2, 2), stride=(2, 2), pool_type="max",
                      name="pool1")
    net = sym.Convolution(net, name="conv2", kernel=(3, 3), num_filter=32,
                          pad=(1, 1))
    net = sym.Activation(net, act_type="relu", name="relu2")
    net = sym.Pooling(net, kernel=(2, 2), stride=(2, 2), pool_type="max",
                      name="pool2")
    net = sym.Flatten(net, name="flat")
    net = sym.FullyConnected(net, name="fc1", num_hidden=128)
    net = sym.Activation(net, act_type="relu", name="relu3")
    net = sym.FullyConnected(net, name="fc2", num_hidden=classes)
    return sym.SoftmaxOutput(net, name="softmax")


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--num-epochs", type=int, default=2)
    parser.add_argument("--lr", type=float, default=0.05)
    parser.add_argument("--kv-store", default="dist_sync")
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO)

    import mxnet_tpu as mx

    kv = mx.kv.create(args.kv_store)
    logging.info("worker %d/%d", kv.rank, kv.num_workers)
    train = load_cifar(args.batch_size, kv.rank, kv.num_workers)

    mod = mx.mod.Module(build_net())
    metric = mx.metric.create("acc")
    mod.fit(train, eval_metric=metric, kvstore=kv,
            num_epoch=args.num_epochs, optimizer="sgd",
            optimizer_params={"learning_rate": args.lr, "momentum": 0.9},
            initializer=mx.init.Xavier())
    print("worker %d final accuracy %.4f" % (kv.rank, metric.get()[1]))


if __name__ == "__main__":
    main()
