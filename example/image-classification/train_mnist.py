#!/usr/bin/env python
"""Train LeNet/MLP on MNIST via the Module API (reference:
example/image-classification/train_mnist.py)."""
from __future__ import annotations

import argparse
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import sym


def get_mlp():
    data = sym.Variable("data")
    data = sym.Flatten(data)
    fc1 = sym.FullyConnected(data, name="fc1", num_hidden=128)
    act1 = sym.Activation(fc1, name="relu1", act_type="relu")
    fc2 = sym.FullyConnected(act1, name="fc2", num_hidden=64)
    act2 = sym.Activation(fc2, name="relu2", act_type="relu")
    fc3 = sym.FullyConnected(act2, name="fc3", num_hidden=10)
    return sym.SoftmaxOutput(fc3, name="softmax")


def get_lenet():
    data = sym.Variable("data")
    conv1 = sym.Convolution(data, name="conv1", kernel=(5, 5), num_filter=20)
    tanh1 = sym.Activation(conv1, act_type="tanh")
    pool1 = sym.Pooling(tanh1, pool_type="max", kernel=(2, 2), stride=(2, 2))
    conv2 = sym.Convolution(pool1, name="conv2", kernel=(5, 5), num_filter=50)
    tanh2 = sym.Activation(conv2, act_type="tanh")
    pool2 = sym.Pooling(tanh2, pool_type="max", kernel=(2, 2), stride=(2, 2))
    flatten = sym.Flatten(pool2)
    fc1 = sym.FullyConnected(flatten, name="fc1", num_hidden=500)
    tanh3 = sym.Activation(fc1, act_type="tanh")
    fc2 = sym.FullyConnected(tanh3, name="fc2", num_hidden=10)
    return sym.SoftmaxOutput(fc2, name="softmax")


def get_mnist_iters(batch_size, data_dir):
    """Read staged MNIST idx files, or fall back to synthetic digits."""
    img = os.path.join(data_dir, "train-images-idx3-ubyte.gz")
    lbl = os.path.join(data_dir, "train-labels-idx1-ubyte.gz")
    if os.path.exists(img) or os.path.exists(img[:-3]):
        train = mx.io.MNISTIter(image=img, label=lbl, batch_size=batch_size,
                                shuffle=True)
        return train, None
    logging.warning("MNIST files not staged under %s; using synthetic data",
                    data_dir)
    rng = np.random.RandomState(0)
    X = rng.uniform(0, 1, (2048, 1, 28, 28)).astype(np.float32)
    Y = rng.randint(0, 10, 2048).astype(np.float32)
    return mx.io.NDArrayIter(X, Y, batch_size=batch_size, shuffle=True), None


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--network", default="mlp", choices=["mlp", "lenet"])
    parser.add_argument("--batch-size", type=int, default=64)
    parser.add_argument("--num-epochs", type=int, default=3)
    parser.add_argument("--lr", type=float, default=0.05)
    parser.add_argument("--kv-store", default="local")
    parser.add_argument("--data-dir", default=os.path.join(
        os.path.expanduser("~"), ".mxnet", "datasets", "mnist"))
    parser.add_argument("--model-prefix", default=None)
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO)

    net = get_mlp() if args.network == "mlp" else get_lenet()
    train, val = get_mnist_iters(args.batch_size, args.data_dir)
    mod = mx.mod.Module(net)
    cb = [mx.callback.Speedometer(args.batch_size, 50)]
    if args.model_prefix:
        epoch_cb = mx.callback.do_checkpoint(args.model_prefix)
    else:
        epoch_cb = None
    mod.fit(train, eval_data=val, num_epoch=args.num_epochs,
            kvstore=args.kv_store, optimizer="sgd",
            optimizer_params={"learning_rate": args.lr},
            initializer=mx.init.Xavier(),
            batch_end_callback=cb, epoch_end_callback=epoch_cb)
    train.reset()
    print("final train accuracy:", mod.score(train, "acc"))


if __name__ == "__main__":
    main()
