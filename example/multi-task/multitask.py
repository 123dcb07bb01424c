"""Multi-task learning: one shared trunk, two supervised heads trained on
a joint loss (reference: example/multi-task/example_multi_task.py — LeNet
trunk on MNIST with a digit-class head and a parity head, each scored by
its own accuracy metric).

Zero-egress version: 16x16 synthetic glyph images (fixed random binary
prototypes per class, pixel noise).  Task A = which of 10 glyph classes;
task B = whether the glyph was rendered inverted (binary).  The two
labels are independent by construction, so solving both through one trunk
is genuine multi-task sharing, not label leakage.

Run (CPU smoke):  JAX_PLATFORMS=cpu python example/multi-task/multitask.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd, gluon, metric
from mxnet_tpu.gluon import nn

SIDE = 16
NUM_CLASSES = 10
_GLYPHS = (np.random.RandomState(21).rand(NUM_CLASSES, SIDE, SIDE) > 0.5) \
    .astype(np.float32)


def synthetic_batch(rng, batch):
    cls = rng.randint(0, NUM_CLASSES, batch)
    inv = rng.randint(0, 2, batch)
    x = _GLYPHS[cls].copy()
    x[inv == 1] = 1.0 - x[inv == 1]
    x += rng.normal(0, 0.25, x.shape).astype(np.float32)
    return (x.reshape(batch, 1, SIDE, SIDE).astype(np.float32),
            cls.astype(np.float32), inv.astype(np.float32))


class MultiTaskNet(gluon.HybridBlock):
    """Conv trunk shared by a class head and a parity head (the
    reference's fc trunk with two SoftmaxOutputs)."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.trunk = nn.HybridSequential()
            self.trunk.add(nn.Conv2D(16, 3, padding=1, activation="relu"),
                           nn.MaxPool2D(2),
                           nn.Conv2D(32, 3, padding=1, activation="relu"),
                           nn.MaxPool2D(2),
                           nn.Flatten(),
                           nn.Dense(64, activation="relu"))
            self.head_cls = nn.Dense(NUM_CLASSES)
            self.head_inv = nn.Dense(2)

    def hybrid_forward(self, F, x):
        h = self.trunk(x)
        return self.head_cls(h), self.head_inv(h)


def evaluate(net, rng, batches, batch):
    acc_cls, acc_inv = metric.Accuracy(), metric.Accuracy()
    for _ in range(batches):
        x, cls, inv = synthetic_batch(rng, batch)
        lc, li = net(nd.array(x))
        acc_cls.update(nd.array(cls), lc)
        acc_inv.update(nd.array(inv), li)
    return acc_cls.get()[1], acc_inv.get()[1]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--lr", type=float, default=0.002)
    ap.add_argument("--task-weight", type=float, default=1.0,
                    help="weight on the parity head's loss")
    args = ap.parse_args(argv)

    np.random.seed(0)
    net = MultiTaskNet()
    net.initialize(mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": args.lr})
    sce = gluon.loss.SoftmaxCrossEntropyLoss()
    rng = np.random.RandomState(0)

    a0_cls, a0_inv = evaluate(net, np.random.RandomState(99), 4,
                              args.batch_size)
    for step in range(args.steps):
        x, cls, inv = synthetic_batch(rng, args.batch_size)
        xb = nd.array(x)
        with autograd.record():
            lc, li = net(xb)
            loss = (sce(lc, nd.array(cls)).mean() +
                    args.task_weight * sce(li, nd.array(inv)).mean())
        loss.backward()
        trainer.step(args.batch_size)
        if step % 100 == 0:
            print("step %d joint loss %.4f" % (
                step, float(loss.asnumpy().ravel()[0])), flush=True)

    a_cls, a_inv = evaluate(net, np.random.RandomState(99), 4,
                            args.batch_size)
    print("class acc: %.3f (untrained %.3f), parity acc: %.3f "
          "(untrained %.3f)" % (a_cls, a0_cls, a_inv, a0_inv))
    return (a0_cls, a_cls), (a0_inv, a_inv)


if __name__ == "__main__":
    main()
