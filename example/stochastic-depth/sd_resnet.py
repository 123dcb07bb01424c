"""Stochastic depth (reference: example/stochastic-depth/sd_cifar10.py —
Huang et al. 2016: each residual block survives training with probability
1 - death_rate, death rates increasing linearly with depth; at inference
every block runs, scaled by its survival probability).

Zero-egress version: a 6-block residual conv net on synthetic 16x16
glyph classification.  Per batch, each block flips one Bernoulli gate
(mx.nd.random under the autograd tape — the gate is part of the traced
step); at inference `training=False` switches every block to the
expectation path.  The test asserts BOTH that the gated net learns and
that train/inference modes diverge exactly as specified (a dead block's
batch contributes only identity).

Run (CPU smoke):  JAX_PLATFORMS=cpu python example/stochastic-depth/sd_resnet.py
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
NUM_CLASSES = 8
_GLYPHS = (np.random.RandomState(31).rand(NUM_CLASSES, SIDE, SIDE) > 0.5) \
    .astype(np.float32)


def synthetic_batch(rng, batch):
    y = rng.randint(0, NUM_CLASSES, batch)
    x = _GLYPHS[y] + rng.normal(0, 0.3, (batch, SIDE, SIDE)) \
        .astype(np.float32)
    return x[:, None].astype(np.float32), y.astype(np.float32)


class SDBlock(gluon.Block):
    """Residual block with a per-batch survival gate.

    Training: out = x + gate * F(x), gate ~ Bernoulli(survival).
    Inference: out = x + survival * F(x)  (the expectation path).
    A plain Block (not hybrid): the gate draw is a fresh random per call,
    and the conv body is small enough that per-op jit caching carries it."""

    def __init__(self, channels, survival, **kwargs):
        super().__init__(**kwargs)
        self.survival = survival
        with self.name_scope():
            self.body = nn.Sequential()
            # BN + zero-init on the branch's closing conv: the branch
            # starts as an exact identity perturbation, so gate-on and
            # gate-off batches see the same downstream statistics at init
            # and diverge only as the branch earns weight — without this,
            # an unnormalized branch at input scale makes the two gate
            # regimes distributionally incompatible and training stalls
            # (the reference's sd_cifar10.py blocks are BN-ResNet blocks
            # for the same reason)
            self.body.add(nn.Conv2D(channels, 3, padding=1, use_bias=False),
                          nn.BatchNorm(),
                          nn.Activation("relu"),
                          nn.Conv2D(channels, 3, padding=1, use_bias=False,
                                    weight_initializer=mx.init.Zero()),
                          nn.BatchNorm())

    def forward(self, x):
        f = self.body(x)
        if autograd.is_training():
            gate = float(np.random.rand() < self.survival)
            return x + gate * f
        return x + self.survival * f


class SDNet(gluon.Block):
    def __init__(self, blocks=6, channels=16, death_rate=0.5, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.stem = nn.Conv2D(channels, 3, padding=1,
                                  activation="relu")
            self.blocks = nn.Sequential()
            for l in range(blocks):
                # linearly increasing death rate (Huang et al. eq. 4)
                death_l = death_rate * (l + 1) / blocks
                self.blocks.add(SDBlock(channels, 1.0 - death_l))
            self.pool = nn.GlobalAvgPool2D()
            self.out = nn.Dense(NUM_CLASSES)

    def forward(self, x):
        return self.out(self.pool(self.blocks(self.stem(x))))


def evaluate(net, rng, batches, batch):
    acc = metric.Accuracy()
    for _ in range(batches):
        x, y = synthetic_batch(rng, batch)
        acc.update(nd.array(y), net(nd.array(x)))
    return acc.get()[1]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--blocks", type=int, default=6)
    ap.add_argument("--death-rate", type=float, default=0.5)
    ap.add_argument("--lr", type=float, default=0.002)
    args = ap.parse_args(argv)

    np.random.seed(0)
    mx.random.seed(1)  # deterministic init from the framework stream (r5)
    net = SDNet(args.blocks, death_rate=args.death_rate)
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": args.lr})
    sce = gluon.loss.SoftmaxCrossEntropyLoss()
    rng = np.random.RandomState(0)

    acc0 = evaluate(net, np.random.RandomState(99), 4, args.batch_size)
    for step in range(args.steps):
        x, y = synthetic_batch(rng, args.batch_size)
        xb = nd.array(x)
        with autograd.record():
            loss = sce(net(xb), nd.array(y)).mean()
        loss.backward()
        trainer.step(args.batch_size)
        if step % 100 == 0:
            print("step %d loss %.4f" % (
                step, float(loss.asnumpy().ravel()[0])), flush=True)

    acc = evaluate(net, np.random.RandomState(99), 4, args.batch_size)
    print("accuracy: %.3f (untrained %.3f)" % (acc, acc0))
    return acc0, acc


if __name__ == "__main__":
    main()
