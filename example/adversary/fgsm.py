"""Fast Gradient Sign Method adversarial examples (reference:
example/adversary/adversary_generation.ipynb — Goodfellow et al. 2014:
perturb an input by epsilon * sign(dLoss/dInput) and watch a trained
classifier's accuracy collapse while the perturbation stays invisible).

Zero-egress version: train a small conv net on synthetic glyph
classification, then attack it.  The interesting machinery is gradients
WITH RESPECT TO THE INPUT — ``x.attach_grad()`` + ``autograd.record`` +
``backward`` on data rather than parameters, the flow the reference
notebook drives through ``mark_variables`` on the data blob.  Asserts the
attack works (accuracy drops far below clean accuracy at small epsilon)
and that the same-magnitude RANDOM-sign perturbation does not — i.e. the
drop comes from the gradient direction, not the noise level.

Run (CPU smoke):  JAX_PLATFORMS=cpu python example/adversary/fgsm.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd, gluon
from mxnet_tpu.gluon import nn

SIDE, NUM_CLASSES = 16, 6
_GLYPHS = (np.random.RandomState(11).rand(NUM_CLASSES, SIDE, SIDE) > 0.5) \
    .astype(np.float32)


def synthetic_batch(rng, batch):
    y = rng.randint(0, NUM_CLASSES, batch)
    x = _GLYPHS[y] + rng.normal(0, 0.2, (batch, SIDE, SIDE)).astype(np.float32)
    return x[:, None].astype(np.float32), y.astype(np.float32)


def build_net():
    net = nn.Sequential()
    net.add(nn.Conv2D(12, 3, padding=1, activation="relu"),
            nn.MaxPool2D(2),
            nn.Flatten(),
            nn.Dense(32, activation="relu"),
            nn.Dense(NUM_CLASSES))
    return net


def accuracy(net, x, y):
    pred = net(nd.array(x)).asnumpy().argmax(axis=1)
    return float((pred == y).mean())


def fgsm_perturb(net, loss_fn, x, y, eps, temperature=5.0):
    """epsilon * sign(dL/dx) — gradients w.r.t. the INPUT.

    The attack loss softens the logits by ``temperature`` before the
    cross-entropy: a net trained to saturation pushes softmax(logits) so
    close to one-hot that dL/dx underflows toward zero (the sign becomes
    float noise and FGSM stops biting — the round-4 red-test failure
    mode).  Dividing the logits by T>1 keeps the softmax un-saturated so
    the gradient DIRECTION is well-conditioned; the perturbation is still
    exactly eps * sign of a cross-entropy input-gradient."""
    data = nd.array(x)
    data.attach_grad()
    with autograd.record():
        loss = loss_fn(net(data) / temperature, nd.array(y))
    loss.backward()
    return x + eps * np.sign(data.grad.asnumpy())


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=150)
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--eps", type=float, default=0.25)
    parser.add_argument("--seed", type=int, default=None,
                        help="net-init seed; defaults to MXNET_TEST_SEED "
                             "(else 0)")
    args = parser.parse_args()

    # Root cause of the round-5 "flakiness" story, in two layers.  Layer 1
    # (fixed in r5): initializers drew from numpy's GLOBAL RNG, so
    # mx.random.seed never controlled net init and the collapse margin
    # changed between *identical* invocations (red at
    # MXNET_TEST_SEED=871536002).  Layer 2 (fixed here): the r5 fix pinned
    # --seed 0, which MASKED the knob instead of testing it —
    # FLAKINESS_FGSM_r05.txt ran "100 seeds" through
    # tools/flakiness_checker.py, but every trial was bit-for-bit the same
    # run, so 0/100 proved determinism, not seed-robustness.  The seed now
    # defaults to MXNET_TEST_SEED so the checker's knob really varies the
    # trained net + attack; the exit gates hold across seeds by MARGIN
    # (measured over seeds 1-16: clean 1.000, fgsm 0.15-0.43 vs the 0.70
    # bound, random-sign 1.000 vs the 0.85 bound), not by pinning.  Data
    # RNGs stay fixed so the classification task itself is constant.
    if args.seed is None:
        args.seed = int(os.environ.get("MXNET_TEST_SEED", "0"))
    mx.random.seed(args.seed)
    rng = np.random.RandomState(3)
    net = build_net()
    net.initialize(mx.init.Xavier())
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    for step in range(args.steps):
        x, y = synthetic_batch(rng, args.batch_size)
        with autograd.record():
            loss = loss_fn(net(nd.array(x)), nd.array(y))
        loss.backward()
        trainer.step(args.batch_size)

    ev = np.random.RandomState(77)
    x, y = synthetic_batch(ev, 256)
    clean = accuracy(net, x, y)
    x_adv = fgsm_perturb(net, loss_fn, x, y, args.eps)
    adv = accuracy(net, x_adv, y)
    x_rand = x + args.eps * np.sign(ev.normal(size=x.shape)).astype(np.float32)
    rand = accuracy(net, x_rand, y)
    print("accuracy clean %.3f | fgsm(eps=%.2f) %.3f | random-sign %.3f"
          % (clean, args.eps, adv, rand))
    return clean, adv, rand


if __name__ == "__main__":
    clean, adv, rand = main()
    ok = clean > 0.9 and adv < clean - 0.3 and rand > clean - 0.15
    if not ok:
        sys.exit("FAIL: clean %.3f adv %.3f rand %.3f" % (clean, adv, rand))
    print("FGSM OK")
