"""DCGAN (reference: example/gan/dcgan.py — Deconvolution generator vs
Conv discriminator trained adversarially).

Zero-egress version: the "real" distribution is synthetic 16x16 images of
a bright disk at a random position (strongly structured second moments).
The generator upsamples a latent vector through two Conv2DTranspose
(Deconvolution) stages; the discriminator mirrors it with stride-2 convs
+ LeakyReLU (the DCGAN recipe).  Both are hybridized so each training
step is two compiled XLA modules.

Success is measured, not eyeballed: after training, the generator's
samples must match the real distribution's pixel mean and per-image
spatial variance within tolerance, while a freshly-initialized generator
fails both (printed as the moment-match report).

Run (CPU smoke):  JAX_PLATFORMS=cpu python example/gan/dcgan.py
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

IMG = 16


def real_batch(rng, n):
    """Bright disks on dark background, random centers/radii."""
    yy, xx = np.mgrid[0:IMG, 0:IMG]
    out = np.empty((n, 1, IMG, IMG), np.float32)
    for i in range(n):
        cy, cx = rng.uniform(4, IMG - 4, 2)
        r = rng.uniform(2.0, 4.0)
        disk = ((yy - cy) ** 2 + (xx - cx) ** 2 <= r * r)
        out[i, 0] = 0.05 + 0.9 * disk
    return out + rng.uniform(0, 0.05, out.shape).astype(np.float32)


class Generator(gluon.HybridBlock):
    def __init__(self, latent=16, **kwargs):
        super().__init__(**kwargs)
        self.latent = latent
        with self.name_scope():
            self.fc = nn.Dense(32 * 4 * 4)
            self.bn0 = nn.BatchNorm()
            self.up1 = nn.Conv2DTranspose(16, 4, strides=2, padding=1)
            self.bn1 = nn.BatchNorm()
            self.up2 = nn.Conv2DTranspose(1, 4, strides=2, padding=1)

    def hybrid_forward(self, F, z):
        h = F.relu(self.bn0(self.fc(z)))
        h = h.reshape((-1, 32, 4, 4))
        h = F.relu(self.bn1(self.up1(h)))          # (N, 16, 8, 8)
        return F.sigmoid(self.up2(h))              # (N, 1, 16, 16)


class Discriminator(gluon.HybridBlock):
    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.c1 = nn.Conv2D(16, 4, strides=2, padding=1)
            self.a1 = nn.LeakyReLU(0.2)
            self.c2 = nn.Conv2D(32, 4, strides=2, padding=1)
            self.a2 = nn.LeakyReLU(0.2)
            self.fc = nn.Dense(1)

    def hybrid_forward(self, F, x):
        h = self.a1(self.c1(x))
        h = self.a2(self.c2(h))
        return self.fc(h)                          # logits (N, 1)


def moments(imgs):
    """(pixel mean, mean per-image spatial std) of a (N,1,H,W) batch."""
    return float(imgs.mean()), float(imgs.reshape(imgs.shape[0], -1)
                                     .std(axis=1).mean())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=600)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--latent", type=int, default=16)
    ap.add_argument("--lr", type=float, default=2e-4)
    args = ap.parse_args()

    # deterministic init: Xavier draws from the numpy global RNG
    np.random.seed(0)
    gen = Generator(args.latent)
    disc = Discriminator()
    for blk in (gen, disc):
        blk.initialize(mx.init.Xavier())
        blk.hybridize()
    g_tr = gluon.Trainer(gen.collect_params(), "adam",
                         {"learning_rate": args.lr, "beta1": 0.5})
    d_tr = gluon.Trainer(disc.collect_params(), "adam",
                         {"learning_rate": args.lr, "beta1": 0.5})
    bce = gluon.loss.SigmoidBinaryCrossEntropyLoss()
    rng = np.random.RandomState(0)
    B = args.batch_size

    def sample(n):
        z = nd.array(rng.normal(0, 1, (n, args.latent)).astype(np.float32))
        return gen(z)

    real_m = moments(real_batch(np.random.RandomState(77), 256))
    fake0_m = moments(sample(256).asnumpy())
    ones, zeros = nd.ones((B, 1)), nd.zeros((B, 1))

    for step in range(args.steps):
        real = nd.array(real_batch(rng, B))
        # --- discriminator: real -> 1, fake -> 0 ----------------------
        # the fake is generated INSIDE record (train-mode BatchNorm batch
        # stats, same distribution the G step optimizes) then detached
        z = nd.array(rng.normal(0, 1, (B, args.latent)).astype(np.float32))
        with autograd.record():
            fake = gen(z).detach()
            d_loss = (bce(disc(real), ones) + bce(disc(fake), zeros)).mean()
        d_loss.backward()
        d_tr.step(B)
        # --- generator: fool the discriminator ------------------------
        z = nd.array(rng.normal(0, 1, (B, args.latent)).astype(np.float32))
        with autograd.record():
            g_loss = bce(disc(gen(z)), ones).mean()
        g_loss.backward()
        g_tr.step(B)
        if step % 100 == 0:
            print("step %d d_loss %.3f g_loss %.3f" % (
                step, float(d_loss.asnumpy().ravel()[0]),
                float(g_loss.asnumpy().ravel()[0])), flush=True)

    fake_m = moments(sample(256).asnumpy())
    print("moments (pixel mean, spatial std): real=(%.3f, %.3f) "
          "fake=(%.3f, %.3f) untrained=(%.3f, %.3f)"
          % (real_m + fake_m + fake0_m))
    mean_err = abs(fake_m[0] - real_m[0])
    std_err = abs(fake_m[1] - real_m[1])
    print("moment match: mean_err=%.4f std_err=%.4f" % (mean_err, std_err))


if __name__ == "__main__":
    main()
