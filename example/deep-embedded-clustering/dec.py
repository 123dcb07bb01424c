"""Deep Embedded Clustering (reference: example/deep-embedded-clustering/
dec.py — stacked-autoencoder pretraining, then joint optimization of the
encoder and K cluster centroids against the self-sharpening KL objective
of Xie et al. 2016, scored by cluster accuracy on MNIST).

Zero-egress version: inputs are 16-D observations generated from K=4
well-separated 2-D latent modes through one fixed random linear map plus
noise, so a 2-D bottleneck autoencoder can recover the latent geometry.

Phases (same shape as the reference):
  1. Autoencoder pretraining (L2 reconstruction).
  2. Centroid init: numpy Lloyd iterations on the encoded training set
     (the reference calls into sklearn KMeans; Lloyd-in-numpy keeps zero
     dependencies).
  3. DEC: student-t soft assignments q, sharpened target p = q^2/f
     (normalized), minimize KL(p || q) through encoder AND centroids —
     the centroids are a first-class gluon Parameter trained by the same
     Trainer step as the encoder weights.

Scored with cluster purity (majority-label accuracy under the best
greedy cluster->class map), the unsupervised-accuracy analog.

Run (CPU smoke):  JAX_PLATFORMS=cpu python example/deep-embedded-clustering/dec.py
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

DIM = 16
LATENT = 2
K = 4
_MAP = np.random.RandomState(5).normal(0, 1, (LATENT, DIM)).astype(np.float32)
_MODES = np.array([[3, 3], [-3, 3], [3, -3], [-3, -3]], np.float32)


def synthetic_data(rng, n):
    labels = rng.randint(0, K, n)
    z = _MODES[labels] + rng.normal(0, 0.4, (n, LATENT)).astype(np.float32)
    x = z @ _MAP + rng.normal(0, 0.15, (n, DIM)).astype(np.float32)
    return x.astype(np.float32), labels


class AutoEncoder(gluon.HybridBlock):
    def __init__(self, hidden=32, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.enc = nn.HybridSequential()
            self.enc.add(nn.Dense(hidden, activation="relu"),
                         nn.Dense(LATENT))
            self.dec = nn.HybridSequential()
            self.dec.add(nn.Dense(hidden, activation="relu"),
                         nn.Dense(DIM))

    def hybrid_forward(self, F, x):
        z = self.enc(x)
        return self.dec(z), z


class DECHead(gluon.HybridBlock):
    """Student-t soft assignment to K trainable centroids (alpha=1)."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.centroids = self.params.get("centroids",
                                             shape=(K, LATENT))

    def hybrid_forward(self, F, z, centroids):
        d2 = ((z.expand_dims(1) - centroids.expand_dims(0)) ** 2).sum(2)
        q = 1.0 / (1.0 + d2)
        return q / q.sum(axis=1, keepdims=True)


def lloyd_init(z, rng, iters=20, restarts=8):
    """k-means centroids, best of ``restarts`` random initializations by
    within-cluster SSE.  A single Lloyd run from one random draw regularly
    sticks in a merged-cluster optimum (purity ~0.75 on this data); the
    reference DEC recipe relies on a well-initialized k-means too."""
    best_c, best_sse = None, np.inf
    for _ in range(restarts):
        c = z[rng.choice(len(z), K, replace=False)].copy()
        for _ in range(iters):
            assign = ((z[:, None] - c[None]) ** 2).sum(-1).argmin(1)
            for k in range(K):
                if (assign == k).any():
                    c[k] = z[assign == k].mean(0)
        d2 = ((z[:, None] - c[None]) ** 2).sum(-1)
        sse = float(d2.min(1).sum())
        if sse < best_sse:
            best_sse, best_c = sse, c
    return best_c


def purity(assign, labels):
    total = 0
    for k in np.unique(assign):
        members = labels[assign == k]
        total += np.bincount(members, minlength=K).max()
    return total / len(labels)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1024)
    ap.add_argument("--pretrain-steps", type=int, default=300)
    ap.add_argument("--dec-steps", type=int, default=200)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.003)
    args = ap.parse_args(argv)

    np.random.seed(0)
    mx.random.seed(0)  # deterministic init (framework stream, r5)
    rng = np.random.RandomState(0)
    x_all, labels = synthetic_data(rng, args.n)

    ae = AutoEncoder()
    ae.initialize(mx.init.Xavier())
    ae.hybridize()
    trainer = gluon.Trainer(ae.collect_params(), "adam",
                            {"learning_rate": args.lr})
    l2 = gluon.loss.L2Loss()

    for step in range(args.pretrain_steps):
        idx = rng.randint(0, args.n, args.batch_size)
        xb = nd.array(x_all[idx])
        with autograd.record():
            recon, _ = ae(xb)
            loss = l2(recon, xb).mean()
        loss.backward()
        trainer.step(args.batch_size)
        if step % 100 == 0:
            print("pretrain %d recon loss %.4f" % (
                step, float(loss.asnumpy().ravel()[0])), flush=True)

    z_all = ae(nd.array(x_all))[1].asnumpy()
    assign0 = ((z_all[:, None] - lloyd_init(z_all, rng)[None]) ** 2) \
        .sum(-1).argmin(1)
    purity0 = purity(assign0, labels)

    head = DECHead()
    head.initialize(mx.init.Zero())
    head.centroids.set_data(nd.array(lloyd_init(z_all, rng)))
    dec_trainer = gluon.Trainer(
        list(ae.enc.collect_params().values()) +
        list(head.collect_params().values()),
        "adam", {"learning_rate": args.lr})

    for step in range(args.dec_steps):
        idx = rng.randint(0, args.n, args.batch_size)
        xb = nd.array(x_all[idx])
        with autograd.record():
            _, z = ae(xb)
            q = head(z)
            # sharpened target: p = (q^2 / cluster-frequency), normalized,
            # treated as a constant (stop-gradient) like the reference
            p = q.asnumpy() ** 2 / q.asnumpy().sum(0, keepdims=True)
            p = nd.array(p / p.sum(1, keepdims=True))
            loss = (p * (nd.log(p + 1e-10) - nd.log(q + 1e-10))) \
                .sum(axis=1).mean()
        loss.backward()
        dec_trainer.step(args.batch_size)
        if step % 100 == 0:
            print("dec %d KL %.4f" % (
                step, float(loss.asnumpy().ravel()[0])), flush=True)

    z_fin = ae(nd.array(x_all))[1].asnumpy()
    c_fin = head.centroids.data().asnumpy()
    assign = ((z_fin[:, None] - c_fin[None]) ** 2).sum(-1).argmin(1)
    pur = purity(assign, labels)
    print("cluster purity: %.3f (kmeans-on-pretrained %.3f)" % (pur, purity0))
    return purity0, pur


if __name__ == "__main__":
    main()
