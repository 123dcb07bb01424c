"""Variational autoencoder (reference: example/vae/VAE.py — MLP
encoder/decoder on MNIST trained on the ELBO: Bernoulli reconstruction
log-likelihood + KL(q(z|x) || N(0,I)), with the reparameterization trick
z = mu + sigma * eps drawn per step).

Zero-egress version: the "digits" are synthetic 16x16 binary images from
K=4 latent modes (fixed random blob prototypes, pixel flip noise), so the
true data manifold is low-dimensional and a 2-D latent VAE can model it.
Success = trained ELBO well above the untrained one AND reconstructions
closer to their inputs than to the other modes' prototypes.

The stochastic layer runs INSIDE autograd.record(): eps is sampled with
mx.nd.random.normal per batch and the gradient flows through mu/sigma
(reparameterization), exercising the RNG-under-tape path end-to-end.

Run (CPU smoke):  JAX_PLATFORMS=cpu python example/vae/vae_mnist_like.py
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

SIDE = 16
PIX = SIDE * SIDE
K = 4
_PROTOS = None


def _prototypes():
    global _PROTOS
    if _PROTOS is None:
        rng = np.random.RandomState(11)
        protos = np.zeros((K, SIDE, SIDE), np.float32)
        for k in range(K):
            for _ in range(3):  # three blobs per mode
                cy, cx = rng.randint(3, SIDE - 3, 2)
                yy, xx = np.mgrid[0:SIDE, 0:SIDE]
                protos[k] += np.exp(-((yy - cy) ** 2 + (xx - cx) ** 2)
                                    / 6.0)
        _PROTOS = (protos > 0.5).astype(np.float32)
    return _PROTOS


def synthetic_batch(rng, batch):
    protos = _prototypes()
    modes = rng.randint(0, K, batch)
    x = protos[modes].reshape(batch, PIX).copy()
    flip = rng.rand(batch, PIX) < 0.02
    x[flip] = 1.0 - x[flip]
    return x.astype(np.float32), modes


class VAE(gluon.HybridBlock):
    """MLP encoder -> (mu, logvar) -> sample -> MLP decoder -> logits."""

    def __init__(self, hidden=128, latent=2, **kwargs):
        super().__init__(**kwargs)
        self._latent = latent
        with self.name_scope():
            self.enc = nn.HybridSequential()
            self.enc.add(nn.Dense(hidden, activation="tanh"))
            self.enc_mu = nn.Dense(latent)
            self.enc_logvar = nn.Dense(latent)
            self.dec = nn.HybridSequential()
            self.dec.add(nn.Dense(hidden, activation="tanh"),
                         nn.Dense(PIX))

    def hybrid_forward(self, F, x, eps):
        h = self.enc(x)
        mu, logvar = self.enc_mu(h), self.enc_logvar(h)
        z = mu + F.exp(0.5 * logvar) * eps       # reparameterization
        logits = self.dec(z)
        return logits, mu, logvar


def elbo_terms(logits, x, mu, logvar):
    """Per-example Bernoulli log-likelihood and KL(q || N(0,I))."""
    ll = -(nd.relu(logits) - logits * x +
           nd.log(1 + nd.exp(-nd.abs(logits)))).sum(axis=1)
    kl = 0.5 * (nd.exp(logvar) + mu * mu - 1 - logvar).sum(axis=1)
    return ll, kl


def mean_elbo(net, rng, batches, batch):
    tot = 0.0
    for _ in range(batches):
        x, _ = synthetic_batch(rng, batch)
        xb = nd.array(x)
        eps = nd.zeros((batch, net._latent))     # posterior mean eval
        logits, mu, logvar = net(xb, eps)
        ll, kl = elbo_terms(logits, xb, mu, logvar)
        tot += float((ll - kl).mean().asnumpy().ravel()[0])
    return tot / batches


def reconstruction_mode_accuracy(net, rng, batch):
    """Decode at the posterior mean; the reconstruction must be nearest
    (in pixel L2) to the prototype of ITS OWN mode."""
    protos = _prototypes().reshape(K, PIX)
    x, modes = synthetic_batch(rng, batch)
    eps = nd.zeros((batch, net._latent))
    logits, _, _ = net(nd.array(x), eps)
    recon = 1.0 / (1.0 + np.exp(-logits.asnumpy()))
    d = ((recon[:, None, :] - protos[None]) ** 2).sum(-1)
    return float((d.argmin(1) == modes).mean())


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=600)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--latent", type=int, default=2)
    ap.add_argument("--lr", type=float, default=0.002)
    args = ap.parse_args(argv)

    np.random.seed(0)
    net = VAE(args.hidden, args.latent)
    net.initialize(mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": args.lr})
    rng = np.random.RandomState(0)

    elbo0 = mean_elbo(net, np.random.RandomState(99), 4, args.batch_size)
    for step in range(args.steps):
        x, _ = synthetic_batch(rng, args.batch_size)
        xb = nd.array(x)
        eps = nd.random.normal(0, 1, (args.batch_size, args.latent))
        with autograd.record():
            logits, mu, logvar = net(xb, eps)
            ll, kl = elbo_terms(logits, xb, mu, logvar)
            loss = -(ll - kl).mean()
        loss.backward()
        trainer.step(args.batch_size)
        if step % 150 == 0:
            print("step %d -ELBO %.2f" % (
                step, float(loss.asnumpy().ravel()[0])), flush=True)

    elbo = mean_elbo(net, np.random.RandomState(99), 4, args.batch_size)
    acc = reconstruction_mode_accuracy(net, np.random.RandomState(123),
                                       args.batch_size)
    print("elbo: %.2f (untrained %.2f), recon mode accuracy: %.3f"
          % (elbo, elbo0, acc))
    return elbo0, elbo, acc


if __name__ == "__main__":
    main()
