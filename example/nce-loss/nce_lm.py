"""Noise-contrastive estimation for large-vocabulary softmax (reference:
example/nce-loss/ — nce.py builds the sampled binary-logistic objective
over one true class + k noise classes per position; wordvec.py/lstm_*.py
train word embeddings and LSTM LMs with it instead of a full softmax).

Zero-egress version: a skip-gram-style task over a 2,000-word vocabulary
whose co-occurrence structure is K=8 "topics" (each word belongs to one
topic; a context word predicts a target drawn from the same topic).  The
full-softmax output matrix would be (dim x 2000); NCE trains the same
embedding with only k=16 sampled noise words per example:

    loss = -log sigmoid(s(w_true)) - sum_k log sigmoid(-s(w_noise))

with s(w) = <h, out_embed[w]> + b[w], noise drawn from the unigram
distribution.  Success = topic coherence of the learned input embedding:
nearest neighbors of a word land in its own topic far above chance
(1/K = 0.125), without ever materializing the full softmax.

Run (CPU smoke):  JAX_PLATFORMS=cpu python example/nce-loss/nce_lm.py
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

VOCAB = 2000
TOPICS = 8
TOPIC_OF = np.arange(VOCAB) % TOPICS


def synthetic_batch(rng, batch):
    ctx = rng.randint(0, VOCAB, batch)
    # target: another word from the context word's topic
    tgt = TOPIC_OF[ctx] + TOPICS * rng.randint(0, VOCAB // TOPICS, batch)
    return ctx.astype(np.float32), tgt.astype(np.float32)


class NCEEmbed(gluon.HybridBlock):
    """Input embedding + output embedding/bias scored only at sampled
    rows — the whole point of NCE is that no (batch x VOCAB) logits
    matrix ever exists."""

    def __init__(self, dim=32, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.embed_in = nn.Embedding(VOCAB, dim)
            self.embed_out = nn.Embedding(VOCAB, dim)
            self.bias_out = nn.Embedding(VOCAB, 1)

    def hybrid_forward(self, F, ctx, cand):
        h = self.embed_in(ctx)                       # (N, dim)
        e = self.embed_out(cand)                     # (N, 1+k, dim)
        b = self.bias_out(cand).reshape((0, -1))     # (N, 1+k)
        return (e * h.expand_dims(1)).sum(axis=2) + b


def topic_coherence(net, rng, n_words=128, topn=8):
    """Fraction of each probe word's top-n cosine neighbors (by input
    embedding) sharing its topic; chance = 1/TOPICS."""
    W = net.embed_in.weight.data().asnumpy()
    W = W / (np.linalg.norm(W, axis=1, keepdims=True) + 1e-8)
    probes = rng.choice(VOCAB, n_words, replace=False)
    hits = 0
    for w in probes:
        sims = W @ W[w]
        sims[w] = -np.inf
        nbrs = np.argpartition(-sims, topn)[:topn]
        hits += (TOPIC_OF[nbrs] == TOPIC_OF[w]).sum()
    return hits / (n_words * topn)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=600)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--num-noise", type=int, default=16)
    ap.add_argument("--lr", type=float, default=0.05)
    args = ap.parse_args(argv)

    np.random.seed(0)
    net = NCEEmbed()
    net.initialize(mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": args.lr})
    rng = np.random.RandomState(0)

    coh0 = topic_coherence(net, np.random.RandomState(99))
    k = args.num_noise
    # labels: first candidate is the true word, rest are noise
    y = np.zeros((args.batch_size, 1 + k), np.float32)
    y[:, 0] = 1.0
    yb = nd.array(y)
    bce = gluon.loss.SigmoidBinaryCrossEntropyLoss()
    for step in range(args.steps):
        ctx, tgt = synthetic_batch(rng, args.batch_size)
        noise = rng.randint(0, VOCAB, (args.batch_size, k))
        cand = np.concatenate([tgt[:, None], noise], axis=1)
        cb, xb = nd.array(cand), nd.array(ctx)
        with autograd.record():
            scores = net(xb, cb)                     # (N, 1+k)
            loss = bce(scores, yb).mean()
        loss.backward()
        trainer.step(args.batch_size)
        if step % 200 == 0:
            print("step %d nce loss %.4f" % (
                step, float(loss.asnumpy().ravel()[0])), flush=True)

    coh = topic_coherence(net, np.random.RandomState(99))
    print("topic coherence: %.3f (untrained %.3f, chance %.3f)"
          % (coh, coh0, 1.0 / TOPICS))
    return coh0, coh


if __name__ == "__main__":
    main()
