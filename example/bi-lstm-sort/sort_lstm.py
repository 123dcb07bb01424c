"""Sort digit sequences with a bidirectional LSTM (reference:
example/bi-lstm-sort — the classic seq2seq-sort sanity task).

A sequence of random digits goes through an embedding and a
BidirectionalCell(LSTM, LSTM); position i's fused forward+backward state
classifies the i-th SMALLEST element.  Because every position sees the
whole sequence through the two directions, the task is learnable exactly —
held-out per-position accuracy should approach 1.0.

Run (CPU smoke):  JAX_PLATFORMS=cpu python example/bi-lstm-sort/sort_lstm.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd, gluon
from mxnet_tpu.gluon import nn, rnn

VOCAB = 10


def batch(rng, n, seq_len):
    x = rng.randint(0, VOCAB, (n, seq_len))
    return x.astype(np.float32), np.sort(x, axis=1).astype(np.float32)


class SortNet(gluon.HybridBlock):
    """Embed -> BiLSTM -> per-position classifier over the vocabulary."""

    def __init__(self, seq_len, hidden=64, **kwargs):
        super().__init__(**kwargs)
        self._seq_len = seq_len
        with self.name_scope():
            self.embed = nn.Embedding(VOCAB, 32)
            self.bi = rnn.BidirectionalCell(rnn.LSTMCell(hidden),
                                            rnn.LSTMCell(hidden))
            self.out = nn.Dense(VOCAB, flatten=False)

    def hybrid_forward(self, F, x):
        h = self.embed(x)                               # (N, T, 32)
        outs, _ = self.bi.unroll(self._seq_len, h, layout="NTC",
                                 merge_outputs=True)    # (N, T, 2H)
        return self.out(outs)                           # (N, T, V)


def accuracy(net, rng, seq_len, batches=4, n=64):
    correct = total = 0
    for _ in range(batches):
        x, y = batch(rng, n, seq_len)
        pred = net(nd.array(x)).asnumpy().argmax(-1)
        correct += (pred == y).sum()
        total += y.size
    return correct / total


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=800)
    ap.add_argument("--seq-len", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--lr", type=float, default=0.003)
    args = ap.parse_args()

    # deterministic init: Xavier draws from the numpy global RNG
    np.random.seed(0)
    net = SortNet(args.seq_len)
    net.initialize(mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": args.lr})
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    rng = np.random.RandomState(0)

    acc0 = accuracy(net, np.random.RandomState(99), args.seq_len)
    for step in range(args.steps):
        x, y = batch(rng, args.batch_size, args.seq_len)
        xb, yb = nd.array(x), nd.array(y)
        with autograd.record():
            logits = net(xb)
            loss = ce(logits, yb).mean()
        loss.backward()
        trainer.step(args.batch_size)
        if step % 200 == 0:
            print("step %d loss %.4f" % (
                step, float(loss.asnumpy().ravel()[0])), flush=True)

    acc = accuracy(net, np.random.RandomState(99), args.seq_len)
    print("held-out per-position sort accuracy: %.3f (untrained %.3f)"
          % (acc, acc0))


if __name__ == "__main__":
    main()
