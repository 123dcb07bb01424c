"""CNN sentence classification (reference:
example/cnn_text_classification/text_cnn.py — Kim-2014-style net on the
MR sentence-polarity set: embedding -> parallel conv filters of widths
3/4/5 -> max-over-time pooling -> concat -> dropout -> dense).

Zero-egress version: token sequences over a 50-word vocabulary; a
sentence is positive iff one of two fixed "sentiment trigrams" occurs
ANYWHERE in it.  Position invariance is the thing max-over-time pooling
buys, so the synthetic task isolates exactly the architecture's claim.

Run (CPU smoke):  JAX_PLATFORMS=cpu python example/cnn_text_classification/text_cnn.py
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

VOCAB = 50
SEQ = 24
POS_TRIGRAMS = [(7, 11, 13), (23, 29, 31)]


def synthetic_batch(rng, batch):
    x = rng.randint(0, VOCAB, (batch, SEQ))
    # scrub accidental positives so labels are exact
    for tri in POS_TRIGRAMS:
        for t in range(SEQ - 2):
            hit = ((x[:, t] == tri[0]) & (x[:, t + 1] == tri[1])
                   & (x[:, t + 2] == tri[2]))
            x[hit, t] = (x[hit, t] + 1) % VOCAB
    y = rng.randint(0, 2, batch)
    for i in np.nonzero(y)[0]:
        tri = POS_TRIGRAMS[rng.randint(len(POS_TRIGRAMS))]
        t = rng.randint(0, SEQ - 3)
        x[i, t:t + 3] = tri
    return x.astype(np.float32), y.astype(np.float32)


class TextCNN(gluon.HybridBlock):
    """Embedding + parallel widths-3/4/5 convs + max-over-time + dense."""

    def __init__(self, embed=32, channels=32, dropout=0.3, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.embed = nn.Embedding(VOCAB, embed)
            self.convs = [nn.Conv1D(channels, w, activation="relu")
                          for w in (3, 4, 5)]
            for i, c in enumerate(self.convs):
                self.register_child(c, "conv%d" % i)
            self.pool = nn.GlobalMaxPool1D()
            self.drop = nn.Dropout(dropout)
            self.out = nn.Dense(2)

    def hybrid_forward(self, F, x):
        e = self.embed(x).transpose((0, 2, 1))   # (N, embed, T) NCW
        feats = [self.pool(c(e)).flatten() for c in self.convs]
        h = F.concat(*feats, dim=1)
        return self.out(self.drop(h))


def evaluate(net, rng, batches, batch):
    acc = metric.Accuracy()
    for _ in range(batches):
        x, y = synthetic_batch(rng, batch)
        acc.update(nd.array(y), net(nd.array(x)))
    return acc.get()[1]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--lr", type=float, default=0.002)
    args = ap.parse_args(argv)

    np.random.seed(0)
    net = TextCNN()
    net.initialize(mx.init.Xavier())
    net.hybridize()
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
    print("sentence accuracy: %.3f (untrained %.3f)" % (acc, acc0))
    return acc0, acc


if __name__ == "__main__":
    main()
