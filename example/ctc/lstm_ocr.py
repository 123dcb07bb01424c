"""LSTM + CTC OCR (reference: example/ctc/lstm_ocr.py — captcha digit
recognition trained with warp-CTC; src/operator/nn/ctc_loss.cc:38 is the op).

Zero-egress version: "captchas" are synthesized as horizontal strips of
per-digit glyph columns (fixed random 8x8 binary patterns) plus pixel
noise; the variable-length digit string is the label.  An LSTM reads the
image column-by-column (T = image width) and CTC aligns the per-column
class posteriors to the unpadded label sequence — same structure as the
reference (image -> column features -> recurrent net -> CTC).

Decoding is greedy best-path: per-step argmax, collapse repeats, strip
blanks (reference example/ctc/ocr_predict.py).

Run (CPU smoke):  JAX_PLATFORMS=cpu python example/ctc/lstm_ocr.py
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

NUM_DIGITS = 10           # classes 0-9; CTC blank is class 10 ('last')
GLYPH_H = GLYPH_W = 8
_GLYPHS = (np.random.RandomState(42).rand(NUM_DIGITS, GLYPH_H, GLYPH_W)
           > 0.5).astype(np.float32)


def synthetic_batch(rng, batch, min_len=3, max_len=5):
    """Images (N, T, H) of glyph columns; labels (N, max_len) padded -1."""
    T = max_len * GLYPH_W
    x = rng.uniform(0, 0.3, (batch, T, GLYPH_H)).astype(np.float32)
    labels = np.full((batch, max_len), -1, np.float32)
    label_lens = np.zeros((batch,), np.float32)
    for i in range(batch):
        L = rng.randint(min_len, max_len + 1)
        digits = rng.randint(0, NUM_DIGITS, L)
        labels[i, :L] = digits
        label_lens[i] = L
        for j, d in enumerate(digits):
            # glyph columns transposed into (T, H) time-major order
            x[i, j * GLYPH_W:(j + 1) * GLYPH_W] += _GLYPHS[d].T
    return x, labels, label_lens


class OCRNet(gluon.HybridBlock):
    """Column LSTM + per-step classifier (reference lstm_ocr.py net).

    HybridBlock so the whole T-step unroll traces into one cached XLA
    module (hybridize gives ~20x over eager for small-op RNN chains —
    EAGER_OVERHEAD.json)."""

    def __init__(self, seq_len, hidden=64, **kwargs):
        super().__init__(**kwargs)
        self._seq_len = seq_len
        with self.name_scope():
            self.lstm = rnn.LSTMCell(hidden)
            self.proj = nn.Dense(NUM_DIGITS + 1, flatten=False)

    def hybrid_forward(self, F, x):            # x: (N, T, H)
        outs, _ = self.lstm.unroll(self._seq_len, x, layout="NTC",
                                   merge_outputs=True)
        return self.proj(outs)                 # (N, T, C+1)


def greedy_decode(logits):
    """Best path: per-step argmax -> collapse repeats -> drop blanks."""
    blank = NUM_DIGITS
    seqs = []
    for path in logits.argmax(-1):
        out, prev = [], -1
        for c in path:
            if c != prev and c != blank:
                out.append(int(c))
            prev = c
        seqs.append(out)
    return seqs


def sequence_accuracy(net, rng, batches, batch):
    correct = total = 0
    for _ in range(batches):
        x, labels, lens = synthetic_batch(rng, batch)
        logits = net(nd.array(x)).asnumpy()
        for seq, lab, L in zip(greedy_decode(logits), labels, lens):
            total += 1
            correct += seq == list(lab[:int(L)].astype(int))
    return correct / total


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=1200)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--hidden", type=int, default=96)
    ap.add_argument("--lr", type=float, default=0.003)
    args = ap.parse_args()

    max_len = 5
    # deterministic init: Xavier draws from the numpy global RNG
    np.random.seed(0)
    net = OCRNet(max_len * GLYPH_W, args.hidden)
    net.initialize(mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": args.lr})
    ctc = gluon.loss.CTCLoss(layout="NTC", label_layout="NT")
    rng = np.random.RandomState(0)

    acc0 = sequence_accuracy(net, np.random.RandomState(99), 4,
                             args.batch_size)
    for step in range(args.steps):
        x, labels, lens = synthetic_batch(rng, args.batch_size)
        xb, lb = nd.array(x), nd.array(labels)
        with autograd.record():
            logits = net(xb)
            loss = ctc(logits, lb, None, nd.array(lens)).mean()
        loss.backward()
        trainer.step(args.batch_size)
        if step % 200 == 0:
            print("step %d ctc loss %.4f" % (step, float(
                loss.asnumpy().ravel()[0])), flush=True)

    acc = sequence_accuracy(net, np.random.RandomState(99), 4,
                            args.batch_size)
    print("sequence accuracy: %.3f (untrained %.3f)" % (acc, acc0))


if __name__ == "__main__":
    main()
