"""DeepSpeech-style acoustic model: Conv front-end + bidirectional RNN + CTC
(reference: example/speech_recognition/ — arch_deepspeech.py builds
conv -> stacked BiGRU -> FC -> warp-CTC over spectrogram buckets;
stt_metric.py scores with CTC label error rate).

Zero-egress version: "utterances" are synthetic filter-bank sequences.
Each of NUM_PHONES phonemes owns a fixed random spectral signature; an
utterance is a phoneme string rendered with *variable duration* (4-8
frames per phoneme, speech's key difference from OCR's fixed glyph
width) plus noise.  The model must align variable-duration events to the
unpadded label string — exactly what CTC solves (the reference trains
against warp-CTC, src/operator/nn/ctc_loss.cc:38; here the XLA ctc_loss).

Architecture mirrors arch_deepspeech.py's shape at toy scale:
Conv1D(stride 2) time-downsample -> BiLSTM (BidirectionalCell) -> Dense.
Scored with phoneme error rate (edit distance / ref length), the
stt_metric.py analog.

Run (CPU smoke):  JAX_PLATFORMS=cpu python example/speech_recognition/deepspeech_toy.py
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

NUM_PHONES = 8            # phoneme classes; CTC blank is class 8 (last)
NUM_MEL = 16              # filter-bank channels per frame
MIN_DUR, MAX_DUR = 4, 8   # frames a single phoneme lasts
_SIGS = np.random.RandomState(7).normal(0, 1, (NUM_PHONES, NUM_MEL)) \
    .astype(np.float32)


def synthetic_batch(rng, batch, min_len=3, max_len=6):
    """Utterances (N, T, NUM_MEL) + labels (N, max_len) padded -1.

    T is fixed at max_len*MAX_DUR (bucketing's single-bucket case; the
    reference pads within a bucket the same way) — trailing frames are
    pure noise the net must learn to emit blanks over."""
    T = max_len * MAX_DUR
    x = rng.normal(0, 0.4, (batch, T, NUM_MEL)).astype(np.float32)
    labels = np.full((batch, max_len), -1, np.float32)
    label_lens = np.zeros((batch,), np.float32)
    for i in range(batch):
        L = rng.randint(min_len, max_len + 1)
        phones = rng.randint(0, NUM_PHONES, L)
        labels[i, :L] = phones
        label_lens[i] = L
        t = 0
        for p in phones:
            dur = rng.randint(MIN_DUR, MAX_DUR + 1)
            # amplitude-modulated signature over the phoneme's duration
            env = np.hanning(dur + 2)[1:-1].astype(np.float32)
            x[i, t:t + dur] += env[:, None] * _SIGS[p]
            t += dur
    return x, labels, label_lens


class AcousticNet(gluon.HybridBlock):
    """Conv1D downsample + BiLSTM + per-frame classifier.

    Same stack as the reference's arch_deepspeech.py (conv front-end,
    bidirectional recurrence, per-step FC into warp-CTC) at toy scale.
    HybridBlock: the full unroll traces into one cached XLA module."""

    def __init__(self, seq_len, hidden=64, conv_channels=32, **kwargs):
        super().__init__(**kwargs)
        self._seq_len = seq_len // 2          # conv stride-2 halves T
        with self.name_scope():
            # NCW layout: channels = mel bins, width = time
            self.conv = nn.Conv1D(conv_channels, kernel_size=5, strides=2,
                                  padding=2, activation="relu")
            self.birnn = rnn.BidirectionalCell(rnn.LSTMCell(hidden),
                                               rnn.LSTMCell(hidden))
            self.proj = nn.Dense(NUM_PHONES + 1, flatten=False)

    def hybrid_forward(self, F, x):           # x: (N, T, NUM_MEL)
        h = self.conv(x.transpose((0, 2, 1))) # (N, C, T/2)
        h = h.transpose((0, 2, 1))            # (N, T/2, C)
        outs, _ = self.birnn.unroll(self._seq_len, h, layout="NTC",
                                    merge_outputs=True)
        return self.proj(outs)                # (N, T/2, classes+1)


def greedy_decode(logits):
    """Best path: per-frame argmax -> collapse repeats -> drop blanks."""
    blank = NUM_PHONES
    seqs = []
    for path in logits.argmax(-1):
        out, prev = [], -1
        for c in path:
            if c != prev and c != blank:
                out.append(int(c))
            prev = c
        seqs.append(out)
    return seqs


def _edit_distance(a, b):
    dp = list(range(len(b) + 1))
    for i, ca in enumerate(a, 1):
        prev, dp[0] = dp[0], i
        for j, cb in enumerate(b, 1):
            prev, dp[j] = dp[j], min(dp[j] + 1, dp[j - 1] + 1,
                                     prev + (ca != cb))
    return dp[-1]


def phone_error_rate(net, rng, batches, batch):
    """CTC label error rate = edit distance / reference length
    (stt_metric.py's EvalSTTMetric analog)."""
    dist = ref_len = 0
    for _ in range(batches):
        x, labels, lens = synthetic_batch(rng, batch)
        logits = net(nd.array(x)).asnumpy()
        for seq, lab, L in zip(greedy_decode(logits), labels, lens):
            ref = list(lab[:int(L)].astype(int))
            dist += _edit_distance(seq, ref)
            ref_len += len(ref)
    return dist / ref_len


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=800)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.003)
    args = ap.parse_args(argv)

    max_len = 6
    np.random.seed(0)
    net = AcousticNet(max_len * MAX_DUR, args.hidden)
    net.initialize(mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": args.lr})
    ctc = gluon.loss.CTCLoss(layout="NTC", label_layout="NT")
    rng = np.random.RandomState(0)

    per0 = phone_error_rate(net, np.random.RandomState(99), 4,
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
            print("step %d ctc loss %.4f" % (
                step, float(loss.asnumpy().ravel()[0])), flush=True)

    per = phone_error_rate(net, np.random.RandomState(99), 4,
                           args.batch_size)
    print("phone error rate: %.3f (untrained %.3f)" % (per, per0))
    return per0, per


if __name__ == "__main__":
    main()
