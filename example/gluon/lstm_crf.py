"""BiLSTM-CRF sequence labeling (reference: example/gluon/lstm_crf.py —
Lample et al. 2016: a BiLSTM scores per-token tag emissions, a CRF layer
with a learned tag-transition matrix scores whole tag SEQUENCES; training
minimizes -log p(gold path) = logZ - score(gold), inference runs viterbi).

Zero-egress version: synthetic BIO chunking where I-tokens draw from the
SAME vocab bucket as O-tokens — per-token evidence cannot identify I at
all; only sequence structure (I must extend a B/I run) can.  The
assertion is exactly that: an emission-only per-token baseline scores
I-tag F1 = 0, the CRF must find the I runs (F1 > 0.5, higher overall
accuracy, zero BIO-grammar violations).  The forward-algorithm recursion
runs in log space under the autograd tape; viterbi decodes in numpy at
inference.

Run (CPU smoke):  JAX_PLATFORMS=cpu python example/gluon/lstm_crf.py
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

VOCAB, TAGS = 30, 3  # tags: O=0, B=1, I=2
SEQ_LEN = 12


def synthetic_batch(rng, batch):
    """BIO-grammar tag walks + ambiguous tag-conditional tokens.

    Token buckets overlap between O and B and between B and I, so the
    emission alone cannot disambiguate — sequence structure must."""
    tags = np.zeros((batch, SEQ_LEN), dtype=np.int64)
    for b in range(batch):
        t = 0
        for i in range(SEQ_LEN):
            if t == 0:
                t = 1 if rng.rand() < 0.35 else 0
            elif t in (1, 2):
                r = rng.rand()
                t = 2 if r < 0.65 else (1 if r < 0.75 else 0)
            tags[b, i] = t
    # bucket ranges per tag: O and I draw from the SAME bucket, so the
    # emission is useless for O-vs-I — only sequence structure (I must
    # follow B or I) can disambiguate; B overlaps both partially
    lo = {0: 0, 1: 16, 2: 0}
    hi = {0: 16, 1: 30, 2: 16}
    toks = np.zeros((batch, SEQ_LEN), dtype=np.int64)
    for t in range(TAGS):
        m = tags == t
        toks[m] = rng.randint(lo[t], hi[t], m.sum())
    return toks.astype(np.float32), tags


def log_sum_exp(x, axis):
    m = nd.max(x, axis=axis, keepdims=True)
    return nd.squeeze(m, axis=axis) + nd.log(
        nd.sum(nd.exp(nd.broadcast_sub(x, m)), axis=axis))


class BiLSTMCRF(gluon.Block):
    """recurrent=True: BiLSTM encoder (the reference architecture).
    recurrent=False: per-token MLP — the emission-only ablation used as
    the baseline, which by construction cannot model tag TRANSITIONS."""

    def __init__(self, hidden=24, embed=16, recurrent=True, **kw):
        super().__init__(**kw)
        self.embed = nn.Embedding(VOCAB, embed)
        self.lstm = rnn.LSTM(hidden, bidirectional=True) if recurrent \
            else nn.Dense(2 * hidden, flatten=False, activation="relu")
        self.proj = nn.Dense(TAGS, flatten=False)
        self.transitions = self.params.get("transitions",
                                           shape=(TAGS, TAGS), init="zeros")

    def emissions(self, toks):
        """(B, T) tokens -> (T, B, K) emission scores."""
        e = nd.transpose(self.embed(toks), axes=(1, 0, 2))  # (T, B, E)
        return self.proj(self.lstm(e))                      # (T, B, K)

    def neg_log_likelihood(self, toks, tags_np):
        """-log p(gold | tokens) = logZ - score(gold), batched."""
        emit = self.emissions(toks)
        trans = self.transitions.data()
        T, B, K = emit.shape
        # forward recursion in log space
        alpha = emit[0]                                      # (B, K)
        for t in range(1, T):
            # alpha[b, j] = lse_i(alpha[b, i] + trans[i, j]) + emit[t, b, j]
            scores = nd.broadcast_add(nd.expand_dims(alpha, 2),
                                      nd.expand_dims(trans, 0))
            alpha = log_sum_exp(scores, axis=1) + emit[t]
        logz = log_sum_exp(alpha, axis=1)                    # (B,)
        # gold-path score via one-hot gathers (stays on the tape)
        oh = np.eye(K, dtype=np.float32)[tags_np]            # (B, T, K)
        oh_nd = nd.array(oh)
        emit_bt = nd.transpose(emit, axes=(1, 0, 2))              # (B, T, K)
        gold_emit = nd.sum(emit_bt * oh_nd, axis=(1, 2))
        pair = oh[:, :-1, :, None] * oh[:, 1:, None, :]      # (B,T-1,K,K)
        gold_trans = nd.sum(nd.broadcast_mul(
            nd.array(pair.sum(axis=1)), nd.expand_dims(trans, 0)),
            axis=(1, 2))
        return nd.mean(logz - (gold_emit + gold_trans))

    def viterbi(self, toks):
        emit = self.emissions(toks).asnumpy()        # (T, B, K)
        trans = self.transitions.data().asnumpy()    # (K, K)
        T, B, K = emit.shape
        delta = emit[0]                              # (B, K)
        back = np.zeros((T, B, K), dtype=np.int64)
        for t in range(1, T):
            scores = delta[:, :, None] + trans[None]  # (B, K, K)
            back[t] = scores.argmax(axis=1)
            delta = scores.max(axis=1) + emit[t]
        path = np.zeros((B, T), dtype=np.int64)
        path[:, -1] = delta.argmax(axis=1)
        for t in range(T - 1, 0, -1):
            path[:, t - 1] = back[t, np.arange(B), path[:, t]]
        return path


def violations(paths):
    """Rate of BIO-grammar breaks: I at start or I after O."""
    start_bad = (paths[:, 0] == 2).sum()
    after_o = np.logical_and(paths[:, :-1] == 0, paths[:, 1:] == 2).sum()
    return float(start_bad + after_o) / paths.size


def i_tag_f1(paths, tags):
    """F1 on the I tag — the class only sequence structure can find
    (its tokens are drawn from the same bucket as O's)."""
    tp = np.logical_and(paths == 2, tags == 2).sum()
    fp = np.logical_and(paths == 2, tags != 2).sum()
    fn = np.logical_and(paths != 2, tags == 2).sum()
    if tp == 0:
        return 0.0
    prec, rec = tp / (tp + fp), tp / (tp + fn)
    return float(2 * prec * rec / (prec + rec))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=300)
    parser.add_argument("--batch-size", type=int, default=32)
    args = parser.parse_args()

    rng = np.random.RandomState(9)
    model = BiLSTMCRF()
    model.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(model.collect_params(), "adam",
                            {"learning_rate": 0.01})

    # emission-only ablation: per-token classifier, no structure model
    base = BiLSTMCRF(recurrent=False)
    base.initialize(mx.init.Xavier())
    base_tr = gluon.Trainer(base.collect_params(), "adam",
                            {"learning_rate": 0.01})
    sce = gluon.loss.SoftmaxCrossEntropyLoss()

    for step in range(args.steps):
        toks, tags = synthetic_batch(rng, args.batch_size)
        toks_nd = nd.array(toks)
        with autograd.record():
            loss = model.neg_log_likelihood(toks_nd, tags)
        loss.backward()
        trainer.step(1)
        with autograd.record():
            emit = base.emissions(toks_nd)            # (T, B, K)
            bloss = sce(nd.transpose(emit, axes=(1, 0, 2)),
                        nd.array(tags.astype(np.float32)))
        bloss.backward()
        base_tr.step(args.batch_size)
        if step % 50 == 0:
            print("step %d crf nll %.3f baseline ce %.3f"
                  % (step, float(loss.asnumpy()[0]),
                     float(nd.mean(bloss).asnumpy()[0])))

    ev = np.random.RandomState(123)
    toks, tags = synthetic_batch(ev, 256)
    crf_path = model.viterbi(nd.array(toks))
    base_path = base.emissions(nd.array(toks)).asnumpy() \
        .transpose(1, 0, 2).argmax(axis=2)
    crf_acc = float((crf_path == tags).mean())
    base_acc = float((base_path == tags).mean())
    crf_f1, base_f1 = i_tag_f1(crf_path, tags), i_tag_f1(base_path, tags)
    crf_bad, base_bad = violations(crf_path), violations(base_path)
    print("accuracy: crf %.3f baseline %.3f | I-tag F1: crf %.3f "
          "baseline %.3f | grammar violations: crf %.4f baseline %.4f"
          % (crf_acc, base_acc, crf_f1, base_f1, crf_bad, base_bad))
    return crf_acc, base_acc, crf_f1, base_f1, crf_bad


if __name__ == "__main__":
    crf_acc, base_acc, crf_f1, base_f1, crf_bad = main()
    ok = crf_acc >= base_acc and crf_f1 > base_f1 + 0.15 and crf_f1 > 0.5 \
        and crf_bad < 0.01
    if not ok:
        sys.exit("FAIL: crf acc %.3f f1 %.3f bad %.4f vs baseline acc %.3f "
                 "f1 %.3f" % (crf_acc, crf_f1, crf_bad, base_acc, base_f1))
    print("LSTM_CRF OK")
