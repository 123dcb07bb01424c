"""LSTNet multivariate time-series forecasting (reference:
example/multivariate_time_series/src/lstnet.py — Lai et al. 2018 on the
electricity dataset: Conv1D feature extraction over a lookback window,
GRU recurrent state, a skip-GRU sampling every ``seasonal period``-th
step, and a parallel autoregressive linear highway summed into the
forecast).

Zero-egress version: D=8 correlated series, each a different phase/
frequency mix of two shared seasonal oscillators plus noise — so the
conv+GRU path must learn cross-series structure and the AR highway the
per-series linear continuation.  Scored by RSE (root relative squared
error, the reference's metric.py) on a held-out window: the LSTNet
forecast must beat the naive last-value predictor decisively.

Run (CPU smoke):  JAX_PLATFORMS=cpu python example/multivariate_time_series/lstnet.py
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

D = 8            # series
WINDOW = 48      # lookback
SKIP = 12        # seasonal period for the skip connection
HORIZON = 3      # steps ahead


def make_series(rng, length):
    t = np.arange(length)
    s1 = np.sin(2 * np.pi * t / SKIP)
    s2 = np.sin(2 * np.pi * t / (SKIP * 4))
    phases = rng.uniform(0, 2 * np.pi, D)
    w1 = rng.uniform(0.5, 1.0, D)
    w2 = rng.uniform(0.2, 0.8, D)
    x = (w1[:, None] * np.sin(2 * np.pi * t[None] / SKIP + phases[:, None])
         + w2[:, None] * s2[None]
         + 0.1 * rng.normal(0, 1, (D, length)))
    return x.T.astype(np.float32)        # (T, D)


def windows(series, rng, batch):
    T = len(series)
    idx = rng.randint(0, T - WINDOW - HORIZON, batch)
    x = np.stack([series[i:i + WINDOW] for i in idx])          # (N, W, D)
    y = np.stack([series[i + WINDOW + HORIZON - 1] for i in idx])  # (N, D)
    return x.astype(np.float32), y.astype(np.float32)


class LSTNet(gluon.HybridBlock):
    """Conv1D -> GRU + skip-GRU -> dense, plus the AR linear highway."""

    def __init__(self, conv_channels=32, rnn_hidden=32, skip_hidden=8,
                 ar_window=8, kernel=6, **kwargs):
        super().__init__(**kwargs)
        self._ar_window = ar_window
        self._kernel = kernel
        self._conv_steps = WINDOW - kernel + 1
        self._skip_steps = self._conv_steps // SKIP
        self._skip_hidden = skip_hidden
        with self.name_scope():
            self.conv = nn.Conv1D(conv_channels, kernel,
                                  activation="relu")   # over time, NCW
            self.gru = rnn.GRUCell(rnn_hidden)
            self.skip_gru = rnn.GRUCell(skip_hidden)
            self.out = nn.Dense(D)
            self.ar = nn.Dense(1, flatten=False)

    def hybrid_forward(self, F, x):                    # x: (N, W, D)
        c = self.conv(x.transpose((0, 2, 1)))          # (N, C, W-k+1)
        seq = c.transpose((0, 2, 1))                   # (N, steps, C)
        outs, _ = self.gru.unroll(self._conv_steps, seq, layout="NTC",
                                  merge_outputs=False)
        last = outs[-1]                                # (N, rnn_hidden)
        # skip recurrence: every SKIP-th conv step, so the recurrent state
        # carries exactly one seasonal period per update; one skip-GRU
        # scan per phase offset, final states concatenated (lstnet.py's
        # skip-RNN reshape expressed as explicit phase scans)
        n_skip = self._skip_steps
        trimmed = outs[-n_skip * SKIP:]
        skip_feats = []
        for offset in range(SKIP):
            sub = F.stack(*trimmed[offset::SKIP], axis=1)  # (N, n_skip, C)
            sub_outs, _ = self.skip_gru.unroll(n_skip, sub, layout="NTC",
                                               merge_outputs=False)
            skip_feats.append(sub_outs[-1])
        skip_cat = F.concat(*skip_feats, dim=1)        # (N, SKIP*skip_hidden)
        pred = self.out(F.concat(last, skip_cat, dim=1))   # (N, D)
        # AR highway: per-series linear map of the last ar_window values
        tail = x.slice_axis(axis=1, begin=WINDOW - self._ar_window,
                            end=WINDOW)                # (N, ar, D)
        ar_in = tail.transpose((0, 2, 1))              # (N, D, ar)
        ar_pred = self.ar(ar_in).reshape((0, D))       # (N, D)
        return pred + ar_pred


def rse(pred, true):
    """Root relative squared error (reference src/metrics.py)."""
    return float(np.sqrt(((pred - true) ** 2).sum()
                         / ((true - true.mean()) ** 2).sum()))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.003)
    args = ap.parse_args(argv)

    np.random.seed(0)
    rng = np.random.RandomState(0)
    series = make_series(rng, 2000)
    train, held = series[:1600], series[1600:]

    net = LSTNet()
    net.initialize(mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": args.lr})
    l2 = gluon.loss.L2Loss()

    ev_rng = np.random.RandomState(99)
    hx, hy = windows(held, ev_rng, 256)
    naive = rse(hx[:, -1], hy)           # last-value predictor
    for step in range(args.steps):
        x, y = windows(train, rng, args.batch_size)
        xb = nd.array(x)
        with autograd.record():
            loss = l2(net(xb), nd.array(y)).mean()
        loss.backward()
        trainer.step(args.batch_size)
        if step % 100 == 0:
            print("step %d mse %.4f" % (
                step, float(loss.asnumpy().ravel()[0])), flush=True)

    pred = net(nd.array(hx)).asnumpy()
    model_rse = rse(pred, hy)
    print("held-out RSE: %.3f (naive last-value %.3f)" % (model_rse, naive))
    return naive, model_rse


if __name__ == "__main__":
    main()
