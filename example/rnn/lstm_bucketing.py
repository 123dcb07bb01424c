#!/usr/bin/env python
"""LSTM language model with bucketing (reference: example/rnn/bucketing/
lstm_bucketing.py; bucketing per
docs/faq/bucketing.md; each bucket is one XLA compilation)."""
from __future__ import annotations

import argparse
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import sym


def sym_gen_factory(num_hidden, num_layers, num_embed, vocab_size,
                    fused=True):
    """Build per-bucket symbols with the legacy mx.rnn cell API (reference
    example/rnn/bucketing/lstm_bucketing.py uses the same structure)."""
    def sym_gen(seq_len):
        data = sym.Variable("data")
        label = sym.Variable("softmax_label")
        embed = sym.Embedding(data, name="embed", input_dim=vocab_size,
                              output_dim=num_embed)
        if fused:
            cell = mx.rnn.FusedRNNCell(num_hidden, num_layers=num_layers,
                                       mode="lstm", prefix="lstm_")
        else:
            cell = mx.rnn.SequentialRNNCell()
            for i in range(num_layers):
                cell.add(mx.rnn.LSTMCell(num_hidden, prefix="lstm_l%d_" % i))
        outputs, _ = cell.unroll(seq_len, inputs=embed, layout="NTC",
                                 merge_outputs=True)
        pred = sym.Reshape(outputs, shape=(-1, num_hidden))
        pred = sym.FullyConnected(pred, name="pred", num_hidden=vocab_size)
        label_flat = sym.Reshape(label, shape=(-1,))
        out = sym.SoftmaxOutput(pred, label_flat, name="softmax")
        return out, ["data"], ["softmax_label"]
    return sym_gen


class BucketSeqIter(mx.io.DataIter):
    """Synthetic bucketed sequence iterator (stand-in for the PTB text
    pipeline; real data plugs in via the same DataBatch protocol)."""

    def __init__(self, buckets, batch_size, vocab_size, batches_per_bucket=8,
                 seed=0):
        super().__init__(batch_size)
        self.buckets = buckets
        self.vocab_size = vocab_size
        rng = np.random.RandomState(seed)
        self._batches = []
        for b in buckets:
            for _ in range(batches_per_bucket):
                data = rng.randint(1, vocab_size, (batch_size, b))
                label = np.roll(data, -1, axis=1)
                self._batches.append((b, data.astype(np.float32),
                                      label.astype(np.float32)))
        rng.shuffle(self._batches)
        self._idx = 0
        self.default_bucket_key = max(buckets)

    @property
    def provide_data(self):
        return [mx.io.DataDesc("data", (self.batch_size, self.default_bucket_key))]

    @property
    def provide_label(self):
        return [mx.io.DataDesc("softmax_label",
                               (self.batch_size, self.default_bucket_key))]

    def reset(self):
        self._idx = 0

    def next(self):
        if self._idx >= len(self._batches):
            raise StopIteration
        b, data, label = self._batches[self._idx]
        self._idx += 1
        from mxnet_tpu import nd
        return mx.io.DataBatch(
            data=[nd.array(data)], label=[nd.array(label)], pad=0,
            bucket_key=b,
            provide_data=[mx.io.DataDesc("data", (self.batch_size, b))],
            provide_label=[mx.io.DataDesc("softmax_label", (self.batch_size, b))])


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--num-hidden", type=int, default=64)
    parser.add_argument("--num-embed", type=int, default=32)
    parser.add_argument("--num-layers", type=int, default=1)
    parser.add_argument("--vocab-size", type=int, default=100)
    parser.add_argument("--batch-size", type=int, default=16)
    parser.add_argument("--num-epochs", type=int, default=2)
    parser.add_argument("--buckets", type=str, default="8,16,32")
    parser.add_argument("--kv-store", default="local")
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO)

    buckets = [int(b) for b in args.buckets.split(",")]
    train = BucketSeqIter(buckets, args.batch_size, args.vocab_size)
    model = mx.mod.BucketingModule(
        sym_gen_factory(args.num_hidden, args.num_layers, args.num_embed,
                        args.vocab_size),
        default_bucket_key=train.default_bucket_key,
        context=mx.cpu())
    model.fit(train, num_epoch=args.num_epochs, kvstore=args.kv_store,
              optimizer="adam", optimizer_params={"learning_rate": 0.01},
              eval_metric=mx.metric.Perplexity(ignore_label=None),
              initializer=mx.init.Xavier())
    print("done")


if __name__ == "__main__":
    main()
