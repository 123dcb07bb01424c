#!/usr/bin/env python
"""Transformer language model (reference: example/gluon/word_language_model +
the transformer attention ops in src/operator/contrib/transformer.cc).

TPU-native: attention runs through the fused flash-attention op (Pallas kernel
on TPU, ops/pallas_ops.py); for sequences sharded over an 'sp' mesh axis the
same model composes with parallel.ring_attention."""
from __future__ import annotations

import argparse
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import gluon, autograd, nd
from mxnet_tpu.gluon import nn
from mxnet_tpu.ndarray import invoke


class MultiHeadSelfAttention(gluon.HybridBlock):
    def __init__(self, dim, heads, **kwargs):
        super().__init__(**kwargs)
        assert dim % heads == 0
        self._heads = heads
        self._dim = dim
        with self.name_scope():
            self.qkv = nn.Dense(3 * dim, use_bias=False, flatten=False)
            self.proj = nn.Dense(dim, use_bias=False, flatten=False)

    def forward(self, x):
        B, T, C = x.shape
        H = self._heads
        qkv = self.qkv(x)                                  # (B, T, 3C)
        qkv = qkv.reshape((B, T, 3, H, C // H))
        q = qkv[:, :, 0].transpose((0, 2, 1, 3))           # (B, H, T, D)
        k = qkv[:, :, 1].transpose((0, 2, 1, 3))
        v = qkv[:, :, 2].transpose((0, 2, 1, 3))
        out = invoke("_contrib_flash_attention", [q, k, v], {"causal": True})
        out = out.transpose((0, 2, 1, 3)).reshape((B, T, C))
        return self.proj(out)


class TransformerBlock(gluon.HybridBlock):
    def __init__(self, dim, heads, hidden, dropout=0.1, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.ln1 = nn.LayerNorm(in_channels=dim)
            self.attn = MultiHeadSelfAttention(dim, heads)
            self.ln2 = nn.LayerNorm(in_channels=dim)
            self.ff1 = nn.Dense(hidden, activation="relu", flatten=False)
            self.ff2 = nn.Dense(dim, flatten=False)
            self.drop = nn.Dropout(dropout)

    def forward(self, x):
        x = x + self.drop(self.attn(self.ln1(x)))
        x = x + self.drop(self.ff2(self.ff1(self.ln2(x))))
        return x


class TransformerLM(gluon.HybridBlock):
    def __init__(self, vocab, dim=64, heads=4, hidden=128, layers=2,
                 max_len=512, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.embed = nn.Embedding(vocab, dim)
            self.pos = self.params.get("pos_weight", shape=(max_len, dim))
            self.blocks = nn.HybridSequential()
            for _ in range(layers):
                self.blocks.add(TransformerBlock(dim, heads, hidden))
            self.ln_f = nn.LayerNorm(in_channels=dim)
            self.head = nn.Dense(vocab, flatten=False)

    def forward(self, x):
        B, T = x.shape
        h = self.embed(x)
        pos = self.pos.data(h.context)[:T]
        h = h + pos.expand_dims(0)
        h = self.blocks(h)
        h = self.ln_f(h)
        return self.head(h)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--vocab", type=int, default=64)
    parser.add_argument("--dim", type=int, default=64)
    parser.add_argument("--heads", type=int, default=4)
    parser.add_argument("--layers", type=int, default=2)
    parser.add_argument("--seq-len", type=int, default=32)
    parser.add_argument("--batch-size", type=int, default=16)
    parser.add_argument("--num-epochs", type=int, default=3)
    parser.add_argument("--lr", type=float, default=3e-3)
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO)

    # synthetic copy-task-ish data: next token = (token + 1) % vocab
    rng = np.random.RandomState(0)
    data = rng.randint(0, args.vocab, (512, args.seq_len))
    target = (data + 1) % args.vocab

    net = TransformerLM(args.vocab, args.dim, args.heads, layers=args.layers)
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": args.lr})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    n = data.shape[0]
    for epoch in range(args.num_epochs):
        total, count = 0.0, 0
        for i in range(0, n, args.batch_size):
            x = nd.array(data[i:i + args.batch_size], dtype="int32")
            y = nd.array(target[i:i + args.batch_size])
            with autograd.record():
                logits = net(x)
                loss = loss_fn(logits, y)
            loss.backward()
            trainer.step(x.shape[0])
            total += float(loss.mean().asscalar())
            count += 1
        logging.info("Epoch %d loss %.4f", epoch, total / count)
    print("final loss:", total / count)


if __name__ == "__main__":
    main()
