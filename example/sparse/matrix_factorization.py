#!/usr/bin/env python
"""Matrix factorization with embedding tables (reference:
example/sparse/matrix_factorization/train.py).

The reference pulls row_sparse weights on demand from the parameter server
(kvstore PullRowSparse); on TPU the embedding tables live in HBM and XLA's
gather serves lookups, so the per-batch "pull" disappears into the compiled
step."""
from __future__ import annotations

import argparse
import logging
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import gluon, autograd, nd
from mxnet_tpu.gluon import nn


class MFBlock(gluon.HybridBlock):
    def __init__(self, max_users, max_items, factor_size, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.user_emb = nn.Embedding(max_users, factor_size)
            self.item_emb = nn.Embedding(max_items, factor_size)

    def forward(self, users, items):
        a = self.user_emb(users)
        b = self.item_emb(items)
        return (a * b).sum(axis=-1)


def synthetic_ratings(num_users=200, num_items=100, n=5000, rank=4, seed=0):
    rng = np.random.RandomState(seed)
    U = rng.normal(0, 1, (num_users, rank))
    V = rng.normal(0, 1, (num_items, rank))
    users = rng.randint(0, num_users, n)
    items = rng.randint(0, num_items, n)
    ratings = (U[users] * V[items]).sum(-1) + rng.normal(0, 0.1, n)
    return users.astype(np.int32), items.astype(np.int32), \
        ratings.astype(np.float32)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--num-epochs", type=int, default=10)
    parser.add_argument("--batch-size", type=int, default=256)
    parser.add_argument("--factor-size", type=int, default=8)
    parser.add_argument("--lr", type=float, default=0.05)
    parser.add_argument("--kv-store", default="device")
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO)

    users, items, ratings = synthetic_ratings()
    net = MFBlock(200, 100, args.factor_size)
    net.initialize(mx.init.Normal(0.1))
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": args.lr}, kvstore=args.kv_store)
    loss_fn = gluon.loss.L2Loss()
    n = len(ratings)
    for epoch in range(args.num_epochs):
        perm = np.random.permutation(n)
        total = 0.0
        for i in range(0, n - args.batch_size + 1, args.batch_size):
            sel = perm[i:i + args.batch_size]
            u = nd.array(users[sel], dtype="int32")
            it = nd.array(items[sel], dtype="int32")
            r = nd.array(ratings[sel])
            with autograd.record():
                pred = net(u, it)
                loss = loss_fn(pred, r)
            loss.backward()
            trainer.step(args.batch_size)
            total += float(loss.mean().asscalar())
        logging.info("Epoch %d loss %.4f", epoch, total / (n // args.batch_size))
    print("final loss:", total / (n // args.batch_size))


if __name__ == "__main__":
    main()
