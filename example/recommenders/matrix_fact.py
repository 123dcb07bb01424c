"""Matrix-factorization recommender (reference:
example/recommenders/matrix_fact.py — user/item embeddings whose dot
product predicts the rating, trained with squared loss on observed
(user, item, rating) triples from MovieLens).

Zero-egress version: a synthetic low-rank-plus-noise ratings matrix
(ground-truth rank 4) with 45% of entries observed.  Same architecture
through the symbolic path: two Embedding tables -> elementwise product ->
sum -> LinearRegressionOutput.  The test asserts held-out RMSE recovers
the noise floor (far below the predict-the-mean baseline), i.e. the
factorization actually generalizes to unobserved pairs rather than
memorizing.

Run (CPU smoke):  JAX_PLATFORMS=cpu python example/recommenders/matrix_fact.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import nd, sym

N_USERS, N_ITEMS, TRUE_RANK = 60, 80, 4


def synthetic_ratings(rng, observed_frac=0.45, noise=0.1):
    u = rng.normal(0, 1, (N_USERS, TRUE_RANK)) / TRUE_RANK ** 0.5
    v = rng.normal(0, 1, (N_ITEMS, TRUE_RANK)) / TRUE_RANK ** 0.5
    full = u @ v.T
    mask = rng.rand(N_USERS, N_ITEMS) < observed_frac
    users, items = np.nonzero(mask)
    ratings = full[users, items] + rng.normal(0, noise, users.size)
    order = rng.permutation(users.size)
    users, items, ratings = users[order], items[order], ratings[order]
    n_test = users.size // 5
    train = (users[n_test:], items[n_test:], ratings[n_test:])
    test = (users[:n_test], items[:n_test], ratings[:n_test])
    return train, test


def get_mf(rank):
    """user-embed . item-embed -> rating (reference matrix_fact.py)."""
    user = sym.Variable("user")
    item = sym.Variable("item")
    u = sym.Embedding(user, name="user_embed", input_dim=N_USERS,
                      output_dim=rank)
    v = sym.Embedding(item, name="item_embed", input_dim=N_ITEMS,
                      output_dim=rank)
    pred = sym.sum(u * v, axis=1)
    return sym.LinearRegressionOutput(pred, name="lro")


def rmse(mod, users, items, ratings, batch):
    """Evaluate every triple: the tail partial batch is padded up to the
    bound batch size (the executor's shape is fixed) and the padding rows
    are sliced off the prediction before scoring."""
    errs = []
    for i in range(0, users.size, batch):
        u, it = users[i:i + batch], items[i:i + batch]
        valid = u.size
        if valid < batch:
            pad = batch - valid
            u = np.concatenate([u, np.repeat(u[-1:], pad)])
            it = np.concatenate([it, np.repeat(it[-1:], pad)])
        db = mx.io.DataBatch(data=[nd.array(u.astype(np.float32)),
                                   nd.array(it.astype(np.float32))])
        mod.forward(db, is_train=False)
        pred = mod.get_outputs()[0].asnumpy()[:valid]
        errs.append((pred - ratings[i:i + valid]) ** 2)
    return float(np.sqrt(np.mean(np.concatenate(errs))))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--rank", type=int, default=6)
    parser.add_argument("--batch-size", type=int, default=128)
    parser.add_argument("--epochs", type=int, default=80)
    parser.add_argument("--lr", type=float, default=0.02)
    args = parser.parse_args()

    rng = np.random.RandomState(5)
    (tu, ti, tr), (vu, vi, vr) = synthetic_ratings(rng)
    print("train triples %d, test triples %d" % (tu.size, vu.size))

    mod = mx.mod.Module(get_mf(args.rank),
                        data_names=("user", "item"), label_names=("lro_label",))
    mod.bind(data_shapes=[("user", (args.batch_size,)),
                          ("item", (args.batch_size,))],
             label_shapes=[("lro_label", (args.batch_size,))])
    mod.init_params(mx.init.Normal(0.1))
    mod.init_optimizer(optimizer="adam",
                       optimizer_params={"learning_rate": args.lr, "wd": 1e-4})

    baseline = float(np.sqrt(np.mean((vr - tr.mean()) ** 2)))
    for epoch in range(args.epochs):
        perm = rng.permutation(tu.size)
        for i in range(0, tu.size - args.batch_size + 1, args.batch_size):
            j = perm[i:i + args.batch_size]
            batch = mx.io.DataBatch(
                data=[nd.array(tu[j].astype(np.float32)),
                      nd.array(ti[j].astype(np.float32))],
                label=[nd.array(tr[j].astype(np.float32))])
            mod.forward(batch, is_train=True)
            mod.backward()
            mod.update()
        if epoch % 10 == 0:
            print("epoch %d test RMSE %.4f (baseline %.4f)"
                  % (epoch, rmse(mod, vu, vi, vr, args.batch_size), baseline))
    final = rmse(mod, vu, vi, vr, args.batch_size)
    print("final test RMSE %.4f vs predict-mean baseline %.4f"
          % (final, baseline))
    return final, baseline


if __name__ == "__main__":
    final, baseline = main()
    if not (final < 0.5 * baseline and final < 0.3):
        sys.exit("FAIL: factorization did not generalize (%.4f vs %.4f)"
                 % (final, baseline))
    print("MATRIX_FACT OK")
