#!/usr/bin/env python
"""Matrix factorization with row_sparse embeddings.

Parity target: `example/sparse/matrix_factorization/train.py` in the
reference — user/item latent factors stored as row_sparse weights; each
batch touches only its users'/items' rows, so workers `row_sparse_pull`
just those rows from the kvstore, push row_sparse gradients back, and
the optimizer on the store updates only touched rows (dense
(num_users x factor) traffic never happens).

Synthetic ratings from planted factors stand in for MovieLens
(zero-egress environment); the script asserts the factorization
recovers them (falling RMSE).

    python examples/sparse/matrix_factorization.py --num-epoch 10
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np


def synthetic_ratings(num_users, num_items, factor, num_ratings, seed=0):
    rs = np.random.RandomState(seed)
    true_u = rs.randn(num_users, factor).astype(np.float32)
    true_i = rs.randn(num_items, factor).astype(np.float32)
    users = rs.randint(0, num_users, num_ratings)
    items = rs.randint(0, num_items, num_ratings)
    ratings = (true_u[users] * true_i[items]).sum(1).astype(np.float32)
    return users, items, ratings


def main(argv=None):
    p = argparse.ArgumentParser(
        description="sparse matrix factorization",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    p.add_argument("--num-epoch", type=int, default=10)
    p.add_argument("--batch-size", type=int, default=128)
    p.add_argument("--num-users", type=int, default=500)
    p.add_argument("--num-items", type=int, default=400)
    p.add_argument("--factor-size", type=int, default=8)
    p.add_argument("--num-ratings", type=int, default=8000)
    p.add_argument("--lr", type=float, default=0.02)
    p.add_argument("--kvstore", type=str, default="local")
    args = p.parse_args(argv)

    import mxnet_tpu as mx
    from mxnet_tpu.ndarray.sparse import row_sparse_array

    nu, ni, fs = args.num_users, args.num_items, args.factor_size
    users, items, ratings = synthetic_ratings(nu, ni, fs,
                                              args.num_ratings)
    n = len(ratings)
    nbatch = n // args.batch_size

    rs = np.random.RandomState(1)
    kv = mx.kv.create(args.kvstore)
    kv.init("user", mx.nd.array(
        0.5 * rs.randn(nu, fs).astype(np.float32)))
    kv.init("item", mx.nd.array(
        0.5 * rs.randn(ni, fs).astype(np.float32)))
    kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=args.lr))

    def pull_rows(key, uniq, dim):
        out = row_sparse_array(
            (np.zeros((len(uniq), fs), np.float32),
             uniq.astype(np.int64)), shape=(dim, fs))
        kv.row_sparse_pull(key, out=out, row_ids=mx.nd.array(uniq))
        return out.data.asnumpy()

    rmse = None
    for epoch in range(args.num_epoch):
        perm = np.random.RandomState(epoch).permutation(n)
        sq = 0.0
        for b in range(nbatch):
            sel = perm[b * args.batch_size:(b + 1) * args.batch_size]
            u, i, y = users[sel], items[sel], ratings[sel]
            uu, uinv = np.unique(u, return_inverse=True)
            ii, iinv = np.unique(i, return_inverse=True)
            # pull ONLY the touched rows of each factor matrix
            U = pull_rows("user", uu, nu)
            V = pull_rows("item", ii, ni)
            pred = (U[uinv] * V[iinv]).sum(1)
            err = pred - y
            sq += float((err ** 2).sum())
            # per-rating step (classic SGD-MF): each touched row
            # accumulates its own ratings' gradients un-normalized
            g = err[:, None]
            gU = np.zeros_like(U)
            np.add.at(gU, uinv, g * V[iinv])
            gV = np.zeros_like(V)
            np.add.at(gV, iinv, g * U[uinv])
            kv.push("user", row_sparse_array(
                (gU, uu.astype(np.int64)), shape=(nu, fs)))
            kv.push("item", row_sparse_array(
                (gV, ii.astype(np.int64)), shape=(ni, fs)))
        rmse = float(np.sqrt(sq / (nbatch * args.batch_size)))
        print(f"Epoch[{epoch}] Train-RMSE={rmse:.6f}")
    return rmse


if __name__ == "__main__":
    final = main()
    assert final < 1.5, f"matrix factorization failed to learn ({final})"
