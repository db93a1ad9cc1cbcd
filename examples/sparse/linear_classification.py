#!/usr/bin/env python
"""Sparse linear classification with row_sparse gradients.

Parity: example/sparse/linear_classification/ in the reference — a linear
model over high-dimensional sparse features. The weight gradient is
row_sparse (only the rows the batch touches carry values), the optimizer
runs ON the kvstore (update_on_kvstore, sparse SGD touches only those
rows), and workers pull only the rows they need via `row_sparse_pull` —
dense weight traffic never happens.

Synthetic sparse data stands in for the criteo-style dataset (zero-egress
environment); the mechanics are the reference's.

    python examples/sparse/linear_classification.py --num-epoch 5
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np


def synthetic_sparse_data(num_samples=2000, num_features=1000, nnz=12,
                          seed=0):
    """Random sparse rows + a planted linear separator."""
    rs = np.random.RandomState(seed)
    true_w = rs.randn(num_features).astype(np.float32)
    rows, vals, labels = [], [], []
    for _ in range(num_samples):
        idx = rs.choice(num_features, nnz, replace=False)
        v = rs.rand(nnz).astype(np.float32)
        rows.append(idx)
        vals.append(v)
        labels.append(1.0 if (true_w[idx] * v).sum() > 0 else 0.0)
    return np.stack(rows), np.stack(vals), np.asarray(labels, np.float32)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--num-epoch", type=int, default=8)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--num-features", type=int, default=1000)
    p.add_argument("--lr", type=float, default=4.0)
    p.add_argument("--kvstore", type=str, default="local")
    args = p.parse_args(argv)

    import mxnet_tpu as mx

    from mxnet_tpu.ndarray.sparse import row_sparse_array

    rows, vals, labels = synthetic_sparse_data(
        num_features=args.num_features)
    n = rows.shape[0]
    nbatch = n // args.batch_size

    kv = mx.kv.create(args.kvstore)
    kv.init("weight", mx.nd.zeros((args.num_features, 1)))
    kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=args.lr))

    acc = 0.0
    for epoch in range(args.num_epoch):
        perm = np.random.RandomState(epoch).permutation(n)
        total_loss, correct = 0.0, 0
        for b in range(nbatch):
            sel = perm[b * args.batch_size:(b + 1) * args.batch_size]
            idx, val, y = rows[sel], vals[sel], labels[sel]
            uniq = np.unique(idx)
            # pull ONLY the touched rows (row_sparse_pull parity)
            pulled = row_sparse_array(
                (np.zeros((len(uniq), 1), np.float32), uniq.astype(np.int64)),
                shape=(args.num_features, 1))
            kv.row_sparse_pull("weight", out=pulled,
                               row_ids=mx.nd.array(uniq))
            w = np.zeros((args.num_features,), np.float32)
            w[np.asarray(pulled.indices.asnumpy(), np.int64)] = \
                pulled.data.asnumpy()[:, 0]
            # logistic forward + loss
            logits = (val * w[idx]).sum(axis=1)
            prob = 1.0 / (1.0 + np.exp(-logits))
            total_loss += float(-np.mean(
                y * np.log(prob + 1e-8) +
                (1 - y) * np.log(1 - prob + 1e-8)))
            correct += int(((prob > 0.5) == (y > 0.5)).sum())
            # row_sparse gradient: only touched rows carry values
            gscale = (prob - y) / len(sel)
            gw = np.zeros((args.num_features,), np.float32)
            np.add.at(gw, idx.reshape(-1),
                      (gscale[:, None] * val).reshape(-1))
            grad = row_sparse_array(
                (gw[uniq][:, None], uniq.astype(np.int64)),
                shape=(args.num_features, 1))
            kv.push("weight", grad)  # sparse SGD applies on the store
        acc = correct / (nbatch * args.batch_size)
        print(f"Epoch[{epoch}] Train-accuracy={acc:.6f}")
        print(f"Epoch[{epoch}] Train-logloss={total_loss / nbatch:.6f}")
    return acc


if __name__ == "__main__":
    final = main()
    assert final > 0.8, f"sparse linear model failed to learn ({final})"
