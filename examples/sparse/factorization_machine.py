#!/usr/bin/env python
"""Factorization machine on the real row_sparse path.

Parity target: `example/sparse/factorization_machine/train.py` +
`model.py` in the reference — the FM formulation

    y = w0 + sum_i x_i w_i
        + 0.5 * (||sum_i x_i v_i||^2 - sum_i x_i^2 ||v_i||^2)

with row_sparse linear weights `w` (num_features, 1) and factor matrix
`v` (num_features, factor_size), trained through the kvstore sparse
machinery: workers `row_sparse_pull` ONLY the rows the batch touches,
push row_sparse gradients, and the optimizer on the store updates just
those rows. Dense (num_features x factor_size) traffic never happens —
the point of the reference example, preserved here.

LibSVM data via --data-train (mx.io.LibSVMIter, reference data path);
without it a synthetic planted-FM dataset is generated (zero-egress
environment), and the script asserts the model actually learns it.

    python examples/sparse/factorization_machine.py --num-epoch 8
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np


def synthetic_fm_data(num_samples, num_features, factor_size, nnz, seed=0):
    """Sparse rows labeled by a planted FM (linear + true interaction
    structure), so only a model with factor terms separates it well."""
    rs = np.random.RandomState(seed)
    true_w = 0.5 * rs.randn(num_features).astype(np.float32)
    true_v = 0.8 * rs.randn(num_features, factor_size).astype(np.float32)
    rows, vals, labels = [], [], []
    for _ in range(num_samples):
        idx = rs.choice(num_features, nnz, replace=False)
        x = rs.rand(nnz).astype(np.float32)
        lin = float((true_w[idx] * x).sum())
        s = (x[:, None] * true_v[idx]).sum(0)
        inter = 0.5 * float((s * s).sum() -
                            ((x ** 2)[:, None] * true_v[idx] ** 2).sum())
        rows.append(idx)
        vals.append(x)
        labels.append(1.0 if lin + inter > 0 else 0.0)
    return np.stack(rows), np.stack(vals), np.asarray(labels, np.float32)


def main(argv=None):
    p = argparse.ArgumentParser(
        description="factorization machine (row_sparse)",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    p.add_argument("--data-train", type=str, default=None,
                   help="training set in LibSVM format")
    p.add_argument("--num-epoch", type=int, default=15)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--input-size", type=int, default=2000,
                   help="number of sparse features")
    p.add_argument("--factor-size", type=int, default=8,
                   help="latent factor dimension")
    p.add_argument("--lr", type=float, default=1.0)
    p.add_argument("--kvstore", type=str, default="local")
    p.add_argument("--num-examples", type=int, default=2000)
    p.add_argument("--nnz", type=int, default=10)
    args = p.parse_args(argv)

    import mxnet_tpu as mx
    from mxnet_tpu.ndarray.sparse import row_sparse_array

    nf, fs = args.input_size, args.factor_size

    if args.data_train and os.path.exists(args.data_train):
        it = mx.io.LibSVMIter(data_libsvm=args.data_train,
                              data_shape=(nf,),
                              batch_size=args.batch_size)
        rows, vals, labels = [], [], []
        for batch in it:
            csr = batch.data[0]
            dense = csr.asnumpy() if hasattr(csr, "asnumpy") else csr
            for r, y in zip(np.asarray(dense),
                            batch.label[0].asnumpy()):
                idx = np.nonzero(r)[0][:args.nnz]
                if len(idx) < args.nnz:  # pad to fixed nnz
                    idx = np.pad(idx, (0, args.nnz - len(idx)))
                rows.append(idx)
                vals.append(r[idx].astype(np.float32))
                labels.append(float(y))
        rows, vals = np.stack(rows), np.stack(vals)
        labels = np.asarray(labels, np.float32)
    else:
        rows, vals, labels = synthetic_fm_data(
            args.num_examples, nf, fs, args.nnz)

    n = rows.shape[0]
    nbatch = n // args.batch_size

    rs = np.random.RandomState(1)
    kv = mx.kv.create(args.kvstore)
    # row_sparse-initialized weights live ON the store (reference: the
    # Module pulls w/v by batch row ids, optimizer runs on the kvstore)
    kv.init("w", mx.nd.array(0.01 * rs.randn(nf, 1).astype(np.float32)))
    kv.init("v", mx.nd.array(0.1 * rs.randn(nf, fs).astype(np.float32)))
    kv.init("w0", mx.nd.zeros((1,)))
    kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=args.lr))

    def pull_rows(key, uniq, width):
        out = row_sparse_array(
            (np.zeros((len(uniq), width), np.float32),
             uniq.astype(np.int64)), shape=(nf, width))
        kv.row_sparse_pull(key, out=out, row_ids=mx.nd.array(uniq))
        return out.data.asnumpy()

    acc = 0.0
    for epoch in range(args.num_epoch):
        perm = np.random.RandomState(epoch).permutation(n)
        total_loss, correct = 0.0, 0
        for b in range(nbatch):
            sel = perm[b * args.batch_size:(b + 1) * args.batch_size]
            idx, x, y = rows[sel], vals[sel], labels[sel]
            uniq, inv = np.unique(idx, return_inverse=True)
            inv = inv.reshape(idx.shape)
            # pull ONLY touched rows of w and v
            w_rows = pull_rows("w", uniq, 1)[:, 0]
            v_rows = pull_rows("v", uniq, fs)
            w0 = float(kv.pull_single("w0").asnumpy()[0]) \
                if hasattr(kv, "pull_single") else None
            if w0 is None:
                out0 = mx.nd.zeros((1,))
                kv.pull("w0", out=out0)
                w0 = float(out0.asnumpy()[0])

            wb = w_rows[inv]                    # (B, nnz)
            vb = v_rows[inv]                    # (B, nnz, fs)
            s = (x[:, :, None] * vb).sum(1)     # (B, fs)
            lin = (x * wb).sum(1)
            inter = 0.5 * ((s * s).sum(1) -
                           ((x ** 2)[:, :, None] * vb ** 2).sum((1, 2)))
            logits = w0 + lin + inter
            prob = 1.0 / (1.0 + np.exp(-logits))
            total_loss += float(-np.mean(
                y * np.log(prob + 1e-8) +
                (1 - y) * np.log(1 - prob + 1e-8)))
            correct += int(((prob > 0.5) == (y > 0.5)).sum())

            # FM gradients, accumulated onto the TOUCHED rows only
            g = (prob - y) / len(sel)           # (B,)
            gw = np.zeros((len(uniq),), np.float32)
            np.add.at(gw, inv.reshape(-1), (g[:, None] * x).reshape(-1))
            gv = np.zeros((len(uniq), fs), np.float32)
            gv_rows = (g[:, None, None] *
                       (x[:, :, None] * s[:, None, :] -
                        (x ** 2)[:, :, None] * vb))
            np.add.at(gv, inv.reshape(-1), gv_rows.reshape(-1, fs))
            kv.push("w", row_sparse_array(
                (gw[:, None], uniq.astype(np.int64)), shape=(nf, 1)))
            kv.push("v", row_sparse_array(
                (gv, uniq.astype(np.int64)), shape=(nf, fs)))
            kv.push("w0", mx.nd.array(np.array([g.sum()], np.float32)))
        acc = correct / (nbatch * args.batch_size)
        print(f"Epoch[{epoch}] Train-accuracy={acc:.6f} "
              f"Train-logloss={total_loss / nbatch:.6f}")
    return acc


if __name__ == "__main__":
    final = main()
    assert final > 0.75, f"factorization machine failed to learn ({final})"
