#!/usr/bin/env python
"""Gluon-imperative MNIST training (parity: example/gluon/mnist/mnist.py —
the canonical imperative-mode demo; `--hybridize` flips it to compiled
mode with zero model changes).

Uses the real MNIST via mx.io.MNISTIter when the files are present,
else a synthetic drop-in (zero-egress environment).

    python examples/gluon/mnist.py --epochs 3 --hybridize
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np


def load_data(batch_size):
    import mxnet_tpu as mx

    path = os.environ.get("MNIST_PATH", "data")
    img = os.path.join(path, "train-images-idx3-ubyte")
    if os.path.exists(img):
        train = mx.io.MNISTIter(image=img,
                                label=os.path.join(
                                    path, "train-labels-idx1-ubyte"),
                                batch_size=batch_size, shuffle=True)
        return train, None
    # synthetic stand-in: 4 gaussian blobs as "digits" 0-3
    rs = np.random.RandomState(0)
    n, classes = 2048, 4
    y = rs.randint(0, classes, n)
    x = rs.rand(n, 1, 28, 28).astype(np.float32) * 0.1
    for i in range(n):
        r, c = divmod(int(y[i]), 2)
        x[i, 0, r * 14:(r + 1) * 14, c * 14:(c + 1) * 14] += 0.8
    return mx.io.NDArrayIter(x, y.astype(np.float32),
                             batch_size=batch_size, shuffle=True), classes


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--hybridize", action="store_true")
    args = p.parse_args(argv)

    import mxnet_tpu as mx
    from mxnet_tpu import gluon

    train_data, classes = load_data(args.batch_size)
    net = gluon.nn.Sequential() if not args.hybridize \
        else gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(128, activation="relu"))
        net.add(gluon.nn.Dense(64, activation="relu"))
        net.add(gluon.nn.Dense(classes or 10))
    net.initialize(mx.init.Xavier())
    if args.hybridize:
        net.hybridize()

    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    metric = mx.metric.Accuracy()

    acc = 0.0
    for epoch in range(args.epochs):
        train_data.reset()
        metric.reset()
        for batch in train_data:
            data, label = batch.data[0], batch.label[0]
            with mx.autograd.record():
                out = net(data.reshape((data.shape[0], -1)))
                loss = loss_fn(out, label)
            loss.backward()
            trainer.step(data.shape[0])
            metric.update([label], [out])
        name, acc = metric.get()
        print(f"Epoch[{epoch}] Train-{name}={acc:.6f}")
    return acc


if __name__ == "__main__":
    final = main()
    assert final > 0.9, f"failed to learn ({final})"
