#!/usr/bin/env python
"""Fine-tune a pretrained checkpoint on a new label set.

Parity: example/image-classification/fine-tune.py — load a saved
(symbol, params) checkpoint, truncate at the penultimate layer
(`get_internals`), attach a fresh classifier head, and train with the
backbone initialized from the checkpoint.

Self-contained demo: trains a small CNN on synthetic "task A", saves the
checkpoint, then fine-tunes it on "task B" with a different class count.

    python examples/image_classification/fine_tune.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np


def get_fine_tune_model(symbol, arg_params, num_classes,
                        layer_name="flatten"):
    """parity: fine-tune.py get_fine_tune_model — truncate + new head."""
    import mxnet_tpu as mx

    all_layers = symbol.get_internals()
    net = all_layers[layer_name + "_output"]
    net = mx.sym.FullyConnected(net, num_hidden=num_classes, name="fc_new")
    net = mx.sym.SoftmaxOutput(net, mx.sym.var("softmax_label"),
                               name="softmax")
    wanted = set(net.list_arguments())
    new_args = {k: v for k, v in arg_params.items() if k in wanted}
    return net, new_args


def base_net(num_classes):
    import mxnet_tpu as mx

    data = mx.sym.var("data")
    net = mx.sym.Convolution(data, kernel=(3, 3), pad=(1, 1), num_filter=8,
                             name="conv1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2),
                         pool_type="max")
    net = mx.sym.Flatten(net, name="flatten")
    net = mx.sym.FullyConnected(net, num_hidden=num_classes, name="fc1")
    return mx.sym.SoftmaxOutput(net, mx.sym.var("softmax_label"),
                                name="softmax")


def synthetic(num, classes, seed):
    rs = np.random.RandomState(seed)
    x = rs.rand(num, 1, 8, 8).astype(np.float32)
    y = (x.mean(axis=(1, 2, 3)) * classes).astype(np.int32) % classes
    return x, y.astype(np.float32)


def fit(symbol, x, y, arg_params=None, num_epoch=4, lr=0.1):
    import mxnet_tpu as mx

    mod = mx.mod.Module(symbol)
    it = mx.io.NDArrayIter(x, y, batch_size=32, shuffle=True)
    mod.fit(it, num_epoch=num_epoch, optimizer="sgd",
            optimizer_params={"learning_rate": lr},
            arg_params=arg_params or {}, allow_missing=True,
            initializer=mx.init.Xavier())
    it_eval = mx.io.NDArrayIter(x, y, batch_size=32)
    return mod, mod.score(it_eval, "acc")[0][1]


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=4)
    args = p.parse_args(argv)

    import mxnet_tpu as mx

    # phase 1: pretrain on task A (3 classes), save checkpoint
    xa, ya = synthetic(512, 3, seed=0)
    mod_a, acc_a = fit(base_net(3), xa, ya, num_epoch=args.epochs)
    print(f"task A accuracy: {acc_a:.3f}")
    prefix = "/tmp/finetune_demo"
    arg_params, aux_params = mod_a.get_params()
    mx.model.save_checkpoint(prefix, args.epochs, base_net(3),
                             arg_params, aux_params)

    # phase 2: fine-tune on task B (5 classes) from the checkpoint
    symbol, arg_params, _ = mx.model.load_checkpoint(prefix, args.epochs)
    net_b, backbone = get_fine_tune_model(symbol, arg_params,
                                          num_classes=5)
    xb, yb = synthetic(512, 5, seed=1)
    _, acc_b = fit(net_b, xb, yb, arg_params=backbone,
                   num_epoch=args.epochs)
    print(f"task B (fine-tuned) accuracy: {acc_b:.3f}")
    return acc_b


if __name__ == "__main__":
    main()
