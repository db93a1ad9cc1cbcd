#!/usr/bin/env python
"""Train an MLP or LeNet on MNIST with the Module API.

Parity target: `example/image-classification/train_mnist.py` — same
argparse surface and network definitions (mlp :44, lenet via symbols);
runs end-to-end on TPU with `--ctx tpu` (default).

    python examples/image_classification/train_mnist.py --network mlp
"""
import argparse
import os
import sys

_here = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _here)
sys.path.insert(0, os.path.dirname(os.path.dirname(_here)))  # repo root

import mxnet_tpu as mx


from common import data, fit


def get_mlp():
    """Multi-layer perceptron (parity: train_mnist.py:44)."""
    d = mx.sym.var("data")
    d = mx.sym.Flatten(d)
    fc1 = mx.sym.FullyConnected(d, name="fc1", num_hidden=128)
    act1 = mx.sym.Activation(fc1, name="relu1", act_type="relu")
    fc2 = mx.sym.FullyConnected(act1, name="fc2", num_hidden=64)
    act2 = mx.sym.Activation(fc2, name="relu2", act_type="relu")
    fc3 = mx.sym.FullyConnected(act2, name="fc3", num_hidden=10)
    return mx.sym.SoftmaxOutput(fc3, mx.sym.var("softmax_label"),
                                name="softmax")


def get_lenet():
    """LeNet (parity: train_mnist.py get_lenet)."""
    d = mx.sym.var("data")
    conv1 = mx.sym.Convolution(d, kernel=(5, 5), num_filter=20,
                               name="conv1")
    tanh1 = mx.sym.Activation(conv1, act_type="tanh")
    pool1 = mx.sym.Pooling(tanh1, pool_type="max", kernel=(2, 2),
                           stride=(2, 2))
    conv2 = mx.sym.Convolution(pool1, kernel=(5, 5), num_filter=50,
                               name="conv2")
    tanh2 = mx.sym.Activation(conv2, act_type="tanh")
    pool2 = mx.sym.Pooling(tanh2, pool_type="max", kernel=(2, 2),
                           stride=(2, 2))
    flat = mx.sym.Flatten(pool2)
    fc1 = mx.sym.FullyConnected(flat, num_hidden=500, name="fc1")
    tanh3 = mx.sym.Activation(fc1, act_type="tanh")
    fc2 = mx.sym.FullyConnected(tanh3, num_hidden=10, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, mx.sym.var("softmax_label"),
                                name="softmax")


def main():
    parser = argparse.ArgumentParser(
        description="train mnist",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    fit.add_fit_args(parser)
    data.add_data_args(parser)
    parser.set_defaults(network="mlp", num_epochs=5, lr=0.01,
                        lr_step_epochs="10", batch_size=64,
                        num_examples=4096)
    args = parser.parse_args()

    net = get_mlp() if args.network == "mlp" else get_lenet()
    fit.fit(args, net, data.get_mnist_iter)


if __name__ == "__main__":
    main()
