#!/usr/bin/env python
"""Train a ResNet on CIFAR-10 with the Module API over the Gluon zoo.

Parity target: `example/image-classification/train_cifar10.py` — same
argparse surface; the network comes from the model zoo (thumbnail
variant for 32x32 inputs) exported to a Symbol, trained via common/fit.

    python examples/image_classification/train_cifar10.py --network resnet18_v1
"""
import argparse
import os
import sys
import tempfile

_here = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _here)
sys.path.insert(0, os.path.dirname(os.path.dirname(_here)))  # repo root

import mxnet_tpu as mx


from common import data, fit


def get_network(name, num_classes=10):
    """Model-zoo network as a Symbol with a SoftmaxOutput head."""
    from mxnet_tpu.gluon.model_zoo import vision

    net = vision.get_model(name, classes=num_classes, thumbnail=True) \
        if "resnet" in name else vision.get_model(name,
                                                  classes=num_classes)
    net.initialize(mx.init.Xavier())
    x = mx.nd.zeros((1, 3, 32, 32))
    net(x)
    with tempfile.TemporaryDirectory() as d:
        net.export(os.path.join(d, "net"), 0)
        sym, _, _ = mx.model.load_checkpoint(os.path.join(d, "net"), 0)
    return mx.sym.SoftmaxOutput(sym, mx.sym.var("softmax_label"),
                                name="softmax")


def main():
    parser = argparse.ArgumentParser(
        description="train cifar10",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    fit.add_fit_args(parser)
    data.add_data_args(parser)
    parser.set_defaults(network="resnet18_v1", num_epochs=10, lr=0.01,
                        lr_step_epochs="50,100", batch_size=128,
                        num_examples=4096)
    args = parser.parse_args()

    net = get_network(args.network)
    fit.fit(args, net, data.get_cifar10_iter)


if __name__ == "__main__":
    main()
