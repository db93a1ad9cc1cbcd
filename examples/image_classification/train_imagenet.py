#!/usr/bin/env python
"""Train an ImageNet-class model — the flagship fit driver.

Parity target: `example/image-classification/train_imagenet.py` +
`common/fit.py:150-321` — full argparse surface (kvstore, lr-step
schedule, checkpoint-per-epoch, top-k metric) plus the `--benchmark 1`
synthetic mode that measures pure training throughput (img/s via
Speedometer) with a device-resident batch, no input pipeline.

    # real data (ImageRecord):
    python train_imagenet.py --data-train train.rec --data-val val.rec
    # throughput benchmark on one chip:
    python train_imagenet.py --benchmark 1 --network resnet50_v1
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


def get_network(name, num_classes, image_shape, dtype="float32"):
    """Model-zoo network as a Symbol with a SoftmaxOutput head."""
    from mxnet_tpu.gluon.model_zoo import vision

    net = vision.get_model(name, classes=num_classes)
    net.initialize(mx.init.Xavier())
    if dtype != "float32":
        net.cast(dtype)
    x = mx.nd.zeros((1,) + image_shape)
    if dtype != "float32":
        x = x.astype(dtype)
    net(x)
    with tempfile.TemporaryDirectory() as d:
        net.export(os.path.join(d, "net"), 0)
        sym, _, _ = mx.model.load_checkpoint(os.path.join(d, "net"), 0)
    return mx.sym.SoftmaxOutput(sym, mx.sym.var("softmax_label"),
                                name="softmax")


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="train imagenet-class models",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    fit.add_fit_args(parser)
    data.add_data_args(parser)
    parser.add_argument("--data-train", type=str,
                        help="training ImageRecord (.rec) file")
    parser.add_argument("--data-val", type=str,
                        help="validation ImageRecord (.rec) file")
    parser.add_argument("--image-shape", type=str, default="3,224,224",
                        help="input shape C,H,W")
    parser.add_argument("--num-classes", type=int, default=1000,
                        help="number of classes")
    parser.add_argument("--benchmark", type=int, default=0,
                        help="1 = measure train throughput on a "
                             "synthetic device-resident batch")
    parser.set_defaults(
        network="resnet50_v1",
        num_epochs=1,
        lr=0.1, lr_factor=0.1, lr_step_epochs="30,60,80",
        batch_size=128, num_examples=1281167,
        disp_batches=10,
    )
    args = parser.parse_args(argv)

    shape = tuple(int(d) for d in args.image_shape.split(","))
    net = get_network(args.network, args.num_classes, shape, args.dtype)

    if args.benchmark:
        # parity: fit.py --benchmark — synthetic feeder, one epoch,
        # Speedometer prints the img/s the driver records
        args.num_epochs = 1
        epoch_size = max(args.num_examples // args.batch_size, 1)

        def synthetic_loader(a, kv):
            return (data.SyntheticDataIter(
                a.num_classes, (a.batch_size,) + shape, epoch_size,
                a.dtype), None)

        return fit.fit(args, net, synthetic_loader)
    return fit.fit(args, net, data.get_rec_iter)


if __name__ == "__main__":
    main()
