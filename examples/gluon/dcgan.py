#!/usr/bin/env python
"""DCGAN on synthetic images (parity: the reference's example/gluon/dcgan
— alternating generator/discriminator training with transposed convs).

The generator upsamples a latent vector through Conv2DTranspose stacks;
the discriminator is a strided-conv classifier; both train with the
adversarial min-max objective under `autograd.record`. Synthetic
gaussian-blob "images" stand in for LSUN/MNIST (zero-egress
environment) — the training mechanics (two optimizers, detached fake
batch for the D step, BCE objective) are the reference's.

    python examples/gluon/dcgan.py --epochs 3
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np


def build_nets(nn, ngf=16, ndf=16, nc=1):
    netG = nn.HybridSequential(prefix="gen_")
    with netG.name_scope():
        # latent (B, nz, 1, 1) -> (B, nc, 16, 16)
        netG.add(nn.Conv2DTranspose(ngf * 2, 4, 1, 0, use_bias=False),
                 nn.BatchNorm(), nn.Activation("relu"),
                 nn.Conv2DTranspose(ngf, 4, 2, 1, use_bias=False),
                 nn.BatchNorm(), nn.Activation("relu"),
                 nn.Conv2DTranspose(nc, 4, 2, 1, use_bias=False),
                 nn.Activation("tanh"))
    netD = nn.HybridSequential(prefix="disc_")
    with netD.name_scope():
        netD.add(nn.Conv2D(ndf, 4, 2, 1, use_bias=False),
                 nn.LeakyReLU(0.2),
                 nn.Conv2D(ndf * 2, 4, 2, 1, use_bias=False),
                 nn.BatchNorm(), nn.LeakyReLU(0.2),
                 nn.Conv2D(1, 4, 1, 0, use_bias=False))
    return netG, netD


def main(argv=None):
    p = argparse.ArgumentParser(
        description="DCGAN",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--nz", type=int, default=16, help="latent dim")
    p.add_argument("--lr", type=float, default=2e-3)
    p.add_argument("--num-examples", type=int, default=512)
    args = p.parse_args(argv)

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn

    mx.random.seed(0)
    rs = np.random.RandomState(0)
    # synthetic 16x16 "images": smooth gaussian bumps in [-1, 1]
    yy, xx = np.mgrid[0:16, 0:16] / 15.0
    centers = rs.rand(args.num_examples, 2)
    real = np.tanh(3.0 * np.exp(
        -(((xx[None] - centers[:, 0, None, None]) ** 2 +
           (yy[None] - centers[:, 1, None, None]) ** 2) / 0.05)) - 0.5)
    real = real[:, None].astype(np.float32)

    netG, netD = build_nets(nn)
    netG.initialize(mx.init.Normal(0.02))
    netD.initialize(mx.init.Normal(0.02))
    trainerG = gluon.Trainer(netG.collect_params(), "adam",
                             {"learning_rate": args.lr, "beta1": 0.5})
    trainerD = gluon.Trainer(netD.collect_params(), "adam",
                             {"learning_rate": args.lr, "beta1": 0.5})
    bce = gluon.loss.SigmoidBinaryCrossEntropyLoss()

    b = args.batch_size
    if args.num_examples < b:
        p.error(f"--num-examples ({args.num_examples}) must be >= "
                f"--batch-size ({b})")
    ones = mx.nd.ones((b,))
    zeros = mx.nd.zeros((b,))
    nbatch = args.num_examples // b
    d_loss = g_loss = 0.0
    for epoch in range(args.epochs):
        perm = rs.permutation(args.num_examples)
        d_tot = g_tot = 0.0
        for i in range(nbatch):
            data = mx.nd.array(real[perm[i * b:(i + 1) * b]])
            noise = mx.nd.random.normal(shape=(b, args.nz, 1, 1))
            # --- D step: real -> 1, detached fake -> 0
            fake = netG(noise)
            with autograd.record():
                out_real = netD(data).reshape((-1,))
                out_fake = netD(fake.detach()).reshape((-1,))
                lossD = bce(out_real, ones) + bce(out_fake, zeros)
            lossD.backward()
            trainerD.step(b)
            # --- G step: fool D on a fresh fake batch
            with autograd.record():
                out = netD(netG(noise)).reshape((-1,))
                lossG = bce(out, ones)
            lossG.backward()
            trainerG.step(b)
            d_tot += float(lossD.mean().asscalar())
            g_tot += float(lossG.mean().asscalar())
        d_loss, g_loss = d_tot / nbatch, g_tot / nbatch
        print(f"Epoch[{epoch}] D-loss={d_loss:.4f} G-loss={g_loss:.4f}")
    samples = netG(mx.nd.random.normal(
        shape=(4, args.nz, 1, 1))).asnumpy()
    assert samples.shape == (4, 1, 16, 16)
    assert np.isfinite(samples).all()
    return d_loss, g_loss


if __name__ == "__main__":
    main()
