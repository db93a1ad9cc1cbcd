"""Shared training harness for the image-classification examples.

Parity target: `example/image-classification/common/fit.py` (reference
lines: `_get_lr_scheduler` :29, `_load_model` :57, `_save_model` :70,
`add_fit_args` :77, `fit` :150) — argparse surface, lr-step schedule,
checkpoint resume, Speedometer/do_checkpoint callbacks, kvstore wiring,
Module train loop. TPU-native: `--ctx tpu` runs the whole graph as one
XLA executable per batch signature.
"""
from __future__ import annotations

import argparse
import logging
import os
import time

import mxnet_tpu as mx


def _get_lr_scheduler(args, kv):
    """parity: fit.py:29 — factor schedule at --lr-step-epochs."""
    if "lr_factor" not in args or args.lr_factor >= 1:
        return (args.lr, None)
    epoch_size = args.num_examples // args.batch_size
    begin_epoch = args.load_epoch if args.load_epoch else 0
    step_epochs = [int(l) for l in args.lr_step_epochs.split(",") if l]
    lr = args.lr
    for s in step_epochs:
        if begin_epoch >= s:
            lr *= args.lr_factor
    if lr != args.lr:
        logging.info("Adjust learning rate to %e for epoch %d", lr,
                     begin_epoch)
    steps = [epoch_size * (x - begin_epoch) for x in step_epochs
             if x - begin_epoch > 0]
    if steps:
        return (lr, mx.lr_scheduler.MultiFactorScheduler(
            step=steps, factor=args.lr_factor, base_lr=lr))
    return (lr, None)


def _load_model(args, rank=0):
    """parity: fit.py:57."""
    if args.load_epoch is None or not args.model_prefix:
        return (None, None, None)
    model_prefix = args.model_prefix
    sym, arg_params, aux_params = mx.model.load_checkpoint(
        model_prefix, args.load_epoch)
    logging.info("Loaded model %s_%04d.params", model_prefix,
                 args.load_epoch)
    return (sym, arg_params, aux_params)


def _save_model(args, rank=0):
    """parity: fit.py:70."""
    if args.model_prefix is None:
        return None
    dst_dir = os.path.dirname(args.model_prefix)
    if dst_dir and not os.path.isdir(dst_dir):
        os.makedirs(dst_dir, exist_ok=True)
    return mx.callback.do_checkpoint(args.model_prefix)


def add_fit_args(parser):
    """parity: fit.py:77 — the common training argument set."""
    train = parser.add_argument_group("Training", "model training")
    train.add_argument("--network", type=str, help="the neural network to use")
    train.add_argument("--num-layers", type=int,
                       help="number of layers in the neural network")
    train.add_argument("--kv-store", type=str, default="local",
                       help="key-value store type")
    train.add_argument("--num-epochs", type=int, default=10,
                       help="max num of epochs")
    train.add_argument("--lr", type=float, default=0.1,
                       help="initial learning rate")
    train.add_argument("--lr-factor", type=float, default=0.1,
                       help="the ratio to reduce lr on each step")
    train.add_argument("--lr-step-epochs", type=str, default="",
                       help="the epochs to reduce the lr, e.g. 30,60")
    train.add_argument("--optimizer", type=str, default="sgd",
                       help="the optimizer type")
    train.add_argument("--mom", type=float, default=0.9,
                       help="momentum for sgd")
    train.add_argument("--wd", type=float, default=1e-4,
                       help="weight decay for sgd")
    train.add_argument("--batch-size", type=int, default=128,
                       help="the batch size")
    train.add_argument("--disp-batches", type=int, default=20,
                       help="show progress for every n batches")
    train.add_argument("--model-prefix", type=str,
                       help="model checkpoint prefix")
    train.add_argument("--load-epoch", type=int,
                       help="load the model on an epoch using the "
                            "model-prefix")
    train.add_argument("--top-k", type=int, default=0,
                       help="report the top-k accuracy; 0 means no report")
    train.add_argument("--ctx", type=str, default="tpu",
                       help="device context: tpu or cpu")
    train.add_argument("--monitor", dest="monitor", type=int, default=0,
                       help="log network parameter stats every N batches")
    train.add_argument("--dtype", type=str, default="float32",
                       help="precision: float32 or bfloat16")
    return train


def fit(args, network, data_loader, **kwargs):
    """Train `network` (a Symbol) with the Module API
    (parity: fit.py:150)."""
    kv = mx.kv.create(args.kv_store)
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)-15s Node[" + str(kv.rank) + "] %(message)s")
    logging.info("start with arguments %s", args)

    train, val = data_loader(args, kv)

    sym, arg_params, aux_params = _load_model(args, kv.rank)
    if sym is not None:
        network = sym

    devs = mx.tpu() if args.ctx == "tpu" and mx.num_tpus() > 0 else mx.cpu()
    logging.info("--ctx %s runs on %s", args.ctx, devs.jax_device())
    lr, lr_scheduler = _get_lr_scheduler(args, kv)

    model = mx.mod.Module(context=devs, symbol=network)

    optimizer_params = {
        "learning_rate": lr,
        "wd": args.wd,
        "lr_scheduler": lr_scheduler,
    }
    if args.optimizer in ("sgd", "nag", "signum", "lbsgd"):
        optimizer_params["momentum"] = args.mom

    monitor = mx.monitor.Monitor(args.monitor, pattern=".*") \
        if args.monitor > 0 else None

    initializer = mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                 magnitude=2)
    eval_metrics = ["accuracy"]
    if args.top_k > 0:
        eval_metrics.append(mx.metric.create("top_k_accuracy",
                                             top_k=args.top_k))
    batch_end_callbacks = [mx.callback.Speedometer(args.batch_size,
                                                   args.disp_batches)]
    checkpoint = _save_model(args, kv.rank)

    model.fit(train,
              begin_epoch=args.load_epoch if args.load_epoch else 0,
              num_epoch=args.num_epochs,
              eval_data=val,
              eval_metric=eval_metrics,
              kvstore=kv,
              optimizer=args.optimizer,
              optimizer_params=optimizer_params,
              initializer=initializer,
              arg_params=arg_params,
              aux_params=aux_params,
              batch_end_callback=batch_end_callbacks,
              epoch_end_callback=checkpoint,
              allow_missing=True,
              monitor=monitor,
              **kwargs)
    return model
