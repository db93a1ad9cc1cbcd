#!/usr/bin/env python
"""Post-training int8 quantization demo.

Parity target: `example/quantization/imagenet_gen_qsym_onedal.py` /
`quantize_model` flow — train fp32, calibrate on a few batches with the
TRUE KL entropy search (`calib_mode="entropy"`, the calibrate.cc
algorithm; `--calib-mode naive|percentile` for A/B), quantize to int8
per output channel, compare accuracy, report the gap — then SERVE the
quantized pair through an `mxnet_tpu.serving` int8 bucket ladder and
show the per-model `weight_dtype` + ladder census. Runs on synthetic
MNIST-like data so it works anywhere; pass --mnist-dir with the idx
files for the real thing.

    python examples/quantization/quantize_mnist.py --ctx tpu
"""
import argparse
import os
import sys

_here = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(_here)))  # repo root
sys.path.insert(0, os.path.join(os.path.dirname(_here),
                                "image_classification"))

import mxnet_tpu as mx


from common import data as common_data  # shared MNIST-or-synthetic iters
from mxnet_tpu.contrib import quantization


def build_sym():
    data = mx.sym.var("data")
    net = mx.sym.Convolution(data, kernel=(3, 3), num_filter=8, name="conv1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2), pool_type="max")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=64, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc2")
    return mx.sym.SoftmaxOutput(net, mx.sym.var("softmax_label"),
                                name="softmax")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mnist-dir", default=None, dest="data_dir")
    ap.add_argument("--num-examples", type=int, default=2048)
    ap.add_argument("--num-val-examples", type=int, default=512)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--num-epochs", type=int, default=3)
    ap.add_argument("--calib-batches", type=int, default=5)
    ap.add_argument("--calib-mode", default="entropy",
                    choices=["entropy", "naive", "percentile"],
                    help="activation calibration: 'entropy' is the real "
                         "KL threshold search (calibrate.cc parity)")
    ap.add_argument("--ctx", default="cpu", choices=["cpu", "tpu"])
    ap.add_argument("--skip-serve", action="store_true",
                    help="skip the served-int8 demo at the end")
    args = ap.parse_args()

    ctx = mx.tpu() if args.ctx == "tpu" else mx.cpu()

    args.data_dir = args.data_dir or ""
    train_it, eval_it = common_data.get_mnist_iter(args, None)
    mod = mx.mod.Module(build_sym(), context=ctx)
    mod.fit(train_it, num_epoch=args.num_epochs,
            initializer=mx.init.Xavier(),
            optimizer_params=(("learning_rate", 0.1),
                              ("rescale_grad", 1.0 / args.batch_size)))
    fp32_acc = dict(mod.score(eval_it, "acc"))["accuracy"]
    print(f"fp32 accuracy: {fp32_acc:.4f}")

    arg_params, aux_params = mod.get_params()
    qsym, qarg, qaux = quantization.quantize_model(
        build_sym(), arg_params, aux_params,
        calib_data=train_it,
        num_calib_examples=args.calib_batches * args.batch_size,
        calib_mode=args.calib_mode)
    calib = quantization.last_calibration()
    print(f"calibration: mode={calib['mode']} bins={calib['num_bins']} "
          f"over {calib['examples']} examples")
    if args.calib_mode == "entropy":
        for tname, rec in sorted(calib["tensors"].items()):
            print(f"  {tname}: KL threshold {rec['threshold']:.4f} "
                  f"(seen [{rec['min_seen']:.3f}, {rec['max_seen']:.3f}])")
    qmod = mx.mod.Module(qsym, context=ctx)
    qmod.bind(eval_it.provide_data, eval_it.provide_label,
              for_training=False)
    qmod.init_params(arg_params=qarg, aux_params=qaux, allow_missing=False)
    int8_acc = dict(qmod.score(eval_it, "acc"))["accuracy"]
    print(f"int8 accuracy: {int8_acc:.4f} "
          f"(gap {fp32_acc - int8_acc:+.4f})")
    assert int8_acc > fp32_acc - 0.05, "int8 accuracy dropped > 5%"

    if not args.skip_serve:
        serve_int8_demo(qsym, qarg, qaux, eval_it)
    print("done")


def serve_int8_demo(qsym, qarg, qaux, eval_it, requests=32):
    """Serve the quantized pair through its own int8 bucket ladder:
    the loaders auto-detect the int8 weights, the ladder pre-compiles
    at warmup (warming the persistent disk cache when
    MXNET_TPU_CACHE_DIR is set — a warm pod then starts with ZERO
    compiles), and stats() reports weight_dtype per model."""
    import numpy as np

    from mxnet_tpu import compile as compile_service
    from mxnet_tpu import serving

    example_shape = tuple(eval_it.provide_data[0].shape[1:])
    # serve the logits: SoftmaxOutput carries the training label input,
    # which a predict server has no business feeding
    serve_sym = qsym.get_internals()["fc2_output"]
    container = serving.ModelContainer()
    container.add_symbol("mnist_int8", serve_sym, dict(qarg), dict(qaux),
                         example_shape=example_shape, buckets=(2, 4, 8))
    server = serving.ModelServer(container, max_wait_ms=1.0).start()
    server.warmup()
    rng = np.random.RandomState(0)
    for i in range(requests):
        rows = int(rng.randint(1, 9))
        x = rng.rand(rows, *example_shape).astype(np.float32)
        y = server.predict("mnist_int8", x, timeout=30.0)
        assert y.shape[0] == rows
    stats = server.stats()["models"]["mnist_int8"]
    comp = compile_service.stats().get("serving", {})
    print(f"served int8: weight_dtype={stats['weight_dtype']} "
          f"ladder={stats['buckets']} census={stats['bucket_census']} "
          f"p50={stats['p50_ms']}ms")
    print(f"serving compile site: hits={comp.get('hits')} "
          f"misses={comp.get('misses')} "
          f"disk_hits={comp.get('disk_hits')}")
    server.drain(timeout=10.0)


if __name__ == "__main__":
    main()
