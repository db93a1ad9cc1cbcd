#!/usr/bin/env python
"""Inference throughput across the model zoo (parity:
example/image-classification/benchmark_score.py).

    python examples/image_classification/benchmark_score.py \
        --models resnet50_v1,mobilenet1_0 --batch-sizes 1,32
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def score(model, batch, iters, ctx, dtype="float32"):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision

    net = vision.get_model(model, classes=1000)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    if dtype != "float32":
        net.cast(dtype)
    net.hybridize(static_alloc=True)
    size = 299 if model.startswith("inception") else 224
    x = mx.nd.random.uniform(shape=(batch, 3, size, size), ctx=ctx)
    if dtype != "float32":
        x = x.astype(dtype)
    net(x).wait_to_read()  # compile
    net(x).wait_to_read()  # warm
    t0 = time.perf_counter()
    outs = [net(x) for _ in range(iters)]
    outs[-1].wait_to_read()
    return batch * iters / (time.perf_counter() - t0)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--models", type=str, default="")
    p.add_argument("--batch-sizes", type=str, default="1,32")
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--dtype", type=str, default="float32")
    args = p.parse_args(argv)

    import mxnet_tpu as mx

    from mxnet_tpu.gluon.model_zoo import vision

    ctx = mx.tpu() if mx.num_tpus() > 0 else mx.cpu()
    print(f"scoring on {ctx.jax_device()}")
    models = ([m for m in args.models.split(",") if m] or
              ["alexnet", "resnet18_v1", "resnet50_v1", "mobilenet1_0",
               "vgg16", "squeezenet1_0", "densenet121", "inception_v3"])
    known = set(vision.get_model_names())
    for model in models:
        if model not in known:
            print(f"skip unknown model {model}")
            continue
        for batch in (int(b) for b in args.batch_sizes.split(",")):
            ips = score(model, batch, args.iters, ctx, args.dtype)
            print(f"batch size {batch:3d}, dtype {args.dtype}, "
                  f"model {model}: {ips:.1f} img/sec")


if __name__ == "__main__":
    main()
