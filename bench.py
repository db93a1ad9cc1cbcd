#!/usr/bin/env python
"""Headline benchmarks: ResNet-50 inference AND training throughput, one chip.

Reference baselines (BASELINE.md / docs perf.md): ResNet-50 bs=128 fp32 on
1x V100 — inference 1233.15 img/s (perf.md:196, fp16 analogue 2355.04),
training 363.69 img/s (perf.md:254, methodology of
example/image-classification/train_imagenet.py --benchmark). Reproduced
here in bfloat16 (the MXU's native input type).

Runs on a TPU only: with no TPU attached every mode exits non-zero with
one line and measures nothing. None of the lines below has been measured
on the current code (PERF.md records what has).

Prints TWO JSON lines {"metric", "value", "unit", "vs_baseline", ...}:
  1. resnet50_v1_infer_bs128_bfloat16  (hybridized compiled scoring)
  2. resnet50_v1_train_bs128_bfloat16  (ONE fused fwd+loss+bwd+SGD-momentum
     executable via parallel.ShardedTrainer, incl. BN stat writeback;
     extra fields: achieved_tflops + the nominal mfu vs the per-device-kind
     peak table in mxnet_tpu.telemetry.costs — TPU v3..v6e,
     BENCH_PEAK_TFLOPS override — AND mfu_xla, the measured
     ratio whose numerator is the XLA cost_analysis() flops the compile
     service captured for the executable)
Every line also carries the device it ran on (``platform``) and
compile-service telemetry (mxnet_tpu.compile): ``compile_ms`` (time
spent compiling this process), ``cache_hits`` / ``cache_misses`` and
``cache_disk_hits``.

A serving line is emitted BY DEFAULT (disable with BENCH_SKIP_SERVE=1,
or run just it with ``--serve-only``): sustained requests/s + p50/p99
latency + batch fill ratio from a ``tools/loadgen.py`` closed loop
against an in-process 2-model ``mxnet_tpu.serving`` container
(BENCH_SERVE_SECONDS, default 30). A ``serving_rps_int8_*`` companion
line follows it (same harness in ``--dtype both`` pair mode,
BENCH_SERVE_INT8_SECONDS, default 16): the embedding-lookup fixture
served fp32 AND entropy-calibrated int8 from one warm ladder, recording
the matched-p99 int8-vs-float rps ratio. BENCH_SKIP_SERVE=1 skips both.
Everything here runs in THIS process: a chip belongs to one process, so
the multi-process fleet measurements (``tools/loadgen.py --workers N``)
are not part of this run.

Env knobs: BENCH_BATCH (default 128), BENCH_DTYPE (bfloat16|float32),
BENCH_ITERS, BENCH_MODEL, BENCH_SKIP_TRAIN, BENCH_PEAK_TFLOPS (default:
auto-detected from the chip generation — v5e 197, v5p 459, v4 275, ...;
an on-chip measured peak is also reported as measured_peak_tflops).

Per-family ``kernel_vs_xla_<family>`` lines are emitted BY DEFAULT
(disable with BENCH_SKIP_KERNELS=1, run just them with
``--kernels-only``): the kernel-layer autotuner (opperf --kernels)
timing each Pallas kernel family against its XLA baseline and
refreshing the persisted dispatch table. BENCH_KERNEL_RUNS sizes the
timing loop.
"""
import json
import os
import time

import numpy as np

# forward GFLOP/img @224x224 per model (public model FLOP counts)
_FWD_GFLOPS = {"resnet50_v1": 4.09, "resnet50_v2": 4.09,
               "resnet18_v1": 1.82, "resnet101_v1": 7.8,
               "resnet152_v1": 11.5, "vgg16": 15.5, "alexnet": 0.71}


def _compile_fields(line):
    """Fold the compile-service totals into one emitted JSON line: how
    much of this process went to compiling vs cache hits (disk hits =
    the persistent-cache warm-start win)."""
    from mxnet_tpu import compile as _compile

    t = _compile.totals()
    line["compile_ms"] = t["compile_ms"]
    line["cache_hits"] = t["hits"]
    line["cache_misses"] = t["misses"]
    line["cache_disk_hits"] = t["disk_hits"]
    return line


def _mfu_xla_fields(line, site, calls_per_sec, devices=1):
    """Measured-flops MFU: the compile service captured XLA
    ``cost_analysis()`` for `site`'s newest executable
    (mxnet_tpu.telemetry.costs); divided by the per-device-kind peak
    table this is ``mfu_xla`` — the ratio whose numerator is what XLA
    actually scheduled, emitted ALONGSIDE the nominal ``mfu``."""
    from mxnet_tpu.telemetry import costs as _tcosts

    rec = _tcosts.latest(site)
    flops = (rec or {}).get("flops")
    if not flops:
        return line
    line["xla_flops_per_call"] = flops
    mfu = _tcosts.mfu_xla(flops, calls_per_sec, devices=devices,
                          peak=_peak_tflops())
    if mfu is not None:
        line["mfu_xla"] = round(mfu, 5)
    return line


def _gradcomms_fields(line, steps=None):
    """Fold the gradient-comms trajectory into a train line:
    ``sync_ms_mean`` (the step timeline's sync phase over the timed
    steps — the serialized collective tail) and ``overlap_ratio`` (the
    bucket pipeline's 1 - blocked/in-flight; null single-host, where no
    cross-host reduction runs)."""
    from mxnet_tpu.kvstore import buckets as _kvbuckets
    from mxnet_tpu.telemetry import steps as _tsteps

    hist = _tsteps.history(steps)
    syncs = [r["phases"].get("sync", 0.0) for r in hist]
    line["sync_ms_mean"] = round(sum(syncs) / len(syncs), 3) \
        if syncs else None
    line["overlap_ratio"] = _kvbuckets.comm_stats()["overlap_ratio"]
    return line


def _require_tpu():
    """Every line of this run is measured on a TPU, or nothing runs: a
    number from another backend never gets a device metric's name."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"bench.py: no TPU (jax reports platform {platform!r}); "
            "nothing measured")


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(prog="bench",
                                 description="headline benchmarks")
    ap.add_argument("--serve", action="store_true",
                    help="also emit the serving throughput metric "
                         "(tools/loadgen.py closed loop against a "
                         "2-model container)")
    ap.add_argument("--serve-only", action="store_true",
                    help="emit ONLY the serving metric")
    ap.add_argument("--dataplane-only", action="store_true",
                    help="emit ONLY the host data-plane metric")
    ap.add_argument("--kernels-only", action="store_true",
                    help="emit ONLY the per-family kernel-vs-XLA lines")
    args = ap.parse_args(argv)
    _require_tpu()

    if args.kernels_only:
        bench_kernels()
        return

    if args.serve_only:
        bench_serve()
        bench_serve_int8()
        return
    if args.dataplane_only:
        bench_dataplane()
        return

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision

    batch = int(os.environ.get("BENCH_BATCH", 128))
    dtype = os.environ.get("BENCH_DTYPE", "bfloat16")
    iters = int(os.environ.get("BENCH_ITERS", 20))
    model = os.environ.get("BENCH_MODEL", "resnet50_v1")
    baseline = 1233.15  # ResNet-50 bs=128 fp32 on V100 (perf.md:196)

    ctx = mx.tpu()
    skip_train = bool(os.environ.get("BENCH_SKIP_TRAIN"))
    net = vision.get_model(model, classes=1000)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    if dtype != "float32":
        net.cast(dtype)
    net.hybridize(static_alloc=True, static_shape=True)

    x = mx.nd.random.uniform(shape=(batch, 3, 224, 224), ctx=ctx)
    if dtype != "float32":
        x = x.astype(dtype)

    # warmup: trigger deferred init (eager) + compile (first hybrid call)
    net(x).wait_to_read()
    net(x).wait_to_read()

    start = time.perf_counter()
    outs = []
    for _ in range(iters):
        outs.append(net(x))
    outs[-1].wait_to_read()
    elapsed = time.perf_counter() - start
    throughput = batch * iters / elapsed

    line = {
        "metric": f"{model}_infer_bs{batch}_{dtype}",
        "value": round(throughput, 2),
        "unit": "img/s",
        "vs_baseline": round(throughput / baseline, 3),
        "platform": ctx.device_type,
    }
    fwd_flops = _FWD_GFLOPS.get(model, 0.0) * 1e9
    if fwd_flops:
        achieved = throughput * fwd_flops / 1e12
        line["achieved_tflops"] = round(achieved, 1)
        line["mfu"] = round(achieved / _peak_tflops(), 3)
    # hybridized scoring compiles through the 'cachedop' service site
    _mfu_xla_fields(line, "cachedop", iters / elapsed)
    print(json.dumps(_compile_fields(line)), flush=True)

    if not skip_train:
        # training compiles a bigger program; cap its timed loop so the
        # whole bench stays inside the driver's window
        train_iters = int(os.environ.get("BENCH_TRAIN_ITERS",
                                         min(iters, 10)))
        bench_train(ctx, batch, dtype, train_iters, model)
    # the serving line is part of the default metric series (the ROADMAP
    # item-1 trajectory); BENCH_SKIP_SERVE=1 opts out of both it and the
    # int8-vs-float companion line (the ROADMAP item-4 ratio)
    if args.serve or not os.environ.get("BENCH_SKIP_SERVE"):
        bench_serve()
        bench_serve_int8()
    # the host data-plane line tracks the streaming input pipeline
    # (native fused decode+augment img/s + trainer data_wait);
    # BENCH_SKIP_DATAPLANE=1 opts out
    if not os.environ.get("BENCH_SKIP_DATAPLANE"):
        bench_dataplane()
    # per-family Pallas-kernel-vs-XLA speedup lines (the kernel-layer
    # trajectory); BENCH_SKIP_KERNELS=1 opts out
    if not os.environ.get("BENCH_SKIP_KERNELS"):
        bench_kernels()


def bench_train(ctx, batch, dtype, iters, model):
    """Training throughput: fused fwd+loss+bwd+SGD step (one executable)."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer

    baseline = 363.69  # ResNet-50 bs=128 fp32 training on V100 (perf.md:254)
    flops_per_img = 3 * _FWD_GFLOPS.get(model, 0.0) * 1e9  # train ~= 3x fwd
    peak_tflops = _peak_tflops()

    mx.random.seed(0)
    net = vision.get_model(model, classes=1000)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    if dtype != "float32":
        net.cast(dtype)
    x = mx.nd.random.uniform(shape=(batch, 3, 224, 224), ctx=ctx)
    if dtype != "float32":
        x = x.astype(dtype)
    y = mx.nd.array(np.random.randint(0, 1000, batch).astype(np.float32),
                    ctx=ctx)
    net(x)  # materialize deferred shapes
    trainer = ShardedTrainer(
        net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4},
        mesh=DeviceMesh({"dp": 1}),
        # benchmark measures async dispatch throughput; the NaN guard's
        # per-step skip-flag read would serialize host and device
        nan_guard=False)
    trainer.step(x, y).wait_to_read()  # compile
    trainer.step(x, y).wait_to_read()  # warm
    start = time.perf_counter()
    for _ in range(iters):
        loss = trainer.step(x, y)
    loss.wait_to_read()
    elapsed = time.perf_counter() - start
    throughput = batch * iters / elapsed
    line = {
        "metric": f"{model}_train_bs{batch}_{dtype}",
        "value": round(throughput, 2),
        "unit": "img/s",
        "vs_baseline": round(throughput / baseline, 3),
        "platform": ctx.device_type,
    }
    if flops_per_img:  # only for models with a known FLOP count
        achieved = throughput * flops_per_img / 1e12
        line["achieved_tflops"] = round(achieved, 1)
        line["mfu"] = round(achieved / peak_tflops, 3)
        measured = _measure_chip_peak()
        line["measured_peak_tflops"] = round(measured, 1)
        line["mfu_vs_measured"] = round(achieved / measured, 3)
    _mfu_xla_fields(line, "trainer", iters * 1.0 / elapsed,
                    devices=trainer.mesh.num_devices)
    _gradcomms_fields(line, steps=iters)
    print(json.dumps(_compile_fields(line)), flush=True)


def bench_serve():
    """Serving throughput: tools/loadgen.py closed loop against an
    in-process 2-model container (mxnet_tpu.serving) — sustained
    requests/s with bounded tail latency, the ROADMAP item-1 acceptance
    number. Pre-traffic warmup compiles every bucket, so
    ``recompiles_during_run`` must be 0 (the compile service served only
    cache hits while the clock ran). Env knobs: BENCH_SERVE_SECONDS
    (default 30), BENCH_SERVE_CONCURRENCY (16), BENCH_SERVE_MODELS (2)."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import loadgen

    duration = float(os.environ.get("BENCH_SERVE_SECONDS", 30))
    concurrency = int(os.environ.get("BENCH_SERVE_CONCURRENCY", 16))
    models = int(os.environ.get("BENCH_SERVE_MODELS", 2))
    rep = loadgen.run_inproc(duration=duration, mode="closed",
                             concurrency=concurrency, models=models)
    import jax

    line = {
        "metric": f"serving_rps_{models}model_closed{concurrency}",
        "value": rep["rps"],
        "unit": "req/s",
        "duration_s": rep["duration_s"],
        "p50_ms": rep.get("p50_ms"),
        "p99_ms": rep.get("p99_ms"),
        "batch_fill_ratio": rep.get("batch_fill_ratio"),
        "rejected": rep.get("rejected"),
        "recompiles_during_run": rep.get("recompiles_during_run"),
        "platform": jax.devices()[0].platform,
    }
    print(json.dumps(_compile_fields(line)), flush=True)


def bench_serve_int8():
    """Int8 serving throughput vs float, same loadgen harness: the
    embedding-lookup fixture pair (``tools/loadgen.py --dtype both``)
    driven closed-loop per variant from ONE warm server — the ROADMAP
    item-4 acceptance number. Emits the int8 rps as the metric value
    with the matched-p99 int8-vs-float ratio alongside.
    ``recompiles_during_run`` must be 0
    (both ladders compiled/disk-loaded at warmup). Env knobs:
    BENCH_SERVE_INT8_SECONDS (default 16), BENCH_SERVE_CONCURRENCY
    (16), BENCH_PAIR_VOCAB/_EMBED_DIM/_SEQ_LEN size the fixture."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import loadgen

    import jax

    duration = float(os.environ.get("BENCH_SERVE_INT8_SECONDS", 16))
    concurrency = int(os.environ.get("BENCH_SERVE_CONCURRENCY", 16))
    rep = loadgen.run_pair(
        duration=duration, concurrency=concurrency,
        vocab=int(os.environ.get("BENCH_PAIR_VOCAB", 50_000)),
        embed_dim=int(os.environ.get("BENCH_PAIR_EMBED_DIM", 512)),
        seq_len=int(os.environ.get("BENCH_PAIR_SEQ_LEN", 1024)))
    line = {
        "metric": f"serving_rps_int8_emblookup_closed{concurrency}",
        "value": rep.get("rps_int8"),
        "unit": "req/s",
        "rps_float32": rep.get("rps_float32"),
        "ratio_int8_vs_float": rep.get("rps_ratio_int8_vs_float"),
        "p99_int8_ms": rep.get("p99_int8_ms"),
        "p99_float32_ms": rep.get("p99_float32_ms"),
        "matched_p99": rep.get("matched_p99"),
        "calib_mode": rep.get("calib_mode"),
        "bucket_census_int8": rep.get("bucket_census_int8"),
        "recompiles_during_run": rep.get("recompiles_during_run"),
        "platform": jax.devices()[0].platform,
    }
    print(json.dumps(_compile_fields(line)), flush=True)


def bench_dataplane():
    """Host data-plane metric (the streaming input pipeline of the
    native OMP decode+augment loop): img/s and img/s/core of the fused
    native path vs the bit-compatible Python fallback, per-thread
    scaling — AND the starvation check: a small conv net trained
    through PrefetchingIter(ImageRecordIter) at a batch size that
    starves a record-at-a-time pipeline, reporting the mean/max
    ``data_wait`` step phase (PR 9 gauge; ~0 = the host kept up).
    Env knobs: BENCH_DATAPLANE_IMAGES (192), BENCH_DATAPLANE_STEPS (12),
    BENCH_SKIP_DATAPLANE opts out of the default emission."""
    import sys
    import tempfile

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "benchmark"))
    import iter_bench

    import mxnet_tpu as mx
    from mxnet_tpu.gluon import loss as gloss, nn
    from mxnet_tpu.io import ImageRecordIter, PrefetchingIter
    from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer
    from mxnet_tpu.telemetry import steps as _tsteps

    n_img = int(os.environ.get("BENCH_DATAPLANE_IMAGES", 192))
    threads = os.cpu_count() or 1
    aug = iter_bench.run_augment(num_images=n_img, src_size=96,
                                 batch_size=32, data_shape=(3, 64, 64),
                                 epochs=2, threads=threads)

    # starvation check: feed a compiled train step from the pipeline and
    # read back the per-step data_wait phase the prefetcher recorded
    steps_n = int(os.environ.get("BENCH_DATAPLANE_STEPS", 12))
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Conv2D(16, 3, padding=1, activation="relu"),
            nn.MaxPool2D(2),
            nn.Conv2D(32, 3, padding=1, activation="relu"),
            nn.GlobalAvgPool2D(),
            nn.Dense(10))
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((2, 3, 64, 64)))
    trainer = ShardedTrainer(
        net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.05, "momentum": 0.9},
        mesh=DeviceMesh({"dp": 1}), nan_guard=False)
    with tempfile.TemporaryDirectory() as d:
        rec = iter_bench.build_rec(os.path.join(d, "dp"), n_img, 96)
        it = PrefetchingIter(ImageRecordIter(
            path_imgrec=rec, data_shape=(3, 64, 64), batch_size=32,
            shuffle=True, rand_crop=True, rand_mirror=True,
            color_jitter=0.2, seed=0, preprocess_threads=threads,
            num_parts=1, part_index=0))
        warmup = 2  # first steps pay compile + pipeline spin-up
        hist_before = None
        done = 0
        while done < steps_n + warmup:
            try:
                batch = it.next()
            except StopIteration:
                it.reset()
                continue
            trainer.step(batch.data[0],
                         batch.label[0]).wait_to_read()
            done += 1
            if done == warmup:
                hist_before = len(_tsteps.history())
        waits = [r["phases"].get("data_wait", 0.0)
                 for r in _tsteps.history()[hist_before:]]
    line = {
        "metric": "dataplane_native_augment",
        "value": aug["value"],
        "unit": "img/s",
        "img_s_per_core": aug["img_s_per_core"],
        "python_img_s": aug["python_img_s"],
        "speedup_vs_python": aug["speedup_vs_python"],
        "thread_scaling": aug["thread_scaling"],
        "scaling_1_to_4": aug["scaling_1_to_4"],
        "native_augment": aug["native_augment"],
        "threads": aug["threads"],
        "cores": aug["cores"],
        # the starvation check: mean/max data_wait per step (ms). ~0 =
        # the prefetched native pipeline kept the step fed
        "train_steps": len(waits),
        "train_data_wait_ms_mean":
            round(sum(waits) / len(waits), 3) if waits else None,
        "train_data_wait_ms_max":
            round(max(waits), 3) if waits else None,
    }
    iter_bench._persist(line)
    print(json.dumps(_compile_fields(line)), flush=True)


def bench_kernels():
    """Per-family kernel-vs-XLA speedup lines from the kernel-layer
    autotuner (benchmark/opperf.py bench_kernels): one
    ``kernel_vs_xla_<family>`` JSON line per registry family, recording
    the measured speedup, the winner the dispatch table now routes to,
    and the shape bucket that was timed. The run also refreshes the
    persisted dispatch table, so the bench doubles as the autotune
    pass. BENCH_SKIP_KERNELS=1 opts out."""
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmark"))
    import opperf

    runs = int(os.environ.get("BENCH_KERNEL_RUNS", 5))
    res = opperf.bench_kernels(runs=runs, warmup=2)
    for r in res["results"]:
        k_ms, x_ms = r.get("kernel_ms"), r.get("xla_ms")
        line = {
            "metric": f"kernel_vs_xla_{r['family']}",
            "value": round(x_ms / k_ms, 3) if k_ms and x_ms else None,
            "unit": "x_speedup",
            "winner": r["winner"],
            "kernel_ms": k_ms,
            "xla_ms": x_ms,
            "bucket": r["bucket"],
            "interpret": bool(r.get("interpret")),
            "platform": "tpu",
        }
        print(json.dumps(line), flush=True)


def _peak_tflops():
    """The per-device-kind peak table (TPU v3..v6e) lives in
    mxnet_tpu.telemetry.costs — BENCH_PEAK_TFLOPS override preserved,
    "0"/unset mean auto-detect from ``jax.devices()[0].device_kind``."""
    from mxnet_tpu.telemetry import costs as _tcosts

    return _tcosts.peak_tflops(env="BENCH_PEAK_TFLOPS")


def _measure_chip_peak(n=4096, chain=16):
    """Sustained bf16 matmul TFLOP/s on THIS chip, next to the nominal
    part spec. Chained inside one executable so dispatch and transfer
    amortize away."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    a = jnp.ones((n, n), jnp.bfloat16)

    @jax.jit
    def f(a):
        def body(x, _):
            return (x @ a) * (1.0 / n), None

        out, _ = lax.scan(body, a, None, length=chain)
        return out.sum()

    float(f(a))  # compile + warm
    t0 = time.perf_counter()
    float(f(a))
    t = time.perf_counter() - t0
    return chain * 2 * n ** 3 / t / 1e12


if __name__ == "__main__":
    main()
