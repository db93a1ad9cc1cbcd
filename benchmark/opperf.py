#!/usr/bin/env python
"""opperf: per-operator micro-benchmark harness over the registry.

Parity target: `benchmark/opperf/opperf.py` — run every (or a chosen
subset of) registered operator with default synthetic inputs, time
forward (and backward where differentiable), and emit results as JSON or
a console table.

Usage:
    python benchmark/opperf.py                      # common op set
    python benchmark/opperf.py --ops dot,softmax    # chosen ops
    python benchmark/opperf.py --all                # whole registry
    python benchmark/opperf.py --output-format json
    python benchmark/opperf.py --dispatch           # bulking microbench

Timing methodology matches the reference's profiler-driven runs: warmup
iterations first (includes XLA compile), then `--runs` timed executions
synchronized via wait_to_read (dispatch+device time per call).

`--dispatch` measures per-op eager dispatch overhead (ns/op) on an
elementwise op chain with engine bulking off (bulk_size=1, today's
per-op jit dispatch) vs on (one fused XLA executable per segment) — the
analogue of the reference's MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN A/B.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.ops import registry

# default input builders per op-shape family; (args, kwargs) given a size
_DEFAULT_SIZE = 1024


def _rand(*shape):
    return mx.nd.array(np.random.rand(*shape).astype(np.float32))


def _inputs_for(op_name, n):
    """Best-effort default inputs for an op; None = not benchmarkable
    with generic inputs."""
    special = {
        "dot": ([_rand(n, n), _rand(n, n)], {}),
        "batch_dot": ([_rand(8, n // 8, n // 8), _rand(8, n // 8, n // 8)],
                      {}),
        "FullyConnected": ([_rand(64, n), _rand(256, n), _rand(256)],
                           {"num_hidden": 256}),
        "Convolution": ([_rand(8, 16, 32, 32), _rand(32, 16, 3, 3),
                         _rand(32)],
                        {"kernel": (3, 3), "num_filter": 32,
                         "pad": (1, 1)}),
        "Pooling": ([_rand(8, 16, 32, 32)],
                    {"kernel": (2, 2), "stride": (2, 2),
                     "pool_type": "max"}),
        "BatchNorm": ([_rand(8, 16, 32, 32), _rand(16), _rand(16),
                       _rand(16), _rand(16)], {}),
        "softmax": ([_rand(64, n)], {}),
        "log_softmax": ([_rand(64, n)], {}),
        "sum": ([_rand(n, n)], {}),
        "mean": ([_rand(n, n)], {}),
        "transpose": ([_rand(n, n)], {}),
        "sgd_update": ([_rand(n, n), _rand(n, n)], {"lr": 0.1}),
        "sgd_mom_update": ([_rand(n, n), _rand(n, n), _rand(n, n)],
                           {"lr": 0.1, "momentum": 0.9}),
        "adam_update": ([_rand(n, n), _rand(n, n), _rand(n, n),
                         _rand(n, n)], {"lr": 0.001}),
    }
    if op_name in special:
        return special[op_name]
    # generic synthesis from the op's reflected schema (ops/schema.py —
    # the dmlc::Parameter layer): the schema names the array inputs, so
    # synthesis no longer re-derives them from raw signature inspection
    op = registry.get(op_name)
    schema = op.schema
    if schema.variadic:
        return [_rand(n, n), _rand(n, n)], {}
    arrays = []
    for pname in schema.inputs:
        if pname in ("key", "training"):
            break
        # scalar-tensor hyper inputs (loss-scale etc.), not matrices
        arrays.append(_rand(1) if pname in ("rescale_grad",)
                      else _rand(n, n))
    if not arrays:
        return None
    return arrays, {}


COMMON_OPS = [
    "elemwise_add", "broadcast_add", "broadcast_mul", "dot", "batch_dot",
    "FullyConnected", "Convolution", "Pooling", "BatchNorm", "softmax",
    "log_softmax", "relu", "sigmoid", "exp", "log", "sum", "mean",
    "transpose", "sgd_update", "sgd_mom_update", "adam_update",
]


def bench_op(op_name, size, runs, warmup, with_backward=True):
    built = _inputs_for(op_name, size)
    if built is None:
        return None
    arrays, kwargs = built
    op = registry.get(op_name)

    def run_fwd():
        out = mx.nd.invoke(op_name, *arrays, **kwargs)
        (out[0] if isinstance(out, tuple) else out).wait_to_read()
        return out

    try:
        for _ in range(warmup):
            run_fwd()
    except Exception as exc:  # op not benchmarkable with generic inputs
        return {"operator": op_name, "error": str(exc)[:80]}
    t0 = time.perf_counter()
    for _ in range(runs):
        run_fwd()
    fwd_ms = (time.perf_counter() - t0) / runs * 1e3

    bwd_ms = None
    if with_backward and op.differentiable:
        try:
            for a in arrays:
                a.attach_grad()
            with mx.autograd.record():
                out = mx.nd.invoke(op_name, *arrays, **kwargs)
                head = out[0] if isinstance(out, tuple) else out
            head.backward()
            t0 = time.perf_counter()
            for _ in range(runs):
                with mx.autograd.record():
                    out = mx.nd.invoke(op_name, *arrays, **kwargs)
                    head = out[0] if isinstance(out, tuple) else out
                head.backward()
                arrays[0].grad.wait_to_read()
            bwd_ms = (time.perf_counter() - t0) / runs * 1e3
        except Exception:
            bwd_ms = None
    entry = {"operator": op_name, "avg_fwd_ms": round(fwd_ms, 4)}
    if bwd_ms is not None:
        entry["avg_fwd_bwd_ms"] = round(bwd_ms, 4)
    return entry


def bench_dispatch(chain_len=16, bulk=16, size=_DEFAULT_SIZE, iters=250,
                   warmup=40, trials=5):
    """Per-op eager dispatch time for a `chain_len`-op elementwise chain,
    bulk_size=1 (per-op executables) vs bulk_size=`bulk` (one fused
    executable per segment). Each chain ends in wait_to_read, so the
    bulked side pays its segment flush inside the timed region; median
    over `trials` interleaved runs defends against scheduler noise."""
    import statistics

    x0 = _rand(size)

    def chain():
        x = x0
        for _ in range(chain_len // 2):
            x = x * 1.0001
            x = x + 0.0001
        x.wait_to_read()

    samples = {1: [], bulk: []}
    for _ in range(trials):
        for bs in (1, bulk):
            with mx.engine.bulk(bs):
                for _ in range(warmup):
                    chain()
                t0 = time.perf_counter()
                for _ in range(iters):
                    chain()
                dt = time.perf_counter() - t0
            samples[bs].append(dt / (iters * chain_len) * 1e9)
    unbulked = statistics.median(samples[1])
    bulked = statistics.median(samples[bulk])
    return {
        "chain_len": chain_len,
        "bulk_size": bulk,
        "tensor_size": size,
        "unbulked_ns_per_op": round(unbulked, 1),
        "bulked_ns_per_op": round(bulked, 1),
        "improvement_pct": round((unbulked - bulked) / unbulked * 100, 1),
    }


def _kernel_cases():
    """(family, builder) shape cases for the kernel autotuner. Builders
    return (args, kwargs) concrete enough to jit both sides; each case
    lands in ONE dispatch-table bucket."""
    import jax.numpy as jnp

    r = np.random.default_rng(0)

    def f32(*shape):
        return jnp.asarray(r.standard_normal(shape, dtype=np.float32))

    # NB: static scalars (scale, thr) ride in kwargs so the jit wrapper
    # below only traces the array positions — they bake into the kernel

    def flash():
        q, k, v = f32(1, 2, 128, 64), f32(1, 2, 128, 64), f32(1, 2, 128, 64)
        return (q, k, v), {"scale": 0.125, "causal": True}

    def flash_bwd():
        # what the forward hands its backward, from the dense softmax
        from mxnet_tpu.kernels.flash import (flash_attention_reference,
                                             row_log_sum_exp)

        (q, k, v), kw = flash()
        out = flash_attention_reference(q, k, v, kw["scale"], kw["causal"])
        lse = row_log_sum_exp(q, k, kw["scale"], kw["causal"])
        return (q, k, v, out, lse, f32(1, 2, 128, 64)), kw

    def int8_gemm():
        qx = jnp.asarray(r.integers(-127, 128, (128, 256)), dtype=jnp.int8)
        w = jnp.asarray(r.integers(-127, 128, (256, 256)), dtype=jnp.int8)
        sc = jnp.asarray(r.random(256), dtype=jnp.float32) * 0.01
        return (qx, w, sc), {"bias": f32(256), "relu": True}

    def decode():
        q, k, v = f32(2, 2, 64), f32(2, 2, 256, 64), f32(2, 2, 256, 64)
        lens = jnp.asarray([256, 100], dtype=jnp.int32)
        return (q, k, v, lens), {"scale": 0.125}

    def twobit_c():
        n = 65536
        return (f32(n), f32(n) * 0.1), {"thr": 0.5}

    def twobit_d():
        codes = jnp.asarray(r.integers(-4, 5, 65536), dtype=jnp.int8)
        return (codes,), {"thr": 0.5}

    return [("flash_attention", flash), ("flash_attention_bwd", flash_bwd),
            ("int8_gemm", int8_gemm),
            ("decode_attention", decode), ("twobit_compress", twobit_c),
            ("twobit_decompress", twobit_d)]


def _time_jitted(fn, args, runs, warmup):
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(runs):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / runs * 1e3


def bench_kernels(runs=10, warmup=3, families=None):
    """The kernel autotuner: time each registry family's Pallas kernel
    against its XLA baseline per shape bucket, record the winner in the
    persisted dispatch table (mxnet_tpu/kernels/table.py). Off-TPU the
    kernel side runs in the Pallas interpreter — rows are stamped
    ``interpret: true`` and honestly lose to XLA (the table then routes
    dispatch to XLA, which IS the tuned decision for this backend)."""
    import jax
    from mxnet_tpu import kernels as klayer
    from mxnet_tpu.kernels import table as ktable

    interp = not klayer.on_tpu()
    t_start = time.time()
    results = []
    for fam, build in _kernel_cases():
        if families and fam not in families:
            continue
        args, kwargs = build()
        e = klayer.entry(fam)
        if not e.supports(*args, **kwargs):
            continue
        bucket = e.bucket(*args, **kwargs)
        kfn = jax.jit(
            lambda *a, _e=e, _kw=kwargs: _e.kernel(*a, interpret=interp,
                                                   **_kw))
        xfn = jax.jit(lambda *a, _e=e, _kw=kwargs: _e.xla(*a, **_kw))
        # a kernel the compiler refuses is a failure of the run, never
        # a persisted "xla wins" row
        k_ms = _time_jitted(kfn, args, runs, warmup)
        x_ms = _time_jitted(xfn, args, runs, warmup)
        winner = "kernel" if k_ms < x_ms else "xla"
        row = ktable.record(fam, bucket, winner, k_ms, x_ms,
                            interpret=interp)
        results.append({"family": fam, "bucket": bucket, **row})
    stamp = {"when": time.time(), "duration_s": round(
        time.time() - t_start, 2), "runs": runs, "interpret": interp,
        "cases": len(results),
        "argv": " ".join(sys.argv[1:]) or "--kernels"}
    ktable.set_opperf_stamp(stamp)
    path = ktable.save()
    return {"table_path": path, "stamp": stamp, "results": results}


# The flash forward's blocks on the chip, one command (PERF.md section 5
# quotes its output): the benchmark's two attention buckets and one
# equal-width long sequence no cell holds, each at the tiles a rule could
# pick, (block_q, block_k, heads a program). The row of the tile the
# kernel picks from the shape (``flash.default_blocks``,
# ``flash.heads_a_program``) is marked ``chosen``.
_FLASH_SWEEP = [
    ("bert_base_s384", (32, 12, 384, 64, 64), False,
     [(128, 128, 1), (384, 128, 1), (128, 384, 1), (384, 384, 1),
      (384, 384, 2), (384, 384, 4), (384, 384, 8)]),
    ("kanana2_s4096", (2, 32, 4096, 192, 128), True,
     [(512, 512, 1), (512, 1024, 1), (1024, 512, 1), (1024, 1024, 1),
      (1024, 2048, 1), (2048, 1024, 1)]),
    ("gpt_like_s4096", (2, 32, 4096, 128, 128), True,
     [(128, 128, 1), (512, 512, 1), (512, 1024, 1), (1024, 1024, 1),
      (1024, 2048, 1)]),
]


def _device_ms(fn, args, runs, match=""):
    """Device time a call of ``fn`` spends in the operations whose name
    holds ``match``, from a profiler trace of ``runs`` calls, read as the
    benchmark reads its own (``chipbench/harness/trace_reduce.py``); None
    where the trace has no TPU plane (the interpreter, the CPU)."""
    import tempfile

    import jax
    from chipbench.harness import trace_reduce

    with tempfile.TemporaryDirectory() as log_dir:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=options)
        for _ in range(runs):
            jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        trace = trace_reduce.load(trace_reduce.find_xplane(log_dir))
    ns = [self_ns for events in trace["devices"].values()
          for name, self_ns in trace_reduce.self_times(events)
          if match in name]
    return round(sum(ns) / 1e6 / runs, 4) if ns else None


def sweep_flash_forward(runs=10, warmup=3, cases=None, dtype="bfloat16"):
    """Time the flash forward (output and row log-sum-exp, one Mosaic
    call) alone in a jit at every tile of ``cases`` (``_FLASH_SWEEP``):
    ``wall_ms`` a call by the host's clock, ``device_ms`` the call's own
    time in a device trace. A tile the compiler refuses (VMEM) is a row
    with its ``error``; each case ends with a row for dense XLA."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import kernels as klayer
    from mxnet_tpu.kernels import flash

    interp = not klayer.on_tpu()
    r = np.random.default_rng(0)
    rows = []
    for label, (b, h, s, d, dv), causal, tiles in cases or _FLASH_SWEEP:
        q, k, v = (jnp.asarray(r.standard_normal((b, h, s, w),
                                                 dtype=np.float32), dtype)
                   for w in (d, d, dv))
        chosen = flash.default_blocks(s, s, d, dv,
                                      jnp.dtype(dtype).itemsize)
        head = {"case": label, "shape": [b, h, s, d, dv], "dtype": dtype,
                "causal": causal, "interpret": interp}
        for bq, bk, heads in tiles:
            fn = jax.jit(lambda *a, _t=(bq, bk), _h=heads, _c=causal, _d=d:
                         flash.flash_forward_lse(*a, _d ** -0.5, _c, *_t,
                                                 interpret=interp, heads=_h))
            row = {**head, "blocks": [bq, bk], "heads": heads,
                   "chosen": (bq, bk) == chosen
                   and heads == flash.heads_a_program(b * h, s, s, bq, bk)}
            try:
                row["wall_ms"] = round(_time_jitted(fn, (q, k, v), runs,
                                                    warmup), 4)
                row["device_ms"] = _device_ms(fn, (q, k, v), runs,
                                              "tpu_custom_call")
            except Exception as e:  # the compiler's refusal is the row
                row["error"] = f"{type(e).__name__}: {str(e)[-300:]}"
            rows.append(row)
        xfn = jax.jit(lambda *a, _c=causal, _d=d:
                      flash.flash_attention_reference(*a, _d ** -0.5, _c))
        rows.append({**head, "blocks": "dense XLA", "heads": None,
                     "chosen": False,
                     "wall_ms": round(_time_jitted(xfn, (q, k, v), runs,
                                                   warmup), 4),
                     "device_ms": _device_ms(xfn, (q, k, v), runs)})
    return rows


# The windowed, grouped calls of the hybrid decoder's cell (20 query and 10
# key heads a softmax, 64 | 128 wide, window 512 of 4,096 positions) at the
# tiles ``flash._inside_band`` could pick, forward and fused backward; the
# last tile is the shape's own without a window, run with the window.
_FLASH_WINDOW_SWEEP = [
    ("phi4_flash_w512", (1, 20, 10, 4096, 64, 128), 512,
     [(128, 128), (256, 256), (256, 512), (512, 256), (512, 512),
      (1024, 1024)]),
]


def sweep_flash_window(runs=10, warmup=3, cases=None, dtype="bfloat16"):
    """Time the causal flash forward and the fused backward with a
    ``window`` and grouped keys, each alone in a jit, at every tile of
    ``cases`` (``_FLASH_WINDOW_SWEEP``): ``device_ms`` is the Mosaic
    call's own time in a device trace. ``chosen`` marks the tile the
    forward / the backward picks (``flash._inside_band``)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import kernels as klayer
    from mxnet_tpu.kernels import flash

    interp = not klayer.on_tpu()
    r = np.random.default_rng(0)
    rows = []
    for label, (b, h, hk, s, d, dv), window, tiles in \
            cases or _FLASH_WINDOW_SWEEP:
        q, k, v, cot = (jnp.asarray(r.standard_normal(
            (b, heads, s, w), dtype=np.float32), dtype)
            for heads, w in ((h, d), (hk, d), (hk, dv), (h, dv)))
        scale = d ** -0.5
        out, lse = flash.flash_forward_lse(
            q, k, v, scale, True, *flash._blocks(q, k, v, window=window),
            interpret=interp, window=window)
        picks = {"forward": flash._blocks(q, k, v, window=window),
                 "backward": flash._blocks_for(q, k, v, window=window)}
        for bq, bk in tiles:
            sides = {
                "forward": (jax.jit(lambda *a, _t=(bq, bk):
                                    flash.flash_forward_lse(
                                        *a, scale, True, *_t,
                                        interpret=interp, window=window)),
                            (q, k, v)),
                "backward": (jax.jit(lambda *a, _t=(bq, bk):
                                     flash.flash_backward_kernel(
                                         *a, scale, True, *_t,
                                         interpret=interp, window=window)),
                             (q, k, v, out, lse, cot))}
            for side, (fn, args) in sides.items():
                row = {"case": label, "shape": [b, h, hk, s, d, dv],
                       "window": window, "dtype": dtype, "side": side,
                       "blocks": [bq, bk], "interpret": interp,
                       "chosen": (bq, bk) == picks[side]}
                try:
                    row["wall_ms"] = round(_time_jitted(fn, args, runs,
                                                        warmup), 4)
                    row["device_ms"] = _device_ms(fn, args, runs,
                                                  "tpu_custom_call")
                except Exception as e:  # the compiler's refusal is the row
                    row["error"] = f"{type(e).__name__}: {str(e)[-300:]}"
                rows.append(row)
    return rows


def run_benchmark(ops, size=_DEFAULT_SIZE, runs=10, warmup=2):
    results = []
    for name in ops:
        res = bench_op(name, size, runs, warmup)
        if res is not None:
            results.append(res)
    return results


def main():
    parser = argparse.ArgumentParser(description="op micro-benchmarks")
    parser.add_argument("--ops", type=str, default="",
                        help="comma-separated op names (default: common set)")
    parser.add_argument("--all", action="store_true",
                        help="benchmark every registered op")
    parser.add_argument("--size", type=int, default=_DEFAULT_SIZE)
    parser.add_argument("--runs", type=int, default=10)
    parser.add_argument("--warmup", type=int, default=2)
    parser.add_argument("--output-format", type=str, default="table",
                        choices=("table", "json"))
    parser.add_argument("--dispatch", action="store_true",
                        help="run the engine-bulking dispatch-overhead "
                             "microbench instead of per-op timings")
    parser.add_argument("--kernels", action="store_true",
                        help="autotune the Pallas kernel layer: time "
                             "kernel vs XLA per (family, shape bucket) "
                             "and persist the winner dispatch table")
    parser.add_argument("--families", type=str, default="",
                        help="comma-separated kernel families for "
                             "--kernels (default: all registered)")
    parser.add_argument("--flash-sweep", type=str, default="",
                        metavar="DIR",
                        help="time the flash forward at every tile of "
                             "_FLASH_SWEEP and the windowed forward and "
                             "backward at those of _FLASH_WINDOW_SWEEP, "
                             "print the tables and keep them as "
                             "DIR/flash_{forward,window}_sweep.json")
    parser.add_argument("--chain", type=int, default=16,
                        help="op-chain length for --dispatch")
    parser.add_argument("--bulk", type=int, default=16,
                        help="bulk_size for the bulked side of --dispatch")
    args = parser.parse_args()

    if args.flash_sweep:
        rows = sweep_flash_forward(runs=args.runs, warmup=args.warmup)
        os.makedirs(args.flash_sweep, exist_ok=True)
        with open(os.path.join(args.flash_sweep,
                               "flash_forward_sweep.json"), "w") as f:
            json.dump(rows, f, indent=1)
        window_rows = sweep_flash_window(runs=args.runs, warmup=args.warmup)
        with open(os.path.join(args.flash_sweep,
                               "flash_window_sweep.json"), "w") as f:
            json.dump(window_rows, f, indent=1)
        print(f"{'Case':<16s} {'Blocks':<12s} {'Heads':>5s} "
              f"{'Wall ms':>9s} {'Device ms':>10s}")
        for r in rows:
            blocks = r["blocks"] if isinstance(r["blocks"], str) \
                else "{} x {}".format(*r["blocks"])
            print(f"{r['case']:<16s} {blocks:<12s} {r['heads'] or '-':>5} "
                  f"{r.get('wall_ms', '-'):>9} "
                  f"{r.get('device_ms') or '-':>10}"
                  + (" <- the shape's" if r["chosen"] else "")
                  + ("  " + r["error"][-120:] if "error" in r else ""))
        for r in window_rows:
            print(f"{r['case']:<16s} {'{} x {}'.format(*r['blocks']):<12s} "
                  f"{r['side'][:5]:>5} {r.get('wall_ms', '-'):>9} "
                  f"{r.get('device_ms') or '-':>10}"
                  + (" <- the window's" if r["chosen"] else "")
                  + ("  " + r["error"][-120:] if "error" in r else ""))
        if rows and rows[0]["interpret"]:
            print("timed in the Pallas INTERPRETER (no TPU here): not a "
                  "hardware speed claim")
        return

    if args.kernels:
        fams = [f for f in args.families.split(",") if f] or None
        res = bench_kernels(runs=args.runs, warmup=args.warmup,
                            families=fams)
        if args.output_format == "json":
            print(json.dumps(res, indent=2))
        else:
            where = res["table_path"] or "(memory only — set " \
                "MXNET_TPU_CACHE_DIR to persist)"
            print(f"kernel dispatch table -> {where}")
            print(f"{'Family':<20s} {'Bucket':<34s} {'Kernel ms':>10s} "
                  f"{'XLA ms':>9s} {'Speedup':>8s} {'Winner':>7s}")
            for r in res["results"]:
                k = r.get("kernel_ms")
                x = r.get("xla_ms")
                sp = r.get("speedup")
                tag = r["winner"] + ("*" if r.get("interpret") else "")
                print(f"{r['family']:<20s} {r['bucket']:<34s} "
                      f"{k if k is not None else '-':>10} "
                      f"{x if x is not None else '-':>9} "
                      f"{sp if sp is not None else '-':>8} {tag:>7s}")
            if any(r.get("interpret") for r in res["results"]):
                print("* kernel timed in the Pallas INTERPRETER (no TPU "
                      "here) — not a hardware speed claim")
        return

    if args.dispatch:
        res = bench_dispatch(chain_len=args.chain, bulk=args.bulk,
                             size=args.size)
        if args.output_format == "json":
            print(json.dumps(res, indent=2))
        else:
            print(f"{args.chain}-op elementwise chain, tensor size "
                  f"{args.size}, CPU backend")
            print(f"  bulk_size=1           : "
                  f"{res['unbulked_ns_per_op']:>10.1f} ns/op")
            print(f"  bulk_size={args.bulk:<12d}: "
                  f"{res['bulked_ns_per_op']:>10.1f} ns/op")
            print(f"  dispatch improvement  : "
                  f"{res['improvement_pct']:>10.1f} %")
        return

    if args.ops:
        ops = args.ops.split(",")
    elif args.all:
        ops = registry.list_ops()
    else:
        ops = COMMON_OPS
    results = run_benchmark(ops, args.size, args.runs, args.warmup)
    if args.output_format == "json":
        print(json.dumps(results, indent=2))
    else:
        print(f"{'Operator':<32s} {'Fwd (ms)':>10s} {'Fwd+Bwd (ms)':>14s}")
        for r in results:
            if "error" in r:
                print(f"{r['operator']:<32s} {'SKIP: ' + r['error']}")
            else:
                bwd = r.get("avg_fwd_bwd_ms")
                print(f"{r['operator']:<32s} {r['avg_fwd_ms']:>10.4f} "
                      f"{bwd if bwd is not None else '-':>14}")


if __name__ == "__main__":
    main()
