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
import functools
import json
import os
import re
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


def _device_ops(fn, args, runs):
    """``{operation: ms a call}`` of ``fn``'s device operations by self
    time, from a profiler trace of ``runs`` calls, read as the benchmark
    reads its own (``chipbench/harness/trace_reduce.py``); empty where the
    trace has no TPU plane (the interpreter, the CPU)."""
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
    ops = {}
    for events in trace["devices"].values():
        for name, self_ns in trace_reduce.self_times(events):
            ops[name] = ops.get(name, 0.0) + self_ns / 1e6 / runs
    return ops


def _device_ms(fn, args, runs, match=""):
    """Device time a call of ``fn`` spends in the operations whose name
    holds ``match``; None where the trace has no TPU plane."""
    ms = [t for name, t in _device_ops(fn, args, runs).items()
          if match in name]
    return round(sum(ms), 4) if ms else None


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


# The gradient of ``Embedding`` on the chip, one command (the docstring of
# ``ops/tensor.py`` ``embedding_grad_columns`` quotes its output): the
# table's cotangent alone in a jit, rows of Zipf(1) ids from a seed, at the
# three text cells' tables, at what separates the causes of the hybrid
# decoder's 10 ms (unique ids, the tied table, half the table, float32), at
# the published vocabularies, and over widths and row counts around them.
# ``forms`` names the op's own (``scatter`` whole, ``columns_N`` in blocks of
# N columns) and the candidates that lost.
_ZIPF, _UNIQUE, _ARANGE = "zipf", "unique", "arange"
_EVERY_FORM = ("scatter", "columns_512", "columns_1024", "product",
               "sorted_dedup", "row_blocks_2", "id_chunks_8")
_EMBEDDING_GRAD_SWEEP = [
    # label, rows, (vocab, width), dtype, ids, tied, forms
    ("phi4_flash", 4096, (25008, 2560), "bfloat16", _ZIPF, False,
     _EVERY_FORM + ("columns_256", "row_blocks_4")),
    ("phi4_flash_unique_ids", 4096, (25008, 2560), "bfloat16", _UNIQUE,
     False, ("scatter", "columns_512", "product")),
    ("phi4_flash_tied", 4096, (25008, 2560), "bfloat16", _ZIPF, True,
     ("scatter", "columns_512", "columns_1024", "product", "id_chunks_8")),
    ("phi4_flash_half_table", 4096, (12504, 2560), "bfloat16", _ZIPF, False,
     ("scatter", "columns_512", "product")),
    ("phi4_flash_float32", 4096, (25008, 2560), "float32", _ZIPF, False,
     ("scatter", "columns_512", "product")),
    ("kanana2", 8192, (16032, 2048), "bfloat16", _ZIPF, False, _EVERY_FORM),
    ("bert_words", 12288, (30522, 768), "bfloat16", _ZIPF, False,
     _EVERY_FORM[:1] + ("columns_256",) + _EVERY_FORM[1:]),
    ("bert_positions", 384, (512, 768), "bfloat16", _ARANGE, False,
     ("scatter", "product")),
    ("bert_types", 12288, (2, 768), "bfloat16", _ZIPF, False,
     ("scatter", "columns_512", "product")),
    ("phi4_flash_published", 4096, (200064, 2560), "bfloat16", _ZIPF, False,
     ("scatter", "columns_512", "product", "sorted_dedup",
      "row_blocks_16")),
    ("phi4_flash_published_tied", 4096, (200064, 2560), "bfloat16", _ZIPF,
     True, ("scatter", "sorted_dedup")),
    ("phi4_flash_published_32k_rows", 32768, (200064, 2560), "bfloat16",
     _ZIPF, False, ("scatter", "columns_512")),
    ("kanana2_published", 8192, (128256, 2048), "bfloat16", _ZIPF, False,
     ("scatter", "columns_512", "sorted_dedup")),
] + [
    (f"width_{w}", 4096, (25008, w), "bfloat16", _ZIPF, False,
     ("scatter", "columns_512") if w > 512 else ("scatter",))
    for w in (256, 512, 768, 1024, 1280, 1536, 2048, 2304, 3072, 4096, 5120)
] + [
    (f"rows_{n}", n, (25008, 2560), "bfloat16", _ZIPF, False,
     ("scatter", "columns_512", "product"))
    for n in (1024, 2048, 3200, 16384)
] + [
    (f"row_by_row_width_{w}", 4096, (200064, w), "bfloat16", _ZIPF, False,
     ("scatter",)) for w in (768, 2048)
]


def _embedding_grad_forms():
    """``{name: f(ids, cot, vocab)}``: the table's cotangent as
    ``Embedding``'s backward computes it, whole (``scatter``) or in blocks
    of columns (``columns_512`` is the op's block), and by the candidates
    that lost (my chip runs, PR 32)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import tensor

    def zeros(vocab, cot):
        return jnp.zeros((vocab, cot.shape[1]), cot.dtype)

    def summed(same, cot):
        return jax.lax.dot_general(
            same.astype(cot.dtype), cot, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(cot.dtype)

    def sorted_dedup(ids, cot, vocab):
        # ids sorted; an (N, N) product sums a run of equal ids into its
        # first row in float32; the other rows go out of range (dropped)
        order = jnp.argsort(ids)
        ids, cot = ids[order], cot[order]
        first = jnp.concatenate([jnp.ones((1,), bool), ids[1:] != ids[:-1]])
        same = (ids[:, None] == ids[None, :]) & first[:, None]
        return jnp.where(first, ids, vocab), summed(same, cot)

    def unique_scatter(rows_of):
        def form(ids, cot, vocab):
            rows, sums = rows_of(ids, cot, vocab)
            return zeros(vocab, cot).at[rows].add(
                sums, unique_indices=True, mode="drop")
        return form

    def row_blocks(k):
        # the table in k blocks of rows, each scattered on its own
        def form(ids, cot, vocab):
            size = -(-vocab // k // 8) * 8
            parts = []
            for lo in range(0, vocab, size):
                n = min(size, vocab - lo)
                local = jnp.where((ids >= lo) & (ids < lo + n), ids - lo, n)
                parts.append(zeros(n, cot).at[local].add(cot, mode="drop"))
            return jnp.concatenate(parts)
        return form

    def id_chunks(k):
        # duplicates summed first, then k scatters of rows / k ids each
        # into one table: each is small against the table, so XLA updates
        # row by row, in place
        def form(ids, cot, vocab):
            rows, sums = sorted_dedup(ids, cot, vocab)
            out, n = zeros(vocab, cot), ids.shape[0] // k
            for lo in range(0, ids.shape[0], n):
                out = out.at[rows[lo:lo + n]].add(sums[lo:lo + n],
                                                  mode="drop")
            return out
        return form

    def product(ids, cot, vocab):
        # one_hot(ids)^T @ cot, float32 accumulator; XLA fuses the
        # comparison into the product's operand (no one-hot in memory)
        one_hot = ids[:, None] == jnp.arange(vocab, dtype=ids.dtype)[None, :]
        return jax.lax.dot_general(
            one_hot.astype(cot.dtype), cot, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(cot.dtype)

    def column_blocks(columns):
        # the op's own form: the table in blocks of columns, each
        # scattered on its own (``None``: one block, XLA's scatter whole)
        return lambda ids, cot, vocab: tensor._embedding_grad(
            ids, cot, vocab, columns or cot.shape[1])

    forms = {"scatter": column_blocks(None), "product": product,
             "sorted_dedup": unique_scatter(sorted_dedup)}
    forms.update({f"columns_{c}": column_blocks(c)
                  for c in (256, 512, 1024)})
    forms.update({f"row_blocks_{k}": row_blocks(k) for k in (2, 4, 16)})
    forms["id_chunks_8"] = id_chunks(8)
    return forms


def _sweep_ids(kind, rows, vocab, seed):
    """``rows`` ids over ``vocab``: Zipf(1) as the text cells draw them
    (id ``i`` with probability proportional to ``1 / (i + 1)``), a random
    choice without repeats, or 0, 1, 2, ... (BERT's positions)."""
    rng = np.random.default_rng(seed)
    if kind == _UNIQUE:
        return rng.permutation(vocab)[:rows].astype(np.int32)
    if kind == _ARANGE:
        return np.arange(rows, dtype=np.int32)
    p = 1.0 / np.arange(1, vocab + 1)
    return np.minimum(np.searchsorted(np.cumsum(p) / p.sum(),
                                      rng.random(rows), side="right"),
                      vocab - 1).astype(np.int32)


def sweep_embedding_grad(runs=10, warmup=3, cases=None, seed=32,
                         text_dir=None):
    """Time the cotangent of ``Embedding``'s table alone in a jit in every
    form of ``cases`` (``_EMBEDDING_GRAD_SWEEP``): ``device_ms`` a call
    over all its device operations with the largest of them beside it,
    ``wall_ms`` by the host's clock. A tied case adds the cotangent to a
    table it is given (the head's dW), donated, as the step does (a new
    table a call: its ``wall_ms`` also holds that table's first use, ~12
    ms on the chip; read its ``device_ms``). From the
    compiled text: ``emitter`` says whether XLA sorted the ids itself and
    walks the table (``sorted``) or updates it row by row, ``S(1)`` whether a
    scatter's result got the compiler's fast-memory placement. ``max_err``
    and ``id0_err`` are against a float64 sum of the same rows (id 0 has
    the most duplicates). ``chosen`` marks the form the op takes
    (``tensor.embedding_grad_columns``). The compiled text of every row
    goes to ``text_dir``."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import kernels as klayer
    from mxnet_tpu.ops import tensor

    forms = _embedding_grad_forms()
    rows_out = []
    for label, rows, (vocab, width), dtype, kind, tied, names in \
            cases or _EMBEDDING_GRAD_SWEEP:
        host_ids = _sweep_ids(kind, rows, vocab, seed)
        ids = jnp.asarray(host_ids)
        cot = jnp.asarray(np.random.default_rng(seed + 1).standard_normal(
            (rows, width), dtype=np.float32), dtype)
        want = np.zeros((vocab, width), np.float64)
        np.add.at(want, host_ids, np.asarray(cot, np.float64))
        head = None
        if tied:
            head = np.random.default_rng(seed + 2).standard_normal(
                (vocab, width), dtype=np.float32)
            want += np.asarray(jnp.asarray(head, dtype), np.float64)
        columns = tensor.embedding_grad_columns(rows, vocab, width)
        chosen = "scatter" if columns >= width else f"columns_{columns}"
        for name in names:
            form = forms[name]
            row = {"case": label, "rows": rows, "table": [vocab, width],
                   "dtype": dtype, "ids": kind, "tied": tied, "form": name,
                   "chosen": name == chosen, "on_tpu": klayer.on_tpu()}
            try:
                if tied:
                    fn = jax.jit(lambda dw, i, c, _f=form:
                                 dw + _f(i, c, dw.shape[0]),
                                 donate_argnums=0)
                    args = lambda: (jnp.asarray(head, dtype), ids, cot)
                else:
                    fn = jax.jit(lambda i, c, _f=form, _v=vocab:
                                 _f(i, c, _v))
                    args = lambda: (ids, cot)
                text = fn.lower(*args()).compile().as_text()
                if text_dir:
                    with open(os.path.join(
                            text_dir, f"{label}.{name}.hlo.txt"), "w") as f:
                        f.write(text)
                scatters = [line for line in text.splitlines()
                            if " scatter(" in line]
                row["emitter"] = None if not scatters else "sorted" if any(
                    "indices_are_sorted=true" in line for line in scatters) \
                    else "row_by_row"
                row["S(1)"] = [
                    "S(1)" in line.split(" scatter(")[0] for line in scatters]
                err = np.abs(np.asarray(fn(*args()), np.float64) - want)
                row["max_err"] = float(err.max())
                row["id0_err"] = float(err[0].max())
                # a donated table is spent by its call: a new one a call,
                # made before the clock starts
                wall = 0.0
                for i in range(warmup + runs):
                    a = jax.block_until_ready(args())
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(*a))
                    wall += (time.perf_counter() - t0) * (i >= warmup)
                row["wall_ms"] = round(wall / runs * 1e3, 4)
                traced = iter([jax.block_until_ready(args())
                               for _ in range(runs)])
                ops = _device_ops(lambda: fn(*next(traced)), (), runs)
                if ops:
                    row["device_ms"] = round(sum(ops.values()), 4)
                    row["largest_ops"] = [
                        [n, round(t, 4)] for n, t in sorted(
                            ops.items(), key=lambda kv: -kv[1])[:3]]
            except Exception as e:  # the compiler's refusal is the row
                row["error"] = f"{type(e).__name__}: {str(e)[-300:]}"
            rows_out.append(row)
    return rows_out


# Dropout where the cells run it: after a Dense, before a residual add and
# a row mean (BERT's attention output and ``ffn2``: 12,288 rows of 768 out
# of 768 and of 3,072, p 0.1), and the vision heads' (128, 4096) at p 0.5.
# (label, rows, in_units, units, p)
_DROPOUT_SWEEP = [
    ("bert_attn_out", 12288, 768, 768, 0.1),
    ("bert_ffn2", 12288, 3072, 768, 0.1),
    ("vision_head", 128, 4096, 4096, 0.5),
]


def _dropout_forms():
    """``{name: f(x, key, p)}``: the op as it is (``generator``: the words
    of XLA's bit generator, drawn once a call) and the two forms that lost
    (my chip runs, PR 33): the threefry ``bernoulli`` the op compared
    before, whose hash XLA copies into every fusion that wants the mask,
    and the same threefry mask drawn once, held as the residual of a
    ``custom_vjp`` behind an ``optimization_barrier``."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import nn

    def masked(x, mask, p):
        return jnp.where(mask, x / (1.0 - p), jnp.zeros((), x.dtype))

    def threefry(x, key, p):
        return masked(x, jax.random.bernoulli(key, 1.0 - p, x.shape), p)

    @jax.custom_vjp
    def held(x, key, p):
        return held_fwd(x, key, p)[0]

    def held_fwd(x, key, p):
        mask = jax.lax.optimization_barrier(
            jax.random.bernoulli(key, 1.0 - p, x.shape))
        return masked(x, mask, p), (mask, p)

    def held_bwd(res, cot):
        mask, p = res
        return masked(cot, mask, p), None, None

    held.defvjp(held_fwd, held_bwd)
    return {"threefry": threefry,
            "generator": lambda x, key, p: nn._dropout(x, key, p=p),
            "threefry_once": held}


def sweep_dropout(runs=10, warmup=3, cases=None, dtype="bfloat16",
                  text_dir=None):
    """Time forward + backward of ``res + Dropout(x @ w + b)`` under a sum
    of squared row means (LayerNorm's first reduction), alone in a jit (the
    loss and the gradients of x, w and b), in every form of
    ``_dropout_forms`` at the shapes of ``cases`` (``_DROPOUT_SWEEP``):
    ``device_ms`` a call over all its device operations with the largest
    three beside it, ``wall_ms`` by the host's clock. From the compiled
    text: ``generator_ops`` counts ``rng-bit-generator`` ops, ``hashes``
    how many times the threefry hash is evaluated over the activation
    (``dropout_program_counts``) and ``hash_in_product`` whether a fused
    computation holds both a ``convolution`` and the hash. The compiled text
    of every row goes to ``text_dir``."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import kernels as klayer

    forms = _dropout_forms()
    rows_out = []
    for label, rows, in_units, units, p in cases or _DROPOUT_SWEEP:
        r = np.random.default_rng(0)
        x = jnp.asarray(r.standard_normal((rows, in_units),
                                          dtype=np.float32), dtype)
        w = jnp.asarray(r.standard_normal((in_units, units),
                                          dtype=np.float32) * 0.02, dtype)
        b = jnp.zeros((units,), dtype)
        res = jnp.asarray(r.standard_normal((rows, units),
                                            dtype=np.float32), dtype)
        key = jax.random.PRNGKey(0)
        for name, form in forms.items():
            def loss(x, w, b, res, key, _f=form, _p=p):
                y = res + _f(x @ w + b, key, _p)
                return jnp.sum(jnp.square(
                    jnp.mean(y.astype(jnp.float32), axis=-1)))

            fn = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
            args = (x, w, b, res, key)
            row = {"case": label, "rows": rows, "in_units": in_units,
                   "units": units, "p": p, "dtype": dtype, "form": name,
                   "chosen": name == "generator", "on_tpu": klayer.on_tpu()}
            _time_row(row, fn, args, runs, warmup,
                      lambda text: dropout_program_counts(text,
                                                          (rows, units)),
                      text_dir and os.path.join(text_dir,
                                                f"{label}.{name}.hlo.txt"))
            rows_out.append(row)
    return rows_out


def _time_row(row, fn, args, runs, warmup, counts, text_path=None, largest=3):
    """Fill ``row`` for the jitted ``fn(*args)``: what ``counts`` reads off
    its compiled text (kept as ``text_path``), ``wall_ms`` a call by the
    host's clock, ``device_ms`` over all its device operations from a trace
    with the ``largest`` of them beside it; the compiler's refusal is the
    row's ``error``."""
    try:
        text = fn.lower(*args).compile().as_text()
        if text_path:
            with open(text_path, "w") as f:
                f.write(text)
        row.update(counts(text))
        row["wall_ms"] = round(_time_jitted(fn, args, runs, warmup), 4)
        ops = _device_ops(fn, args, runs)
        if ops:
            row["device_ms"] = round(sum(ops.values()), 4)
            row["largest_ops"] = [
                [n, round(t, 4)] for n, t in sorted(
                    ops.items(), key=lambda kv: -kv[1])[:largest]]
    except Exception as e:
        row["error"] = f"{type(e).__name__}: {str(e)[-300:]}"


def _computations(text):
    """``{name: text}`` of a compiled program's computations; the entry's is
    the one that starts with ``ENTRY``."""
    return {m.group(1): m.group(0) for m in re.finditer(
        r"^(?:ENTRY )?%(\S+) \(.*?^}", text, re.MULTILINE | re.DOTALL)}


def _holds(bodies, found):
    """``name -> bool``: ``found(body)`` of the computation ``name`` or of
    any it ``calls`` (a product's fusion holds the producer of an operand
    as a nested fusion)."""
    memo = {}

    def holds(name):
        if name not in memo:
            memo[name] = False          # a computation does not call itself
            body = bodies.get(name, "")
            memo[name] = bool(found(body)) or any(
                holds(callee)
                for callee in re.findall(r"calls=%([\w.\-]+)", body))
        return memo[name]

    return holds


def dropout_program_counts(text, shape):
    """What a compiled program's text says of the Dropout masks over
    activations of ``shape``: ``generator_ops`` (``rng-bit-generator``
    ops), ``hashes`` (evaluations of the threefry hash over the
    activation: the ``xor`` ops with a ``u32`` result of its size / 21,
    twenty rounds and the two words' ``xor``) and ``hash_in_product`` (a
    fused computation holds a ``convolution`` and such an ``xor``)."""
    n = int(np.prod(shape))
    xor = re.compile(r"= u32\[([0-9,]+)\]\S* xor\(")

    def hash_xors(block):
        return sum(1 for dims in xor.findall(block)
                   if int(np.prod([int(d) for d in dims.split(",")])) == n)

    bodies = _computations(text)
    holds_hash = _holds(bodies, hash_xors)
    return {
        "generator_ops": len(re.findall(r" rng-bit-generator\(", text)),
        "hashes": round(hash_xors(text) / 21, 2),
        "hash_in_product": any(
            " convolution(" in body and holds_hash(name)
            for name, body in bodies.items() if not body.startswith("ENTRY"))}


# Exact GELU where BERT runs it: between ``ffn1`` (768 -> 3,072) and ``ffn2``
# (3,072 -> 768) over a batch of 32 x 384. (label, batch, units, hidden)
_GELU_SWEEP = [("bert_ffn", (32, 384), 768, 3072)]


def _gelu_forms():
    """``{name: f(x)}``: ``jax.nn.gelu(x, approximate=False)`` as the op
    called it until PR 35 (XLA copies the ``erfc`` expansion into every
    fusion that wants ``gelu(h)`` or its slope), the op as it is
    (``kept_erfc``: ``erfc`` behind an ``optimization_barrier``, the value
    rebuilt as ``0.5 * h * erfc`` wherever it is wanted, the backward reads
    ``h`` and ``erfc``) and the form that lost on the chip (``ops/math.py``
    ``exact_gelu`` holds the readings): the value kept behind the barrier
    beside ``erfc``."""
    import jax
    from mxnet_tpu.ops import math as mops

    @jax.custom_vjp
    def kept_erfc_and_value(x):
        return jax.nn.gelu(x, approximate=False)

    def kept_erfc_and_value_fwd(x):
        e = jax.lax.erfc(-x * np.sqrt(0.5).astype(x.dtype))
        out, e = jax.lax.optimization_barrier((0.5 * x * e, e))
        return out, (x, e)

    kept_erfc_and_value.defvjp(kept_erfc_and_value_fwd, mops._exact_gelu_bwd)

    return {"jax": lambda x: jax.nn.gelu(x, approximate=False),
            "kept_erfc": mops.exact_gelu,
            "kept_erfc_and_value": kept_erfc_and_value}


def sweep_gelu(runs=10, warmup=3, cases=None, dtype="bfloat16",
               text_dir=None):
    """Time exact GELU where ``bert_base`` runs it, alone in a jit, at the
    shapes of ``cases`` (``_GELU_SWEEP``), through the ops a layer calls
    (``FullyConnected(flatten=False)``, ``LayerNorm``). ``product``:
    ``ffn2(f(h))`` alone, forward only, with no activation (``none``) and
    with ``jax.nn.gelu(h, approximate=False)`` on the operand. ``step``:
    forward + backward of a layer's second half, ``LayerNorm(x +
    ffn2(f(ffn1(x))))`` under a sum of squares (the loss and the gradients
    of x, both layers' weights and biases, gamma and beta), in every form
    of ``_gelu_forms``; it is the LayerNorm behind the residual that makes
    XLA copy the ``erfc`` expansion into ``ffn2``'s product and its dW
    (without it the compiler keeps ``gelu(h)`` itself). ``device_ms`` a
    call over all its device operations with the largest six beside it,
    ``wall_ms`` by the host's clock, ``exponentials`` and ``holders`` off
    the compiled text (``gelu_program_counts``). The compiled text of every
    row goes to ``text_dir``."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import kernels as klayer
    from mxnet_tpu.ops import nn

    forms = _gelu_forms()
    dense = functools.partial(nn._fully_connected, flatten=False)
    rows_out = []
    for label, batch, units, hidden in cases or _GELU_SWEEP:
        r = np.random.default_rng(0)

        def normal(*shape, scale=1.0):
            return jnp.asarray(r.standard_normal(shape, dtype=np.float32)
                               * scale, dtype)

        x, h = normal(*batch, units), normal(*batch, hidden)
        w1, w2 = normal(hidden, units, scale=0.02), \
            normal(units, hidden, scale=0.02)
        b1, b2 = jnp.zeros((hidden,), dtype), jnp.zeros((units,), dtype)
        gamma, beta = jnp.ones((units,), dtype), jnp.zeros((units,), dtype)

        def step(f, x, w1, b1, w2, b2, gamma, beta):
            y = nn._layer_norm(x + dense(f(dense(x, w1, b1)), w2, b2),
                               gamma, beta)
            return jnp.sum(jnp.square(y.astype(jnp.float32)))

        timed = [("product", "none", jax.jit(dense), (h, w2, b2)),
                 ("product", "jax", jax.jit(
                     lambda h, w2, b2: dense(forms["jax"](h), w2, b2)),
                  (h, w2, b2))]
        timed += [("step", name, jax.jit(jax.value_and_grad(
            functools.partial(step, form), argnums=tuple(range(7)))),
            (x, w1, b1, w2, b2, gamma, beta)) for name, form in forms.items()]
        for what, name, fn, args in timed:
            row = {"case": label, "what": what, "batch": list(batch),
                   "units": units, "hidden": hidden, "dtype": dtype,
                   "form": name, "on_tpu": klayer.on_tpu(),
                   "chosen": what == "step" and name == "kept_erfc"}
            _time_row(row, fn, args, runs, warmup,
                      lambda text: gelu_program_counts(
                          text, tuple(batch) + (hidden,)),
                      text_dir and os.path.join(
                          text_dir, f"{label}.{what}.{name}.hlo.txt"),
                      largest=6)
            rows_out.append(row)
    return rows_out


def gelu_program_counts(text, shape):
    """What a compiled program's text says of exact GELU over activations
    of ``shape`` (any leading axes: 32 x 384 x 3,072 counts as 12,288 x
    3,072): ``exponentials``, the ``exponential`` instructions with a
    float32 result of that size (one in each copy of the ``erfc`` expansion
    and one in the density of the slope: two a GELU call where each is
    evaluated once), and ``holders``, the result types of the entry's
    fusions that hold one, themselves or in a fusion they call."""
    n = int(np.prod(shape))
    exp = re.compile(r"= f32\[([0-9,]+)\]\S* exponential\(")

    def exponentials(block):
        found = ([int(d) for d in dims.split(",")]
                 for dims in exp.findall(block))
        return sum(1 for dims in found
                   if dims[-1] == shape[-1] and int(np.prod(dims)) == n)

    bodies = _computations(text)
    holds = _holds(bodies, exponentials)
    entry = next(b for b in bodies.values() if b.startswith("ENTRY"))
    holders = [", ".join(re.findall(r"\w+\[[\d,]*\]", m.group(1)))
               for m in re.finditer(
                   r"^\s*(?:ROOT )?%\S+ = (.+?) fusion\(.*?calls=%([\w.\-]+)",
                   entry, re.MULTILINE) if holds(m.group(2))]
    return {"exponentials": exponentials(text), "holders": sorted(holders)}


# The expert layer's first-rung grouped products at the three expert cells'
# widths (first rung's rows, held experts, hidden, expert width), one row a
# (form, tile): each of the six product shapes of a rung at every tile that
# divides its shape and is estimated to fit VMEM, beside jax.lax.ragged_dot.
_GMM_SWEEP = [
    ("mellum2_12b", 17408, 16, 2304, 896),
    ("lfm2_8b_a1b", 9216, 8, 2048, 1792),
    ("kanana2_30b_a3b", 7168, 16, 2048, 768),
]


def _gmm_tiles(mode, rows, k, n, cap=15 * 2 ** 20):
    """The tiles the sweep tries for one product: row tiles of 512 and
    1,024, K and N tiles of at least 384 (or the whole width) that divide
    the shape, inside ``cap`` bytes of VMEM by ``vmem_bytes``."""
    from mxnet_tpu.kernels import grouped_matmul as gm

    def sides(width):
        got = [d for d in gm._divisors(width, width) if d >= 384]
        return got or [width]

    return [(tm, tk, tn) for tm in (512, 1024) if rows % tm == 0
            for tk in sides(k) for tn in sides(n)
            if gm.vmem_bytes(mode, tm, tk, tn) <= cap]


def sweep_grouped_matmul(runs=20, warmup=3, cases=None, seed=41):
    """Time the ``grouped_matmul`` kernel at every tile ``_gmm_tiles``
    gives, for the six products of a rung (``nn`` / ``nt`` / ``tn`` at h x f
    and f x h) at each case's first-rung rows, and ``jax.lax.ragged_dot``
    beside it: ``wall_ms`` is a call's mean over ``runs`` calls queued
    back to back (the host's clock; the calls are ~0.3-3 ms, far above
    what a dispatch costs), ``err`` the largest difference from
    ``ragged_dot`` on the live rows over its largest magnitude. The live
    rows are 15/16 of the rung, split unevenly over the groups. A tile the
    compiler refuses is a row with its ``error``; the fastest tile of each
    product is marked ``best``."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import kernels as klayer
    from mxnet_tpu.kernels import grouped_matmul as gm

    interp = not klayer.on_tpu()
    r = np.random.default_rng(seed)
    rows_out = []
    for label, rows, groups, h, f in cases or _GMM_SWEEP:
        share = r.dirichlet(np.full(groups, 8.0))
        sizes = np.floor(share * rows * 15 / 16).astype(np.int32)
        sizes = jnp.asarray(sizes)
        live = int(sizes.sum())
        bf = jnp.bfloat16
        for k, n in ((h, f), (f, h)):
            a = jnp.asarray(r.standard_normal((rows, k), np.float32), bf)
            operands = {
                "nn": jnp.asarray(r.standard_normal((groups, k, n),
                                                    np.float32), bf),
                "nt": jnp.asarray(r.standard_normal((groups, n, k),
                                                    np.float32), bf),
                "tn": jnp.asarray(r.standard_normal((rows, n), np.float32),
                                  bf)}
            for mode, b in operands.items():
                head = {"case": label, "mode": mode, "rows": rows,
                        "live": live, "k": k, "n": n, "groups": groups,
                        "interpret": interp}
                want = jax.jit(functools.partial(
                    gm.grouped_matmul_xla, mode=mode))(a, b, sizes)
                keep = slice(None) if mode == "tn" else slice(0, live)
                want = np.asarray(want, np.float32)[keep]
                scale = float(np.abs(want).max()) or 1.0
                found = []
                for tile in _gmm_tiles(mode, rows, k, n):
                    if mode == "tn":
                        fn = jax.jit(functools.partial(
                            gm._tgmm, tile=tile, interpret=interp))
                    else:
                        fn = jax.jit(functools.partial(
                            gm._gmm, tile=tile, transpose=mode == "nt",
                            interpret=interp))
                    row = {**head, "tile": list(tile),
                           "vmem_mib": round(gm.vmem_bytes(mode, *tile)
                                             / 2 ** 20, 2)}
                    try:
                        got = np.asarray(fn(a, b, sizes), np.float32)[keep]
                        row["err"] = float(np.abs(got - want).max() / scale)
                        row["wall_ms"] = round(_queued_ms(
                            fn, (a, b, sizes), runs, warmup), 4)
                    except Exception as e:  # the compiler's refusal
                        row["error"] = f"{type(e).__name__}: {str(e)[-300:]}"
                    found.append(row)
                timed = [x for x in found if "wall_ms" in x]
                if timed:
                    min(timed, key=lambda x: x["wall_ms"])["best"] = True
                xla = jax.jit(functools.partial(gm.grouped_matmul_xla,
                                                mode=mode))
                found.append({**head, "tile": "ragged_dot",
                              "wall_ms": round(_queued_ms(
                                  xla, (a, b, sizes), runs, warmup), 4)})
                rows_out += found
    return rows_out


def _queued_ms(fn, args, runs, warmup):
    """Mean ms a call over ``runs`` calls queued back to back."""
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(runs):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / runs * 1e3


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
    parser.add_argument("--embedding-grad-sweep", type=str, default="",
                        metavar="DIR",
                        help="time the gradient of Embedding's table in "
                             "every form of _EMBEDDING_GRAD_SWEEP, print "
                             "the table and keep it as "
                             "DIR/embedding_grad_sweep.json beside the "
                             "compiled text of every row")
    parser.add_argument("--dropout-sweep", type=str, default="",
                        metavar="DIR",
                        help="time Dense -> Dropout -> residual -> mean, "
                             "forward + backward, in every form of "
                             "_dropout_forms at the shapes of "
                             "_DROPOUT_SWEEP, print the table and keep it "
                             "as DIR/dropout_sweep.json beside the compiled "
                             "text of every row")
    parser.add_argument("--gelu-sweep", type=str, default="",
                        metavar="DIR",
                        help="time ffn2's product with exact GELU on its "
                             "operand and Dense -> GELU -> Dense, forward "
                             "+ backward, in every form of _gelu_forms at "
                             "the shapes of _GELU_SWEEP, print the table "
                             "and keep it as DIR/gelu_sweep.json beside "
                             "the compiled text of every row")
    parser.add_argument("--gmm-sweep", type=str, default="",
                        help="time the expert layer's grouped products "
                             "(kernels.grouped_matmul) at every tile that "
                             "fits, at the expert cells' first-rung shapes, "
                             "beside ragged_dot, and keep the rows as "
                             "DIR/gmm_sweep.json")
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

    if args.gmm_sweep:
        os.makedirs(args.gmm_sweep, exist_ok=True)
        rows = sweep_grouped_matmul(runs=max(args.runs, 20),
                                    warmup=args.warmup)
        with open(os.path.join(args.gmm_sweep, "gmm_sweep.json"), "w") as f:
            json.dump(rows, f, indent=1)
        print(f"{'Case':<16s} {'Mode':<4s} {'K x N':<11s} {'Tile':<16s} "
              f"{'VMEM':>6s} {'Wall ms':>9s} {'Err':>9s}")
        for r in rows:
            tile = r["tile"] if isinstance(r["tile"], str) \
                else "{} {} {}".format(*r["tile"])
            err = f"{r['err']:.3g}" if "err" in r else "-"
            print(f"{r['case']:<16s} {r['mode']:<4s} "
                  f"{'{} x {}'.format(r['k'], r['n']):<11s} {tile:<16s} "
                  f"{r.get('vmem_mib', '-'):>6} {r.get('wall_ms', '-'):>9} "
                  f"{err:>9}" + (" <- best" if r.get("best") else "")
                  + ("  " + r["error"][-120:] if "error" in r else ""))
        if rows and rows[0]["interpret"]:
            print("timed in the Pallas INTERPRETER (no TPU here): not a "
                  "hardware speed claim")
        return

    if args.embedding_grad_sweep:
        os.makedirs(args.embedding_grad_sweep, exist_ok=True)
        rows = sweep_embedding_grad(runs=args.runs, warmup=args.warmup,
                                    text_dir=args.embedding_grad_sweep)
        with open(os.path.join(args.embedding_grad_sweep,
                               "embedding_grad_sweep.json"), "w") as f:
            json.dump(rows, f, indent=1)
        print(f"{'Case':<24s} {'Rows':>6s} {'Table':<14s} {'Form':<17s} "
              f"{'Device ms':>10s} {'Wall ms':>9s} {'Emitter':<11s} "
              f"{'Error at id 0':>13s}")
        for r in rows:
            print(f"{r['case']:<24s} {r['rows']:>6d} "
                  f"{'{} x {}'.format(*r['table']):<14s} {r['form']:<17s} "
                  f"{r.get('device_ms', '-'):>10} {r.get('wall_ms', '-'):>9} "
                  f"{r.get('emitter') or '-':<11s} "
                  f"{r.get('id0_err', float('nan')):>13.4g}"
                  + (" <- the shape's" if r["chosen"] else "")
                  + ("  " + r["error"][-120:] if "error" in r else ""))
        if rows and not rows[0]["on_tpu"]:
            print("timed on the CPU (no TPU here): not a hardware speed "
                  "claim")
        return

    if args.dropout_sweep:
        os.makedirs(args.dropout_sweep, exist_ok=True)
        rows = sweep_dropout(runs=args.runs, warmup=args.warmup,
                             text_dir=args.dropout_sweep)
        with open(os.path.join(args.dropout_sweep,
                               "dropout_sweep.json"), "w") as f:
            json.dump(rows, f, indent=1)
        print(f"{'Case':<14s} {'Product':<20s} {'p':>4s} {'Form':<14s} "
              f"{'Device ms':>10s} {'Wall ms':>9s} {'Generator':>9s} "
              f"{'Hashes':>7s} {'In product':>10s}")
        for r in rows:
            product = f"{r['rows']} x {r['in_units']} x {r['units']}"
            print(f"{r['case']:<14s} {product:<20s} {r['p']:>4} "
                  f"{r['form']:<14s} {r.get('device_ms', '-'):>10} "
                  f"{r.get('wall_ms', '-'):>9} "
                  f"{r.get('generator_ops', '-'):>9} "
                  f"{r.get('hashes', '-'):>7} "
                  f"{str(r.get('hash_in_product', '-')):>10}"
                  + (" <- the op's" if r["chosen"] else "")
                  + ("  " + r["error"][-120:] if "error" in r else ""))
        if rows and not rows[0]["on_tpu"]:
            print("timed on the CPU (no TPU here): not a hardware speed "
                  "claim")
        return

    if args.gelu_sweep:
        os.makedirs(args.gelu_sweep, exist_ok=True)
        rows = sweep_gelu(runs=args.runs, warmup=args.warmup,
                          text_dir=args.gelu_sweep)
        with open(os.path.join(args.gelu_sweep, "gelu_sweep.json"),
                  "w") as f:
            json.dump(rows, f, indent=1)
        print(f"{'Case':<10s} {'What':<8s} {'Form':<20s} {'Device ms':>10s} "
              f"{'Wall ms':>9s} {'Exponentials':>12s}  Largest ops")
        for r in rows:
            largest = "  ".join(f"{n} {t}" for n, t in
                                r.get("largest_ops", []))
            print(f"{r['case']:<10s} {r['what']:<8s} {r['form']:<20s} "
                  f"{r.get('device_ms', '-'):>10} {r.get('wall_ms', '-'):>9} "
                  f"{r.get('exponentials', '-'):>12}  {largest}"
                  + (" <- the op's" if r["chosen"] else "")
                  + ("  " + r["error"][-120:] if "error" in r else ""))
        if rows and not rows[0]["on_tpu"]:
            print("timed on the CPU (no TPU here): not a hardware speed "
                  "claim")
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
