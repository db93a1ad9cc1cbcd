#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Reads ``workloads/<name>.json``, imports the configuration's ``model.py``
and the mode's driver by the names in it, refuses to run unless jax
reports a TPU with at least the cell's chips, and prints as its LAST line
one JSON object with exactly ``correct``, ``attempted``, ``failed``,
``metrics`` and ``device`` (plus ``breakdown`` with ``--trace 1``). With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics. Everything else a reader may want
(loss trajectory, request counts, versions, set-up split) is on earlier
lines, each one JSON object after a ``# `` prefix.
"""
import time

T0 = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))   # the checkout's root

from chipbench.harness import bench as hbench  # noqa: E402
from chipbench.harness import device, trace_reduce  # noqa: E402


def note(kind, payload):
    print(f"# {kind}: {json.dumps(payload)}", flush=True)


def layer_metrics(bench_dir, run):
    """Every reader under ``layer_metrics/`` that applies to this run and
    finds something to read: ``{name: {"value", "unit"}}``."""
    out = {}
    folder = os.path.join(bench_dir, "layer_metrics")
    for fname in sorted(os.listdir(folder)):
        if not fname.endswith(".py") or fname.startswith("_"):
            continue
        reader = hbench.load_module(os.path.join(folder, fname))
        if not reader.applies(run):
            continue
        value = reader.compute(run)
        if value is not None:
            out[fname[:-3]] = {"value": float(value), "unit": reader.UNIT}
    return out


def result_line(outcome, metrics, dev, breakdown=None):
    line = {"correct": bool(outcome.correct),
            "attempted": int(outcome.attempted),
            "failed": int(outcome.failed),
            "metrics": metrics, "device": dev}
    if breakdown is not None:
        line["breakdown"] = breakdown
    return json.dumps(line)


def main(argv=None, bench_dir=BENCH_DIR, t0=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    t0 = T0 if t0 is None else t0

    wl_file = os.path.join(bench_dir, "workloads", f"{args.workload}.json")
    if not os.path.isfile(wl_file):
        print(f"chipbench: no workload file {wl_file}", file=sys.stderr)
        return 2
    workload = hbench.load_json(wl_file)
    device.pin_host_cpus(workload.get("host_cpus"))
    device.require_tpu(int(workload["chips"]))
    # the program itself, before a line is printed: in a directory that
    # holds only the benchmark the run ends here with nothing on stdout
    import jax

    import mxnet_tpu.compile as mxcompile

    # every program, however small or quick to compile, goes to the
    # persistent cache (the program sets this itself only when it also
    # picks the directory); the directory is JAX_COMPILATION_CACHE_DIR
    # where set, else the program's fixed <checkout>/.mxtpu_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cache_root = mxcompile.cache_dir()

    bench = hbench.Bench(bench_dir, args.workload, args.seed, args.seconds,
                         args.trace, t0)
    dev = device.info()
    note("run", {"workload": args.workload, "seed": args.seed,
                 "seconds": args.seconds, "trace": args.trace,
                 "config": bench.workload["config"],
                 "mode": bench.workload["mode"], "device": dev,
                 "versions": device.versions(), "cache_root": cache_root,
                 "loadavg": os.getloadavg(), "cpus": os.cpu_count(),
                 "jax_cache_dir": jax.config.jax_compilation_cache_dir})

    outcome = bench.mode.run(bench)

    note("setup", {"setup_s": bench.setup_s, "events": bench.setup_compile})
    note("window", {"events": bench.window_compile,
                    "loadavg": os.getloadavg()})
    for kind, payload in outcome.notes.items():
        note(kind, payload)
    compiled = bench.window_compile["backend_compile"]["n"]
    if compiled:
        outcome.correct = False
        note("incorrect", {"compiles_in_window": compiled})

    dev["memory_peak_bytes"] = device.memory_peak_bytes() or 0
    note("memory", device.memory_stats())
    breakdown = None
    if args.trace:
        run = dict(outcome.run, bench=bench,
                   trace=bench.reduced or trace_reduce.EMPTY,
                   device=dev, end_to_end=outcome.end_to_end)
        metrics = layer_metrics(bench_dir, run)
        if bench.reduced is not None:
            lo, hi = trace_reduce.window(bench.reduced)
            dev["busy_s"] = trace_reduce.busy_seconds(bench.reduced)
            dev["window_s"] = (hi - lo) / 1e9
            breakdown = {
                "device_ops": trace_reduce.op_table(bench.reduced),
                "idle_gaps": trace_reduce.idle_gaps(bench.reduced)}
    else:
        unit = bench.workload["end_to_end"]
        metrics = {name: {"value": float(value), "unit": unit[name]}
                   for name, value in outcome.end_to_end.items()}
        metrics["setup_s"] = {"value": float(bench.setup_s), "unit": "s"}
    print(result_line(outcome, metrics, dev, breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
