#!/usr/bin/env python3
"""Find the knee of a ``serve_open`` cell once, on the chip: the highest
offered rate the server sustains. One process, one server, one line per
rate; the cell's file then fixes its rate at four fifths of the knee (and
a later saturated cell at 1.25 x). Not run by the driver.

    python3 chipbench/sweep_knee.py --workload resnet50_serve_open \
        --seed 1 --seconds 6 --rates 200,400,800,1200,1600
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from chipbench.harness import bench as hbench  # noqa: E402
from chipbench.harness import device  # noqa: E402


def main(argv=None, bench_dir=BENCH_DIR):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    args = ap.parse_args(argv)
    bench = hbench.Bench(bench_dir, args.workload, args.seed, args.seconds,
                         0, T0)
    device.require_tpu(bench.chips)
    mode = bench.mode
    net, server, pool, _warm = mode.setup(bench)
    out_shape = (bench.cfg["classes"],)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            traffic = dict(bench.traffic, rate_per_s=rate)
            res = mode.drive(server, pool, traffic, args.seed, args.seconds,
                             bench.span, out_shape)
            note, _lat, _late = mode.summary(res, args.seconds)
            del note["outcomes"]
            print(json.dumps(dict(note, rate_per_s=rate)), flush=True)
            time.sleep(1.0)   # let a backlog empty before the next rate
    finally:
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
