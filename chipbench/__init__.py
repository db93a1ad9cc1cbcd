"""chipbench: the benchmark of mxnet_tpu on the chip (BENCHMARK.json).

One cell per process: ``python3 chipbench/run.py --workload <name> --seed
<n> --seconds <s> --trace <0|1>``. Everything that belongs to one
configuration, one traffic mix, one mode or one per-layer metric sits in
a file of its own, found by the name in the workload file or by listing
its directory; ``harness/`` is the shared yardstick (timing, arrivals,
percentiles, peaks, the reduction from profiler trace to numbers).
"""
