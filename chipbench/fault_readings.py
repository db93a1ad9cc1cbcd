#!/usr/bin/env python3
"""What the comparison that decides ``correct`` reads when one thing is
computed wrong: the readings a configuration's ``check.tolerance`` is set
from. Not run by the driver; one process, one chip.

    python3 chipbench/fault_readings.py phi4_mini_flash_train_s4096 \\
        --seeds 11 2147483659 --faults state_bf16 dt_bf16

For a configuration whose ``reference`` takes ``fault`` (``model.FAULTS``
names them). Per seed: the network ``build`` makes, with the weights
``seed_params`` gives the comparison, answers ``check_inputs`` once; then
``harness/check.against_reference``, the very call a run's ``correct``
comes from, holds that answer to the reference as it is (``sound``) and
to the reference with each fault. A fault the tolerance catches reads
``ok: false``. One JSON line a seed:
``{"seed", "sound": {"ok", "max_err_over_scale", "s"}, "<fault>": ...}``.
The system's answer is the same in every comparison of a seed, so a fault
that moves the reference by less than the system's own rounding cannot
show, whatever the tolerance: ``moves_reference`` beside each fault is the
faulty reference against the sound one, on the same scale.
"""
import argparse
import functools
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from chipbench.harness import check  # noqa: E402
from chipbench.harness.bench import Bench  # noqa: E402


_JITTED = {}   # fault -> the jitted reference with it, for moves_reference


def readings(bench, net, faults):
    """``{"sound" | fault: {"ok", "max_err_over_scale", ...}}`` for the
    seed ``bench`` carries."""
    import jax
    import numpy as np

    import mxnet_tpu as mx

    model, cfg = bench.model, bench.cfg
    model.seed_params(net, cfg, bench.seed)
    x = model.check_inputs(cfg, bench.seed, int(cfg["check"]["samples"]))
    got = net(mx.nd.array(x, ctx=mx.tpu(), dtype=str(x.dtype))).asnumpy()
    sound = model.reference
    params = model.export_params(net, cfg)

    def logits(fault):
        if fault not in _JITTED:
            _JITTED[fault] = jax.jit(lambda p, xx: sound(
                cfg, p, (xx, None), fault=fault)["logits"])
        return np.asarray(_JITTED[fault](params, x))

    clean = logits(None)
    out = {}
    for fault in (None,) + tuple(faults):
        t0 = time.time()
        model.reference = functools.partial(sound, fault=fault)
        try:
            ok, note = check.against_reference(bench, net, x, got)
        finally:
            model.reference = sound
        row = {"ok": ok, "max_err_over_scale": note["max_err_over_scale"]}
        if fault is None:
            row.update(tolerance=note["tolerance"],
                       output_scale=note["output_scale"])
        else:
            row["moves_reference"] = float(
                np.abs(logits(fault) - clean).max() / np.abs(clean).max())
        row["s"] = round(time.time() - t0, 1)
        out[fault or "sound"] = row
    return out


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--seeds", type=int, nargs="+", default=[2147483659])
    ap.add_argument("--faults", nargs="*", default=None,
                    help="default: every fault model.FAULTS names")
    ap.add_argument("--bench-dir", default=BENCH_DIR)
    args = ap.parse_args(argv)

    import mxnet_tpu as mx

    bench = Bench(args.bench_dir, args.workload, args.seeds[0], 0, 0,
                  time.perf_counter())
    faults = bench.model.FAULTS if args.faults is None else args.faults
    net = bench.model.build(bench.cfg, mx.tpu(), bench.seed)
    net.hybridize()
    for seed in args.seeds:
        bench.seed = int(seed)
        print(json.dumps({"seed": bench.seed,
                          **readings(bench, net, faults)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
