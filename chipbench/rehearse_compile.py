#!/usr/bin/env python3
"""Compile a training cell's step at its real size for a DESCRIBED v5e:2x2,
here, without the chip (on-chip-measurement guide, section 2, the third
rehearsal). What the chip's compiler would refuse (memory, a Mosaic
objection, a kernel that cannot be partitioned) it refuses here, at no
chip time. Not run by the driver; nothing it prints is a measurement.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse_compile.py resnet50_train_dp4

Prints per cell: seconds to compile, ``memory_analysis()`` bytes per
device, and how many Mosaic calls and collectives the compiled step holds.

Two things have to be steered from here, because the program decides them
from ``jax.devices()``, which is the CPU in this sandbox: nothing can be
put on a described device, so ``ShardedTrainer._place_params`` is skipped,
and ``kernels.on_tpu()`` is made to answer as it will on the chip.
"""
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from chipbench.harness import bench as hbench  # noqa: E402

MEM_FIELDS = ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes")


def rehearse(workload, topo, bench_dir=BENCH_DIR):
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import kernels
    from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer

    kernels.on_tpu = lambda: True
    ShardedTrainer._place_params = lambda self: None
    wl = hbench.load_json(
        os.path.join(bench_dir, "workloads", f"{workload}.json"))
    folder = os.path.join(bench_dir, "configs", wl["config"])
    cfg = hbench.load_json(os.path.join(folder, "config.json"))
    model = hbench.load_module(os.path.join(folder, "model.py"))
    traffic, job = wl["traffic"], cfg["job"]
    mesh = DeviceMesh(dict(traffic["mesh"]),
                      devices=topo.devices[:wl["chips"]])
    kernels.reset_stats()
    net = model.build(cfg, mx.tpu(), 0)
    trainer = ShardedTrainer(
        net, model.loss(cfg), job["optimizer"],
        dict(job["optimizer_params"]), mesh=mesh,
        **traffic.get("trainer_options", {}))
    x, y = jax.eval_shape(lambda k: model.make_batch(cfg, traffic, k),
                          jax.random.PRNGKey(0))
    t0 = time.time()
    compiled = trainer.aot_lower(x, y).compile()
    mem = compiled.memory_analysis()
    mem = {k: int(getattr(mem, k)) for k in MEM_FIELDS}
    text = compiled.as_text()
    return {
        "workload": workload, "compile_s": round(time.time() - t0, 1),
        "memory": mem,
        "per_device_gib": round(
            (mem["argument_size_in_bytes"] + mem["output_size_in_bytes"]
             - mem["alias_size_in_bytes"] + mem["temp_size_in_bytes"])
            / 2 ** 30, 3),
        "tpu_custom_call": text.count("tpu_custom_call"),
        "collectives": {op: text.count(f" {op}(") for op in (
            "all-reduce", "all-reduce-start", "reduce-scatter",
            "all-gather", "all-to-all", "collective-permute")},
        "dispatch": {f: {"kernel": r["kernel"], "xla": r["xla"]}
                     for f, r in kernels.dispatch_stats().items()}}


def main(argv):
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for workload in argv:
        print(json.dumps(rehearse(workload, topo)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
