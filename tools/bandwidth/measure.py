#!/usr/bin/env python
"""Measure collective (allreduce) bandwidth across the device mesh.

Parity: tools/bandwidth/measure.py in the reference, which times KVStore
push+pull of model-sized gradients across GPUs/machines. TPU-native
redesign: the gradient-sync primitive is an XLA ``psum`` over a
``jax.sharding.Mesh`` axis (riding ICI between chips, DCN between hosts),
so that is what gets timed — per payload size, reporting effective
algorithm bandwidth ``2*(n-1)/n * bytes / t`` (ring-allreduce convention,
comparable to the reference's numbers).

    python tools/bandwidth/measure.py --sizes 1,16,64 --iters 10
    (sizes in MiB; runs on however many devices are visible — use
    XLA_FLAGS=--xla_force_host_platform_device_count=8 for a CPU mesh)
"""
import argparse
import time


def measure(sizes_mib, iters=10, dtype="float32", warmup=2):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()
    n = len(devices)
    mesh = Mesh(np.array(devices), ("x",))
    results = []

    shard_map = jax.shard_map

    @jax.jit
    def _psum(arr):
        return shard_map(
            lambda a: jax.lax.psum(a, "x"), mesh=mesh,
            in_specs=P("x"), out_specs=P())(arr)

    for mib in sizes_mib:
        elems = int(mib * (1 << 20) // jnp.dtype(dtype).itemsize)
        elems = max(n, elems - elems % n)
        arr = jax.device_put(
            jnp.ones((elems,), dtype),
            NamedSharding(mesh, P("x")))
        for _ in range(warmup):
            _psum(arr).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(iters):
            out = _psum(arr)
        out.block_until_ready()
        dt = (time.perf_counter() - t0) / iters
        nbytes = elems * jnp.dtype(dtype).itemsize
        algo_bw = 2 * (n - 1) / n * nbytes / dt / 1e9 if n > 1 else \
            nbytes / dt / 1e9
        results.append({"size_mib": mib, "time_ms": dt * 1e3,
                        "algo_gbps": algo_bw, "devices": n})
    return results


def main(argv=None):
    p = argparse.ArgumentParser(description="allreduce bandwidth harness")
    p.add_argument("--sizes", type=str, default="1,4,16,64",
                   help="comma-separated payload sizes in MiB")
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--dtype", type=str, default="float32")
    args = p.parse_args(argv)
    sizes = [float(s) for s in args.sizes.split(",")]
    rows = measure(sizes, iters=args.iters, dtype=args.dtype)
    print(f"{'size(MiB)':>10} {'time(ms)':>10} {'algo BW(GB/s)':>14} devices")
    for r in rows:
        print(f"{r['size_mib']:>10.1f} {r['time_ms']:>10.3f} "
              f"{r['algo_gbps']:>14.2f} {r['devices']:>7}")
    return rows


if __name__ == "__main__":
    main()
