"""The device as jax reports it, and the refusal to run without it."""
import os
import sys


def pin_host_cpus(k):
    """Confine this process, and every thread it starts from now on, to
    the first ``k`` CPUs it may run on (no-op for a falsy ``k``). Called
    before jax is imported, as ``taskset`` would be. A one-chip machine
    has 13 CPUs, and where the threads of an unpinned run came to sit
    put about one process in four into a mode 5 ms a step slower (host
    dispatch, not the device); 10 runs of 10 under ``taskset`` to two or
    four CPUs showed the fast mode only (PERF.md, PR 22)."""
    if k:
        os.sched_setaffinity(0, sorted(os.sched_getaffinity(0))[:int(k)])


def require_tpu(chips):
    """The attached devices, or exit with code 3 and one line on stderr:
    a measurement path that finds no chip fails, it never falls back."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chipbench: no TPU (jax reports platform "
              f"{devs[0].platform!r}); nothing run", file=sys.stderr)
        raise SystemExit(3)
    if len(devs) < chips:
        print(f"chipbench: the cell needs {chips} chip(s), jax reports "
              f"{len(devs)}; nothing run", file=sys.stderr)
        raise SystemExit(3)
    return devs


def info():
    """``platform``, ``kind`` and ``count`` for the last line."""
    import jax

    devs = jax.devices()
    return {"platform": str(devs[0].platform),
            "kind": str(devs[0].device_kind), "count": len(devs)}


def memory_peak_bytes():
    """Peak bytes held on the fullest chip, or None where the backend
    keeps no allocator statistics (the CPU). The TPU runtime counts live
    arrays under ``peak_bytes_in_use`` and the scratch memory of loaded
    programs (their temporaries) apart, under ``peak_bytes_reserved``: a
    chip probe (PR 22) ran a program with 2.7 GB of temporaries and read
    0.54 GB in use, 2.15 GB reserved. The reservation stands while the
    program is loaded, so the footprint is the sum."""
    import jax

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"])
                         + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks) if peaks else None


def memory_stats():
    """The raw allocator statistics of every device, for an earlier
    line."""
    import jax

    keys = ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
            "peak_bytes_reserved", "bytes_limit")
    return [{k: (d.memory_stats() or {}).get(k) for k in keys}
            for d in jax.devices()]


def versions():
    import jax
    import jaxlib

    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu_version, "python": sys.version.split()[0]}
