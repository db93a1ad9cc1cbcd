"""XLA-cost-derived telemetry: per-executable flops/bytes records, the
per-device-kind peak-TFLOPS table, and measured-MFU arithmetic.

Every ``mfu`` this framework ever printed before this module was
*nominal*: public per-model FLOP counts divided by a part-spec peak —
trustworthy for neither numerator nor denominator. This module grounds
both sides:

* the **numerator** comes from XLA itself — the unified compile service
  (:mod:`mxnet_tpu.compile`) captures ``cost_analysis()`` (flops, bytes
  accessed) and, where a compiled executable is in hand,
  ``memory_analysis()`` (argument/output/temp/generated bytes) for every
  executable it builds, keyed by the service token, bounded per site;
* the **denominator** is the per-device-kind peak table below
  (``jax.devices()[0].device_kind`` selected, ``BENCH_PEAK_TFLOPS``
  override preserved); a device kind the table does not know is an
  error, never a default.

``ShardedTrainer`` reports ``mfu_xla`` =
``measured flops/step x steps/s ÷ (peak x devices)`` alongside the
legacy nominal ``mfu``: a ratio whose numerator is what XLA actually
scheduled.
"""
from __future__ import annotations

import os
import threading
import time
from collections import deque

from . import _state

__all__ = ["PEAK_TFLOPS_TABLE", "nominal_peak_tflops", "peak_tflops",
           "record_executable", "flops_for", "latest", "records",
           "aggregate", "mfu_xla", "reset"]

# nominal bf16 peak per chip generation (public specs). Order matters:
# 'v5 lite'/'v5e' must match before the bare 'v5'.
PEAK_TFLOPS_TABLE = (
    ("v6e", 918.0), ("v6", 918.0),
    ("v5 lite", 197.0), ("v5e", 197.0),
    ("v5p", 459.0), ("v5", 459.0),
    ("v4", 275.0), ("v3", 123.0),
)

_MAX_TOKENS = 512    # distinct executables tracked (FIFO eviction)
_PER_SITE = 64       # recent records kept per site

_lock = threading.Lock()
_by_token: dict = {}            # token_key -> record (insertion-ordered)
_by_site: dict = {}             # site -> deque of records
_agg: dict = {}                 # site -> aggregate sums


def nominal_peak_tflops(device_kind=None) -> float:
    """Table lookup by device kind (default: the attached backend's
    ``jax.devices()[0].device_kind``). A kind the table does not know
    raises ``LookupError``: a utilization against a guessed peak is worse
    than none."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    kind = str(device_kind).lower()
    for key, peak in PEAK_TFLOPS_TABLE:
        if key in kind:
            return peak
    raise LookupError(
        f"no peak TFLOP/s entry for device kind {device_kind!r}; known "
        f"keys: {[k for k, _ in PEAK_TFLOPS_TABLE]}")


def peak_tflops(device_kind=None, env="BENCH_PEAK_TFLOPS") -> float:
    """The effective per-device peak: the ``env`` override when set to a
    positive number ("0"/unset mean auto-detect), else the table."""
    try:
        override = float(os.environ.get(env, 0) or 0)
    except ValueError:
        override = 0.0
    return override if override > 0 else nominal_peak_tflops(device_kind)


# ---------------------------------------------------- executable records ---

def _norm_cost(cost):
    """Normalize ``cost_analysis()`` output (dict, or list of dicts on
    some jax versions) to {flops, bytes_accessed, transcendentals}."""
    if cost is None:
        return {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    try:
        return {"flops": float(cost.get("flops", 0.0)),
                "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
                "transcendentals": float(cost.get("transcendentals", 0.0))}
    except (AttributeError, TypeError, ValueError):
        return {}


_MEM_FIELDS = (("argument_bytes", "argument_size_in_bytes"),
               ("output_bytes", "output_size_in_bytes"),
               ("temp_bytes", "temp_size_in_bytes"),
               ("generated_code_bytes", "generated_code_size_in_bytes"),
               ("alias_bytes", "alias_size_in_bytes"))


def _norm_mem(mem):
    """Normalize ``memory_analysis()`` (a CompiledMemoryStats) to plain
    byte fields."""
    if mem is None:
        return {}
    out = {}
    for name, attr in _MEM_FIELDS:
        try:
            out[name] = int(getattr(mem, attr, mem.get(attr, 0))
                            if isinstance(mem, dict)
                            else getattr(mem, attr, 0))
        except (AttributeError, TypeError, ValueError):
            out[name] = 0
    return out


def record_executable(site, token, cost=None, mem=None, source="compile"):
    """Store one executable's analyses (called by the compile service at
    compile / disk-load / warmup time). Bounded: at most ``_MAX_TOKENS``
    distinct tokens and ``_PER_SITE`` recent records per site."""
    if not _state.enabled:
        return None
    rec = {"site": site, "token": token, "source": source,
           "t": time.time()}
    rec.update(_norm_cost(cost))
    rec.update(_norm_mem(mem))
    with _lock:
        if token not in _by_token and len(_by_token) >= _MAX_TOKENS:
            _by_token.pop(next(iter(_by_token)))
        prev = _by_token.get(token)
        if prev is not None:
            # an AOT/disk pass may know memory where a trace-only pass
            # did not (and vice versa) — merge instead of dropping fields
            merged = dict(prev)
            merged.update({k: v for k, v in rec.items() if v or k in
                           ("site", "token", "source", "t")})
            rec = merged
        _by_token[token] = rec
        dq = _by_site.get(site)
        if dq is None:
            dq = _by_site[site] = deque(maxlen=_PER_SITE)
        dq.append(rec)
        agg = _agg.setdefault(site, {"executables": 0, "flops": 0.0,
                                     "bytes_accessed": 0.0,
                                     "temp_bytes": 0, "output_bytes": 0,
                                     "argument_bytes": 0,
                                     "generated_code_bytes": 0})
        if prev is None:
            agg["executables"] += 1
            for k in ("flops", "bytes_accessed", "temp_bytes",
                      "output_bytes", "argument_bytes",
                      "generated_code_bytes"):
                agg[k] += rec.get(k, 0) or 0
    return rec


def flops_for(token):
    """Measured flops per invocation of the executable registered under
    `token`, or None."""
    rec = _by_token.get(token)
    if rec is None:
        return None
    f = rec.get("flops")
    return f if f else None


def latest(site):
    """The most recently recorded executable for `site`, or None."""
    dq = _by_site.get(site)
    return dict(dq[-1]) if dq else None


def records(site=None):
    """Recent executable records (per site, or all sites merged)."""
    if site is not None:
        return [dict(r) for r in _by_site.get(site, ())]
    with _lock:
        return [dict(r) for r in _by_token.values()]


def aggregate():
    """Per-site sums over distinct executables (diagnose / the
    ``mxtpu_executables_tracked`` series)."""
    with _lock:
        return {s: dict(a) for s, a in sorted(_agg.items())}


def mfu_xla(flops_per_step, steps_per_sec, devices=1, peak=None,
            device_kind=None):
    """Measured-flops MFU: ``flops/step x steps/s ÷ (peak x devices)``.
    Returns None when the numerator is unknown; raises ``LookupError``
    when the peak of this device kind is (no ``peak`` passed)."""
    if not flops_per_step or not steps_per_sec:
        return None
    if peak is None:
        peak = peak_tflops(device_kind)
    denom = peak * 1e12 * max(1, int(devices or 1))
    if denom <= 0:
        return None
    return flops_per_step * steps_per_sec / denom


def reset():
    """Drop every record (tests)."""
    with _lock:
        _by_token.clear()
        _by_site.clear()
        _agg.clear()
