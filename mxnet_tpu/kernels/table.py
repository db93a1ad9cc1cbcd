"""Persisted kernel-dispatch table.

The autotuner (``benchmark/opperf.py --kernels``) times each registered
kernel against its XLA baseline per (backend, family, shape bucket) and
records the winner here; :func:`mxnet_tpu.kernels.dispatch` consults the
table at trace time. Persistence follows the compile-cache discipline
exactly (``mxnet_tpu/compile.py`` disk layer): entries live under
``<cache root>/kernels/dispatch_<fingerprint>.json`` where the
fingerprint folds in jax/jaxlib versions, backend platform, device kind
and count — a backend change makes old measurements invisible instead of
silently mis-routing. Writes are tmp + fsync + rename (concurrent-writer
safe); the payload carries its own CRC32, and a corrupt or mismatched
file loads as EMPTY (dispatch then falls back to the untuned default,
counted by ``mxtpu_kernels_table_corrupt_total``) — a torn write can
never wedge dispatch.

Table format (version 1)::

    {"version": 1, "fingerprint": "<12 hex>", "backend": "cpu|tpu|...",
     "created": <epoch>, "opperf": {...last autotune run stamp...},
     "crc32": <crc of the canonical entries json>,
     "entries": {"<family>|<bucket>": {"winner": "kernel"|"xla",
                                       "kernel_ms": ..., "xla_ms": ...,
                                       "speedup": ..., "interpret": bool}}}

Bucket keys are produced by each registry entry's bucketing function —
a pure function of the aval shapes, so the same workload always lands on
the same row (distcheck pass 4 sweeps the dispatch keys for churn).
"""
from __future__ import annotations

import json
import os
import threading
import time
import zlib

__all__ = ["table_path", "load", "save", "lookup", "record", "entries",
           "census", "invalidate", "set_opperf_stamp", "opperf_stamp"]

_lock = threading.RLock()
_loaded = None        # in-memory table dict, or None before first load
_loaded_path = None   # path it came from (staleness check for diagnose)
_corrupt_seen = None  # last corruption reason (diagnose)


def _canon_entries(entries):
    return json.dumps(entries, sort_keys=True, separators=(",", ":"))


def _crc(entries):
    return zlib.crc32(_canon_entries(entries).encode()) & 0xFFFFFFFF


def table_path():
    """The active on-disk table path, or None when no cache dir is
    configured (memory-only dispatch table)."""
    from .. import compile as _compile

    root = _compile.cache_dir()
    if root is None:
        return None
    return os.path.join(root, "kernels",
                        f"dispatch_{_compile.fingerprint()}.json")


def _fresh():
    import jax

    from .. import compile as _compile

    return {"version": 1, "fingerprint": _compile.fingerprint(),
            "backend": jax.devices()[0].platform, "created": time.time(), "opperf": None,
            "entries": {}}


def _note_corrupt(reason):
    global _corrupt_seen
    _corrupt_seen = reason
    try:
        from ..telemetry import registry as _registry

        _registry.counter(
            "mxtpu_kernels_table_corrupt_total",
            "Kernel dispatch-table files that failed CRC/format "
            "verification and were ignored (dispatch fell back to the "
            "untuned defaults)").inc()
    except Exception:
        pass
    try:
        from .. import log as _log

        _log.get_logger("mxnet_tpu.kernels").warning(
            "kernel dispatch table unreadable (%s); dispatch uses the "
            "untuned per-family defaults until opperf --kernels rewrites "
            "it", reason)
    except Exception:
        pass


def load(reload=False):
    """The live table dict (loaded once per process; ``reload=True``
    re-reads disk — tests and the autotuner use it). Corrupt/stale files
    load as a fresh empty table, never raise."""
    global _loaded, _loaded_path
    with _lock:
        path = table_path()
        if _loaded is not None and not reload and path == _loaded_path:
            return _loaded
        table = _fresh()
        if path is not None and os.path.exists(path):
            try:
                with open(path, "r", encoding="utf-8") as f:
                    raw = json.load(f)
                if raw.get("version") != 1:
                    _note_corrupt(f"unsupported version {raw.get('version')!r}")
                elif raw.get("fingerprint") != table["fingerprint"]:
                    # stale: measured on a different backend/jax — ignore
                    _note_corrupt(
                        f"fingerprint {raw.get('fingerprint')!r} != current "
                        f"{table['fingerprint']!r} (backend/jax changed)")
                elif _crc(raw.get("entries", {})) != raw.get("crc32"):
                    _note_corrupt("entries CRC mismatch (torn write?)")
                else:
                    table = raw
            except (OSError, ValueError) as e:
                _note_corrupt(f"{type(e).__name__}: {e}")
        _loaded = table
        _loaded_path = path
        return table


def save(table=None):
    """Atomically persist the table (tmp + fsync + rename, CRC stamped).
    Returns the path written, or None when no cache dir is configured."""
    with _lock:
        table = table if table is not None else load()
        path = table_path()
        if path is None:
            return None
        table["crc32"] = _crc(table.get("entries", {}))
        os.makedirs(os.path.dirname(path), exist_ok=True)
        data = json.dumps(table, indent=1, sort_keys=True).encode()
        tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
        try:
            with open(tmp, "wb") as f:
                f.write(data)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):
                try:
                    os.remove(tmp)
                except OSError:
                    pass
        return path


def _key(family, bucket):
    return f"{family}|{bucket}"


def lookup(family, bucket):
    """The tuned row for (family, bucket) — ``{"winner": ...}`` — or
    None when untuned."""
    return load().get("entries", {}).get(_key(family, bucket))


def record(family, bucket, winner, kernel_ms=None, xla_ms=None,
           interpret=False):
    """Record one autotune measurement (in memory; call :func:`save` to
    persist)."""
    with _lock:
        table = load()
        row = {"winner": winner, "interpret": bool(interpret)}
        if kernel_ms is not None:
            row["kernel_ms"] = round(float(kernel_ms), 5)
        if xla_ms is not None:
            row["xla_ms"] = round(float(xla_ms), 5)
        if kernel_ms and xla_ms:
            row["speedup"] = round(xla_ms / kernel_ms, 3)
        table.setdefault("entries", {})[_key(family, bucket)] = row
        return row


def set_opperf_stamp(stamp):
    """Stamp the last ``opperf --kernels`` run (argv, duration, counts)
    into the table — surfaced by tools/diagnose.py."""
    with _lock:
        load()["opperf"] = stamp


def opperf_stamp():
    return load().get("opperf")


def entries():
    return dict(load().get("entries", {}))


def invalidate():
    """Drop the in-memory table so the next lookup re-reads disk (tests,
    and ``compile.configure`` callers that move the cache dir)."""
    global _loaded, _loaded_path
    with _lock:
        _loaded = None
        _loaded_path = None


def census():
    """Table census for tools/diagnose.py: location, entry/winner counts,
    staleness, last corruption reason, last opperf run."""
    with _lock:
        table = load()
        ents = table.get("entries", {})
        winners = {"kernel": 0, "xla": 0}
        per_family = {}
        for key, row in ents.items():
            fam = key.split("|", 1)[0]
            w = row.get("winner", "xla")
            winners[w] = winners.get(w, 0) + 1
            f = per_family.setdefault(fam, {"kernel": 0, "xla": 0})
            f[w] = f.get(w, 0) + 1
        path = table_path()
        return {
            "path": path,
            "exists": bool(path and os.path.exists(path)),
            "fingerprint": table.get("fingerprint"),
            "backend": table.get("backend"),
            "created": table.get("created"),
            "entries": len(ents),
            "winners": winners,
            "per_family": per_family,
            "corrupt_seen": _corrupt_seen,
            "opperf": table.get("opperf"),
        }
