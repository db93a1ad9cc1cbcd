"""Unified compile service: ONE trace→lower→compile seam for the framework.

Before this module, four paths compiled XLA executables independently —
per-op dispatch caches (``ops/registry.py``), fused bulk segments
(``bulk.py``), ``CachedOp`` signature caches, and the Module/symbol
``Executor`` — each with its own keying scheme and zero cross-run
persistence: every process cold-started by recompiling the world. All of
them (plus ``ShardedTrainer``) now call :func:`jit` here instead of
``jax.jit`` directly (the ``tools/mxlint.py`` ``raw-jit`` rule gates new
call sites), which buys one seam for:

* **One canonical cache key** — ``(function token, input avals incl.
  shardings + weak types + pytree structure, donation/jit options, backend
  fingerprint)``. The *token* is the site's stable identity (op name +
  frozen kwargs, bulk plan, CachedOp signature, symbol graph) so the key
  survives process restarts; the *fingerprint* folds in jax/jaxlib
  versions, backend platform, device kind and device count so an upgrade
  or a topology change invalidates instead of mis-hitting.
* **A two-level cache** — the in-memory executable map (per wrapped
  function, keyed on the call signature) over a **persistent on-disk
  cache** of serialized compiled executables under the cache root
  (CRC-manifested per entry like ``checkpoint.py``, written tmp+rename so
  concurrent writers are safe, corrupt entries fall back to recompile).
  On an accelerator backend jax's own compilation cache backs what this
  layer does not serialize (donating executables: the trainer step and
  the serving ladder) — in ``JAX_COMPILATION_CACHE_DIR`` where that is
  set (jax reads it itself; nothing here overrides it), else in
  ``<root>/xla``.
* **AOT warmup** — every compile records its signature into an in-memory
  (and, with a cache dir, on-disk) *warmup manifest*; :func:`warmup`
  replays a manifest so serving/training pods compile before first
  traffic. ``ShardedTrainer`` and ``CachedOp`` record automatically by
  virtue of compiling through the service.
* **Per-site metrics** — hit/miss/disk-hit/compile-ms per site
  (``dispatch``/``bulk``/``cachedop``/``executor``/``trainer``/
  ``predictor`` [the MXPred C-ABI path]/``serving`` [the predict-server
  bucket executables]), flowing
  into the profiler's ``compile_cache.*`` counter tracks, the
  ``analysis.distcheck`` recompile-churn detector (site family
  ``service``), and the ``tools/diagnose.py`` "Compile Cache" report.

Knobs
-----
``MXNET_TPU_CACHE_DIR``          on-disk cache root; unset, the root is
                                 ``JAX_COMPILATION_CACHE_DIR``, else on a
                                 TPU backend the fixed in-checkout
                                 ``.mxtpu_cache``, else (CPU) memory only
``MXNET_TPU_COMPILE_SERVICE=0``  bypass the service (raw ``jax.jit``)
``MXNET_TPU_CACHE_SALT``         extra fingerprint salt (tests use it to
                                 simulate a jax-version/backend change)

Fault-injection points (``mxnet_tpu.faults``): ``compile.load`` fires on
every disk-cache read with the raw entry bytes as payload (``corrupt``
mode exercises the CRC fallback), ``compile.write`` on every disk write.

Dispatch-cost contract: with no cache dir the per-call overhead on a hit
is one signature build + one dict lookup; the eager per-op path
(``opperf --dispatch``) is asserted within noise of the raw-jit baseline
by the perf gate in ``tests/test_compile.py``.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import re
import threading
import time
import weakref
import zlib

from . import faults as _faults
from . import profiler as _profiler
from .analysis import distcheck as _distcheck
from .telemetry import _state as _tele_state
from .telemetry import costs as _tele_costs
from .telemetry import flight as _flight
from .telemetry import trace as _trace

__all__ = ["jit", "stats", "totals", "reset_stats", "set_enabled",
           "enabled", "configure", "cache_dir", "fingerprint", "warmup",
           "manifest", "save_manifest", "clear_manifest", "last_warmup",
           "disk_report", "gc_cache", "clear_memory", "registered",
           "program_texts"]

ENV_DIR = "MXNET_TPU_CACHE_DIR"
ENV_JAX_DIR = "JAX_COMPILATION_CACHE_DIR"
#: the cache root on an accelerator backend when neither variable names
#: one: a fixed path inside the checkout, resolved from this file's own
#: location (the path is part of jax's cache key — a directory that
#: moves never hits)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".mxtpu_cache")
ENV_ENABLE = "MXNET_TPU_COMPILE_SERVICE"
ENV_SALT = "MXNET_TPU_CACHE_SALT"

MANIFEST_FILE = "warmup_manifest.json"
LAST_WARMUP_FILE = "last_warmup.json"
_MANIFEST_CAP = 1024

_lock = threading.RLock()
_ENABLED = os.environ.get(ENV_ENABLE, "1").lower() not in ("0", "false",
                                                           "off")
_CONFIGURED = False
_DIR = None          # cache root (absolute) or None
_FP = None           # backend fingerprint (12 hex chars), computed lazily
# site -> [hits, misses, disk_hits, compiles, compile_ms, load_ms, corrupt]
_SITES = {}
_REGISTRY = {}       # token key -> weakref(ServiceFunction)
_MANIFEST = []       # in-memory JSON-able warmup entries
_MANIFEST_SEEN = set()
_PENDING_WARMUP = {}  # token key -> [manifest entries awaiting registration]
_LAST_WARMUP = None

# lazily bound jax symbols (this module sits on the dispatch import chain
# and must not pull jax in at import time)
_jax = None
_Tracer = None
_np = None
_dtype_str = None


class _Bypass(Exception):
    """Signature not service-cacheable (tracer input); use raw jit."""


def _ensure_jax():
    global _jax, _Tracer, _np, _dtype_str
    if _jax is None:
        import jax
        import numpy
        from jax.core import Tracer

        from .ops.registry import dtype_str

        _jax, _Tracer, _np, _dtype_str = jax, Tracer, numpy, dtype_str
    return _jax


# ------------------------------------------------------------- lifecycle ---

def enabled() -> bool:
    return _ENABLED


def set_enabled(on) -> bool:
    """Runtime service toggle (the perf A/B seam); returns the previous
    state. Disabled calls fall straight through to the wrapped
    ``jax.jit`` — no signature build, no accounting."""
    global _ENABLED
    prev = _ENABLED
    _ENABLED = bool(on)
    return prev


def _resolve_dir():
    """The cache root when nothing was passed to :func:`configure`:
    ``MXNET_TPU_CACHE_DIR``, else ``JAX_COMPILATION_CACHE_DIR`` (this
    layer's ``exec/``, ``kernels/`` and manifest files then sit next to
    jax's own entries), else on an accelerator backend the one fixed
    :data:`DEFAULT_DIR` inside the checkout, else (CPU) None."""
    explicit = os.environ.get(ENV_DIR) or os.environ.get(ENV_JAX_DIR)
    if explicit:
        return explicit
    return DEFAULT_DIR if _default_device().platform != "cpu" else None


def configure(cache_dir="__env__"):
    """(Re)configure the disk layer. Default: :func:`_resolve_dir`.
    Explicit ``cache_dir=None`` forces memory-only mode. Re-running after
    an env change is supported (tests); in-memory executables persist —
    call :func:`clear_memory` to force the disk path."""
    global _DIR, _FP, _CONFIGURED
    with _lock:
        if cache_dir == "__env__":
            cache_dir = _resolve_dir()
        _DIR = os.path.abspath(cache_dir) if cache_dir else None
        _FP = None  # salt / backend may have changed
        _CONFIGURED = True
        if _DIR:
            os.makedirs(os.path.join(_DIR, "exec"), exist_ok=True)
            _enable_native_cache(_DIR)


def _ensure_configured():
    if not _CONFIGURED:
        configure()


def _enable_native_cache(root):
    """Point jax's own compilation cache at ``<root>/xla`` on accelerator
    backends, where donating executables (the trainer step, the serving
    ladder) are never serialized by this layer and warm-start through
    jax's cache instead. Where ``JAX_COMPILATION_CACHE_DIR`` is set jax
    reads it itself and this function does nothing. On the CPU backend
    jax's cache stays off: executables loaded from it corrupt the heap
    when they donate (see the platform policy in :func:`jit`), and this
    layer's ``exec/`` entries cover the CPU warm start."""
    if os.environ.get(ENV_JAX_DIR) or _default_device().platform == "cpu":
        return
    from jax.experimental.compilation_cache import compilation_cache as _cc

    _cc.set_cache_dir(os.path.join(root, "xla"))
    jax = _ensure_jax()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax latches cache availability at the first compile, which has
    # very likely already happened (device_put on import paths)
    _cc.reset_cache()


def cache_dir():
    """The active on-disk cache root, or None (memory-only)."""
    _ensure_configured()
    return _DIR


def fingerprint() -> str:
    """Backend fingerprint folded into every on-disk key: jax + jaxlib
    versions, platform, device kind and count, plus ``MXNET_TPU_CACHE_SALT``.
    A change in any component makes old entries invisible (and
    :func:`gc_cache`-collectable) instead of silently mis-hitting."""
    global _FP
    if _FP is None:
        jax = _ensure_jax()
        try:
            import jaxlib

            jl = getattr(jaxlib, "__version__", "?")
        except ImportError:
            jl = "?"
        devs = jax.devices()
        backend = (devs[0].platform, devs[0].device_kind, str(len(devs)))
        parts = (jax.__version__, jl) + backend + (
            os.environ.get(ENV_SALT, ""),)
        _FP = hashlib.sha1("|".join(parts).encode()).hexdigest()[:12]
    return _FP


# ------------------------------------------------------------ signatures ---

_SHARD_SIGS = {}    # sharding object -> canonical sig tuple
_DEFAULT_DEV = None


def _default_device():
    global _DEFAULT_DEV
    if _DEFAULT_DEV is None:
        _DEFAULT_DEV = _ensure_jax().devices()[0]
    return _DEFAULT_DEV


def _shard_sig(s):
    """Canonical, cross-process-stable description of a sharding. The
    default single device and 'uncommitted' both canonicalise to ``()`` so
    warmup specs (no sharding) hit the same key as default-device
    traffic."""
    if s is None:
        return ()
    hit = _SHARD_SIGS.get(s)
    if hit is not None:
        return hit
    jax = _ensure_jax()
    if isinstance(s, jax.sharding.SingleDeviceSharding):
        d = next(iter(s.device_set))
        sig = () if d == _default_device() else ("dev", int(d.id))
    elif isinstance(s, jax.sharding.NamedSharding):
        m = s.mesh
        sig = ("named",
               tuple(zip(m.axis_names, m.devices.shape)),
               tuple(_spec_item(x) for x in s.spec),
               tuple(int(d.id) for d in m.devices.flat))
    else:
        r = repr(s)
        # reprs with object addresses are per-process: usable in memory,
        # never persisted (the canonicaliser rejects '0x')
        sig = ("other", r)
    _SHARD_SIGS[s] = sig
    return sig


def _spec_item(x):
    if x is None or isinstance(x, str):
        return x
    return tuple(x)


def _leaf_sig(obj, dt):
    jax = _jax
    if isinstance(obj, _Tracer):
        raise _Bypass
    if isinstance(obj, jax.Array):
        return ("a", obj.shape, dt(obj.dtype), _shard_sig(obj.sharding),
                bool(obj.weak_type))
    if isinstance(obj, jax.ShapeDtypeStruct):
        return ("a", tuple(obj.shape), dt(obj.dtype),
                _shard_sig(getattr(obj, "sharding", None)),
                bool(getattr(obj, "weak_type", False)))
    if isinstance(obj, (_np.ndarray, _np.generic)):
        # a numpy scalar is the 0-d array jit makes of it
        return ("a", obj.shape, dt(obj.dtype), (), False)
    if obj is None or isinstance(obj, (bool, int, float, complex, str)):
        # traced scalar: the value is a runtime argument, only the python
        # type shapes the executable
        return ("p", type(obj).__name__)
    # generic pytree (vjp Partial pullbacks etc.): structure + leaves
    leaves, treedef = jax.tree_util.tree_flatten(obj)
    return ("t", treedef, tuple(_leaf_sig(v, dt) for v in leaves))


def _sig_node(obj, dt):
    t = type(obj)
    if t is tuple or t is list:
        return ("T" if t is tuple else "L",
                tuple(_sig_node(o, dt) for o in obj))
    if t is dict:
        return ("D", tuple((k, _sig_node(v, dt))
                           for k, v in sorted(obj.items())))
    return _leaf_sig(obj, dt)


def _sig_of(args):
    """In-memory call signature: hashable, aval-level (shape/dtype/
    sharding/weak-type/structure). None = not service-cacheable (tracer
    inputs — a nested trace must go through the raw jit path)."""
    _ensure_jax()
    try:
        return tuple(_sig_node(a, _dtype_str) for a in args)
    except _Bypass:
        return None
    except TypeError:
        return None


def _canon(token_key, sig):
    """Cross-process canonical form of (token, sig) for the disk key, or
    None when the signature embeds per-process identity (object reprs
    with addresses, e.g. closure-carrying pullback pytrees)."""
    r = repr(sig)
    if "0x" in r or " object at " in r:
        return None
    return token_key + "||" + r


# ----------------------------------------------------------- site stats ----

def _site_stats(site):
    st = _SITES.get(site)
    if st is None:
        st = _SITES[site] = [0, 0, 0, 0, 0.0, 0.0, 0, 0, 0]
    return st


def stats():
    """Per-site service statistics: ``{site: {hits, misses, disk_hits,
    compiles, compile_ms, load_ms, corrupt, sig_hits, sig_misses}}``.
    ``misses`` = ``disk_hits + compiles`` (+ raw-jit fallbacks);
    ``compile_ms`` on the memory path includes the first execution
    (dispatch-inclusive). ``sig_hits`` / ``sig_misses``: calls through
    :func:`call_spanned` that took the caller's remembered signature
    nodes for their leading arguments, and those that walked every leaf."""
    out = {}
    for site, st in sorted(_SITES.items()):
        if not (st[0] or st[1] or st[2] or st[3] or st[6]):
            continue  # registered but no traffic yet
        out[site] = {"hits": st[0], "misses": st[1], "disk_hits": st[2],
                     "compiles": st[3], "compile_ms": round(st[4], 3),
                     "load_ms": round(st[5], 3), "corrupt": st[6],
                     "sig_hits": st[7], "sig_misses": st[8]}
    return out


def totals():
    """Aggregate over sites."""
    agg = {"hits": 0, "misses": 0, "disk_hits": 0, "compiles": 0,
           "compile_ms": 0.0, "load_ms": 0.0, "corrupt": 0}
    for st in _SITES.values():
        agg["hits"] += st[0]
        agg["misses"] += st[1]
        agg["disk_hits"] += st[2]
        agg["compiles"] += st[3]
        agg["compile_ms"] += st[4]
        agg["load_ms"] += st[5]
        agg["corrupt"] += st[6]
    agg["compile_ms"] = round(agg["compile_ms"], 3)
    agg["load_ms"] = round(agg["load_ms"], 3)
    return agg


def reset_stats():
    # zero IN PLACE: live ServiceFunctions hold references to their
    # site's stat list — replacing the lists would orphan their counters
    with _lock:
        for st in _SITES.values():
            st[0] = st[1] = st[2] = st[3] = st[6] = st[7] = st[8] = 0
            st[4] = st[5] = 0.0


def clear_memory():
    """Drop every registered function's in-memory executable map (disk
    entries and stats are kept) — the next call per signature goes back
    through the disk/compile path. Test seam for exercising persistence
    in-process."""
    with _lock:
        _LOWERED.clear()
        for ref in list(_REGISTRY.values()):
            fn = ref()
            if fn is not None:
                fn._seen.clear()


def registered():
    """Live registered functions as {token_key: site} (diagnose/tests)."""
    out = {}
    for key, ref in list(_REGISTRY.items()):
        fn = ref()
        if fn is not None:
            out[key] = fn._site
    return out


# ------------------------------------------------------------ disk layer ---

def _atomic_write_bytes(path, data):
    """tmp + fsync + rename (concurrent-writer safe: last rename wins,
    readers only ever see complete files). Local twin of
    ``checkpoint.atomic_write`` WITHOUT the ``ckpt.write`` fault point —
    cache writes must not perturb checkpoint fault schedules; they have
    their own ``compile.write`` point."""
    _faults.point("compile.write")
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


def _exec_dir():
    return os.path.join(_DIR, "exec", fingerprint())


def _disk_key(canon):
    return hashlib.sha1(canon.encode()).hexdigest()


#: framed .bin layout: magic + 4-byte meta length + meta json + payload.
#: The CRC meta rides INSIDE the payload file so a load never depends on
#: the .bin/.json pairing — two processes cold-compiling the same key
#: concurrently serialize non-identical bytes, and interleaved renames
#: of separate bin/json files could otherwise leave a permanently
#: mismatched pair (payload from writer B, checksum from writer A).
#: The .json sidecar remains for gc/diagnose introspection.
_FRAME_MAGIC = b"MXTC1"


def _frame(meta_bytes, payload):
    return (_FRAME_MAGIC + len(meta_bytes).to_bytes(4, "big")
            + meta_bytes + payload)


def _unframe(blob):
    """-> (embedded meta | None, payload | None). A legacy (unframed)
    file returns ``(None, blob)``; a mangled frame returns
    ``(None, None)``."""
    if not blob.startswith(_FRAME_MAGIC):
        return None, blob
    try:
        n = int.from_bytes(blob[5:9], "big")
        meta = json.loads(blob[9:9 + n].decode())
        if not isinstance(meta, dict):
            return None, None
        return meta, blob[9 + n:]
    except (ValueError, UnicodeDecodeError):
        return None, None


def _disk_store(key, compiled, site, canon, spec_args):
    """Serialize one compiled executable: a self-verifying framed .bin
    (embedded CRC meta) + a .json sidecar for gc/diagnose. Best effort:
    any failure (unpicklable out-tree, full disk) leaves the in-memory
    entry working and the site on the compile path."""
    try:
        from jax.experimental import serialize_executable as se

        payload = pickle.dumps(se.serialize(compiled))
    except Exception:
        return False
    d = _exec_dir()
    os.makedirs(d, exist_ok=True)
    meta = {"crc32": zlib.crc32(payload) & 0xFFFFFFFF,
            "size": len(payload), "site": site, "canon": canon,
            "fingerprint": fingerprint(), "created": time.time(),
            "args": spec_args,
            # the device assignment the executable was compiled for, in
            # order: deserialize_and_load spreads an executable over
            # EVERY device of the backend unless told which ones
            "devices": [int(d.id) for d in
                        compiled.runtime_executable().local_devices()]}
    meta_bytes = json.dumps(meta, sort_keys=True).encode()
    try:
        _atomic_write_bytes(os.path.join(d, key + ".bin"),
                            _frame(meta_bytes, payload))
        _atomic_write_bytes(os.path.join(d, key + ".json"), meta_bytes)
    except OSError:
        return False
    return True


def _disk_load(key, st):
    """Load + CRC-verify + deserialize one entry; None on any mismatch or
    failure (the corrupt counter distinguishes checksum failures, which
    the caller resolves by recompiling — and eventually GC'ing). The CRC
    comes from the meta embedded in the framed .bin; the .json sidecar
    is only the fallback for legacy (unframed) entries."""
    d = _exec_dir()
    bpath = os.path.join(d, key + ".bin")
    try:
        with open(bpath, "rb") as f:
            blob = f.read()
    except OSError:
        return None
    # 'compile.load' injection point: corrupt mode flips entry bytes so
    # the CRC fallback is deterministically testable
    blob = _faults.point("compile.load", blob)
    meta, payload = _unframe(blob)
    if payload is None:
        st[6] += 1
        return None
    if meta is None:  # legacy unframed entry: the sidecar carries the CRC
        try:
            with open(os.path.join(d, key + ".json"), "rb") as f:
                meta = json.loads(f.read().decode())
        except (OSError, ValueError):
            return None
    if len(payload) != meta.get("size") or \
            (zlib.crc32(payload) & 0xFFFFFFFF) != meta.get("crc32"):
        st[6] += 1
        return None
    ids = meta.get("devices")
    if not ids:  # entry predates the recorded device assignment
        return None
    try:
        from jax.experimental import serialize_executable as se

        by_id = {d.id: d for d in _ensure_jax().devices()}
        return se.deserialize_and_load(
            *pickle.loads(payload),
            execution_devices=[by_id[i] for i in ids])
    except Exception:
        st[6] += 1
        return None


def disk_report():
    """On-disk cache census for diagnose: location, per-fingerprint entry
    counts and bytes, and how much is stale (≠ current fingerprint)."""
    _ensure_configured()
    rep = {"dir": _DIR, "entries": 0, "bytes": 0, "stale_entries": 0,
           "stale_bytes": 0, "fingerprint": None, "xla_entries": 0}
    if _DIR is None:
        return rep
    rep["fingerprint"] = fingerprint()
    root = os.path.join(_DIR, "exec")
    if os.path.isdir(root):
        for fp in sorted(os.listdir(root)):
            sub = os.path.join(root, fp)
            if not os.path.isdir(sub):
                continue
            for name in os.listdir(sub):
                if not name.endswith(".bin"):
                    continue
                try:
                    sz = os.path.getsize(os.path.join(sub, name))
                except OSError:
                    continue
                if fp == rep["fingerprint"]:
                    rep["entries"] += 1
                    rep["bytes"] += sz
                else:
                    rep["stale_entries"] += 1
                    rep["stale_bytes"] += sz
    xla = os.path.join(_DIR, "xla")
    if os.path.isdir(xla):
        rep["xla_entries"] = sum(1 for n in os.listdir(xla)
                                 if n.endswith("-cache"))
    return rep


def gc_cache():
    """Prune the disk cache: whole fingerprint subdirectories that no
    longer match the current backend fingerprint, plus current-fingerprint
    entries whose payload fails its CRC (torn/corrupt writes). Returns a
    summary dict (``tools/diagnose.py --gc``)."""
    _ensure_configured()
    out = {"removed_stale": 0, "removed_corrupt": 0, "bytes_freed": 0}
    if _DIR is None:
        return out
    root = os.path.join(_DIR, "exec")
    if not os.path.isdir(root):
        return out
    cur = fingerprint()
    for fp in sorted(os.listdir(root)):
        sub = os.path.join(root, fp)
        if not os.path.isdir(sub):
            continue
        for name in sorted(os.listdir(sub)):
            path = os.path.join(sub, name)
            if fp != cur:
                try:
                    sz = os.path.getsize(path)
                    os.remove(path)
                    if name.endswith(".bin"):
                        out["removed_stale"] += 1
                    out["bytes_freed"] += sz
                except OSError:
                    pass
                continue
            if not name.endswith(".json"):
                continue
            bpath = path[:-5] + ".bin"
            try:
                with open(bpath, "rb") as f:
                    blob = f.read()
                emeta, payload = _unframe(blob)
                if payload is None:
                    ok = False
                else:
                    # framed entries self-verify; legacy ones fall back
                    # to the sidecar CRC
                    meta = emeta
                    if meta is None:
                        with open(path, "rb") as f:
                            meta = json.loads(f.read().decode())
                    ok = (len(payload) == meta.get("size") and
                          (zlib.crc32(payload) & 0xFFFFFFFF)
                          == meta.get("crc32"))
            except (OSError, ValueError):
                ok = False
            if not ok:
                for p in (bpath, path):
                    try:
                        out["bytes_freed"] += os.path.getsize(p)
                        os.remove(p)
                    except OSError:
                        pass
                out["removed_corrupt"] += 1
        if fp != cur:
            try:
                os.rmdir(sub)
            except OSError:
                pass
    return out


# -------------------------------------------------------- warmup manifest --

def _spec_tree(obj):
    """JSON-able spec of an argument tree (arrays -> shape/dtype/sharding,
    scalars by type+value, containers structurally), or None when the tree
    holds something replay cannot rebuild (closures, tracers)."""
    jax = _ensure_jax()
    t = type(obj)
    if t is tuple or t is list:
        items = [_spec_tree(o) for o in obj]
        if any(i is None for i in items):
            return None
        return {"t": "tuple" if t is tuple else "list", "items": items}
    if t is dict:
        items = {}
        for k, v in obj.items():
            if not isinstance(k, str):
                return None
            sv = _spec_tree(v)
            if sv is None:
                return None
            items[k] = sv
        return {"t": "dict", "items": items}
    if isinstance(obj, _Tracer):
        return None
    if isinstance(obj, (jax.Array, _np.ndarray, _np.generic,
                        jax.ShapeDtypeStruct)):
        from .ops.registry import dtype_str as dt

        sh = getattr(obj, "sharding", None)
        return {"t": "arr", "shape": list(obj.shape),
                "dtype": dt(obj.dtype), "sharding": _shard_json(sh),
                "weak": bool(getattr(obj, "weak_type", False))}
    if obj is None or isinstance(obj, (bool, int, float)):
        return {"t": "py", "type": type(obj).__name__,
                "value": obj}
    return None


def _shard_json(s):
    sig = _shard_sig(s)
    if sig == ():
        return None
    if sig[0] == "dev":
        return ["dev", sig[1]]
    if sig[0] == "named":
        return ["named", [list(p) for p in sig[1]],
                [list(x) if isinstance(x, tuple) else x for x in sig[2]],
                list(sig[3])]
    return None  # 'other' shardings are not manifestable


def _shard_from_json(js):
    if js is None:
        return None
    jax = _ensure_jax()
    if js[0] == "dev":
        for d in jax.devices():
            if d.id == js[1]:
                return jax.sharding.SingleDeviceSharding(d)
        raise ValueError(f"device id {js[1]} not present on this host")
    axes, spec, ids = js[1], js[2], js[3]
    by_id = {d.id: d for d in jax.devices()}
    try:
        devs = [by_id[i] for i in ids]
    except KeyError as e:
        raise ValueError(f"mesh device id {e} not present on this host")
    arr = _np.array(devs).reshape(tuple(int(s) for _, s in axes))
    mesh = jax.sharding.Mesh(arr, tuple(a for a, _ in axes))
    P = jax.sharding.PartitionSpec
    parts = tuple(tuple(x) if isinstance(x, list) else x for x in spec)
    return jax.sharding.NamedSharding(mesh, P(*parts))


def _spec_args(node):
    jax = _ensure_jax()
    t = node["t"]
    if t in ("tuple", "list"):
        items = [_spec_args(i) for i in node["items"]]
        return tuple(items) if t == "tuple" else list(items)
    if t == "dict":
        return {k: _spec_args(v) for k, v in node["items"].items()}
    if t == "arr":
        # "on the default device" replays as COMMITTED there — what an
        # NDArray or a staged serving batch presents. jit lowers committed
        # and uncommitted arguments separately, so replaying uncommitted
        # would compile (and seed jax's cache with) a program that
        # traffic never asks for.
        sh = _shard_from_json(node.get("sharding")) \
            or jax.sharding.SingleDeviceSharding(_default_device())
        return jax.ShapeDtypeStruct(tuple(node["shape"]), node["dtype"],
                                    sharding=sh)
    # scalar leaf: replay with the recorded sample value
    return node.get("value")


def _record_manifest(token_key, site, args):
    """Record one compiled signature; the new entry, or None where it
    was recorded before, cannot be replayed, or the manifest is full."""
    spec = _spec_tree(args)
    if spec is None:
        return None
    ident = (token_key, json.dumps(spec, sort_keys=True))
    with _lock:
        # the cap bounds what the per-op sites can record; a big
        # executable's few signatures always get in (program_texts
        # replays them, and the per-op sites record first)
        if ident in _MANIFEST_SEEN or (len(_MANIFEST) >= _MANIFEST_CAP
                                       and site not in _XCOST_DEFAULT):
            return None
        _MANIFEST_SEEN.add(ident)
        entry = {"site": site, "token": token_key, "args": spec}
        _MANIFEST.append(entry)
    if _DIR is not None:
        _append_manifest_file(entry)
    return entry


def _append_manifest_file(entry):
    """Merge one entry into the cache-dir manifest (read-merge-rename;
    concurrent writers may drop each other's newest entry — warmup is an
    optimisation, losing an entry costs one compile, never correctness)."""
    path = os.path.join(_DIR, MANIFEST_FILE)
    try:
        with _lock:
            try:
                with open(path, "rb") as f:
                    entries = json.loads(f.read().decode())
                if not isinstance(entries, list):
                    entries = []
            except (OSError, ValueError):
                entries = []
            seen = {(e.get("token"), json.dumps(e.get("args"),
                                                sort_keys=True))
                    for e in entries}
            ident = (entry["token"], json.dumps(entry["args"],
                                                sort_keys=True))
            if ident in seen or len(entries) >= _MANIFEST_CAP:
                return
            entries.append(entry)
            _atomic_write_bytes(
                path, json.dumps(entries, sort_keys=True).encode())
    except OSError:
        pass


def manifest():
    """The in-memory warmup manifest recorded by this process (one entry
    per compiled signature whose arguments are replayable)."""
    with _lock:
        return [dict(e) for e in _MANIFEST]


def clear_manifest():
    with _lock:
        _MANIFEST.clear()
        _MANIFEST_SEEN.clear()
        _LOWERED.clear()
        _PROGRAMS.clear()
        _PROGRAM_LISTS.clear()


def save_manifest(path):
    """Write the in-memory manifest as JSON (atomic)."""
    _atomic_write_bytes(os.fspath(path),
                        json.dumps(manifest(), sort_keys=True).encode())
    return path


def last_warmup():
    """Report of the most recent :func:`warmup` replay in this process, or
    (with a cache dir) the one persisted by a previous process."""
    if _LAST_WARMUP is not None:
        return _LAST_WARMUP
    _ensure_configured()
    if _DIR is None:
        return None
    try:
        with open(os.path.join(_DIR, LAST_WARMUP_FILE), "rb") as f:
            return json.loads(f.read().decode())
    except (OSError, ValueError):
        return None


def warmup(source=None):
    """AOT warmup: replay a recorded shape manifest so every registered
    compile site compiles (or disk-loads) its executables BEFORE first
    traffic.

    source : list of manifest entries, a path to a manifest JSON, or None
        — None replays this process's in-memory manifest merged with the
        cache-dir ``warmup_manifest.json`` (the pod cold-start path).

    Entries whose function is not registered yet (lazy sites — CachedOp
    builds on first call, bulk plans on first flush) are kept *pending*
    and replay automatically the moment the site registers, so calling
    ``warmup()`` at process start still front-loads every compile to the
    site's build step instead of its first traffic.

    Returns a report dict (also persisted to ``last_warmup.json`` under
    the cache dir, where ``tools/diagnose.py`` finds it)."""
    global _LAST_WARMUP
    _ensure_configured()
    if source is None:
        entries = manifest()
        if _DIR is not None:
            try:
                with open(os.path.join(_DIR, MANIFEST_FILE), "rb") as f:
                    disk_entries = json.loads(f.read().decode())
                if isinstance(disk_entries, list):
                    seen = {(e.get("token"),
                             json.dumps(e.get("args"), sort_keys=True))
                            for e in entries}
                    for e in disk_entries:
                        ident = (e.get("token"),
                                 json.dumps(e.get("args"), sort_keys=True))
                        if ident not in seen:
                            entries.append(e)
            except (OSError, ValueError):
                pass
    elif isinstance(source, (str, os.PathLike)):
        with open(os.fspath(source), "rb") as f:
            entries = json.loads(f.read().decode())
    else:
        entries = list(source)
    report = {"entries": len(entries), "compiled": 0, "disk": 0,
              "cached": 0, "pending": 0, "errors": [],
              "time": time.time()}
    for entry in entries:
        token_key = entry.get("token")
        ref = _REGISTRY.get(token_key)
        fn = ref() if ref is not None else None
        if fn is None:
            with _lock:
                _PENDING_WARMUP.setdefault(token_key, []).append(entry)
            report["pending"] += 1
            continue
        try:
            outcome = fn._warmup(entry)
            report[outcome] += 1
        except Exception as e:
            report["errors"].append(f"{token_key}: "
                                    f"{type(e).__name__}: {e}")
    _LAST_WARMUP = report
    if _DIR is not None:
        try:
            _atomic_write_bytes(os.path.join(_DIR, LAST_WARMUP_FILE),
                                json.dumps(report, sort_keys=True).encode())
        except OSError:
            pass
    return report


# id(manifest entry) -> the ``Lowered`` a miss of a big-executable site
# made for its cost analysis, until program_texts prints it. A STOPGAP for
# a caller that reads its trace after it dropped its trainer
# (chipbench/modes/train.py; ROADMAP A2 has what lets it go again). jit's
# own caches hold the same lowering and its loaded executable while the
# function lives, so a kept one costs nothing until its owner dies and
# pins both after (a module in host memory, the program in the device's;
# PERF.md section 6 has the sizes), nothing of the owner (no function, no
# buffer). The last ``_LOWERED_CAP`` a process: a later miss, program_texts,
# clear_manifest and clear_memory let go.
_LOWERED = {}
_LOWERED_CAP = 4
_PROGRAMS = {}       # id(manifest entry) -> what program_texts printed
_PROGRAM_LISTS = {}  # site -> the list program_texts last returned


def _keep_lowered(entry, lowered):
    with _lock:
        _LOWERED[id(entry)] = lowered
        for key in list(_LOWERED)[:-_LOWERED_CAP]:
            del _LOWERED[key]


def program_texts(site):
    """The optimized HLO of what ``site`` ran: one ``{"token", "module",
    "text"}`` for every signature of ``site`` in the manifest (``"trainer"``:
    the compiled step) whose function is alive or left its lowering
    behind (``_LOWERED`` above). ``module`` is the
    ``HloModule``'s name, what a device trace's ``XLA Modules`` events are
    named after (``jit_step_fn``); every instruction of ``text`` carries
    ``metadata={op_name="jit(step_fn)/..."}``, where jax marks forward
    (``jvp(``) and backward (``transpose(jvp(``) and the step its update
    and its guard (``sharded_trainer.UPDATE_SCOPE`` / ``GUARD_SCOPE``): the
    key to join a trace's instruction names with.

    The executable itself is never in this module's hands (a donating step
    runs through jit's own path), so the recorded specs are lowered again
    through the live function, the way ``_warmup`` replays them, and
    ``compile()`` hands back what jit made of that lowering (memoized
    there; a load from jax's persistent cache where it is not). On demand
    and only here; the ``Compiled`` is dropped uncalled and enters no
    cache of this module, no counter moves, and a lowering that fails
    raises. The names in an executable jax took from its persistent cache
    are those of whichever tree compiled it first (jax's key leaves them
    out): over a cache an older tree filled the text is that tree's.
    Memoized: the same list until ``site`` records another signature."""
    _ensure_configured()
    with _lock:
        entries = [e for e in _MANIFEST if e["site"] == site]
    texts = []
    for entry in entries:
        held = _PROGRAMS.get(id(entry))
        if held is None:
            lowered = _LOWERED.pop(id(entry), None)
            if lowered is None:
                ref = _REGISTRY.get(entry["token"])
                fn = ref() if ref is not None else None
                if fn is None:
                    continue
                lowered = fn.lower(*_spec_args(entry["args"]))
            text = lowered.compile().as_text()
            module = re.match(r"HloModule\s+([^\s,]+)", text)
            held = _PROGRAMS[id(entry)] = {
                "token": entry["token"],
                "module": module.group(1) if module else None, "text": text}
        texts.append(held)
    memo = _PROGRAM_LISTS.get(site)
    if memo is not None and len(memo) == len(texts) \
            and all(a is b for a, b in zip(memo, texts)):
        return memo
    _PROGRAM_LISTS[site] = texts
    return texts


# -------------------------------------------------- telemetry capture ------

_XCOST_DEFAULT = frozenset(
    ("trainer", "cachedop", "executor", "serving", "predictor"))
_xcost_sites = None


def _xcost_wanted(site):
    """Should this site's executables get XLA cost/memory analyses
    captured into telemetry? ``MXNET_TPU_TELEMETRY_XCOST``: unset = the
    big-executable sites (per-op 'dispatch' and fused 'bulk' segments
    are excluded — their trace-only capture would re-trace on every
    miss for records nobody reads); '0' = none; 'all' = every site; a
    comma list = exactly those sites."""
    global _xcost_sites
    if not _tele_state.enabled:
        return False
    if _xcost_sites is None:
        spec = os.environ.get("MXNET_TPU_TELEMETRY_XCOST", "").strip()
        if not spec:
            _xcost_sites = _XCOST_DEFAULT
        elif spec.lower() in ("0", "false", "off"):
            _xcost_sites = frozenset()
        elif spec.lower() == "all":
            _xcost_sites = True
        else:
            _xcost_sites = frozenset(
                s.strip() for s in spec.split(",") if s.strip())
    return _xcost_sites is True or site in _xcost_sites


def _capture_analysis(site, token_key, compiled=None, lowered=None,
                      source="compile"):
    """Record one executable's XLA analyses into telemetry (best effort
    — never let observability fail a compile). With a ``Compiled`` in
    hand both ``cost_analysis`` and ``memory_analysis`` land; the
    trace-only path (``Lowered``) yields cost only."""
    obj = compiled if compiled is not None else lowered
    if obj is None:
        return
    try:
        cost = obj.cost_analysis()
    except Exception:
        cost = None
    mem = None
    if compiled is not None:
        try:
            mem = compiled.memory_analysis()
        except Exception:
            mem = None
    if cost is None and mem is None:
        return
    try:
        _tele_costs.record_executable(site, token_key, cost=cost, mem=mem,
                                      source=source)
    except Exception:
        pass


# --------------------------------------------------------------- service ---

class ServiceFunction:
    """A jit-compatible callable owned by the compile service.

    Call path: signature build -> in-memory map. A hit calls the cached
    executable (for plain signatures without a cache dir that IS the
    wrapped ``jax.jit``, whose C++ dispatch cache does the real work — the
    service adds one dict probe). A miss consults the disk cache, then
    AOT-compiles (``lower().compile()``) when persisting or falls through
    to the jit call, records the signature into the warmup manifest, and
    accounts per-site metrics."""

    def __init__(self, fn, site, token_key, jit_kwargs):
        jax = _ensure_jax()
        self._fn = fn
        self._site = site
        self._token_key = token_key
        self._jit = jax.jit(fn, **jit_kwargs)
        # donated buffers MUST dispatch through jit's C++ path: the AOT
        # Compiled.__call__ donation handling corrupts the heap on CPU
        # jaxlib (observed: malloc_consolidate aborts under the trainer
        # step) — donating executables therefore never persist as
        # serialized artifacts; their cross-run warm start is jax's
        # native compilation cache (re-trace, backend-compile skipped)
        self._donating = bool(jit_kwargs.get("donate_argnums"))
        self._st = _site_stats(site)
        self._seen = {}
        self.__name__ = getattr(fn, "__name__", site)
        with _lock:
            _REGISTRY[token_key] = weakref.ref(self)
            pending = _PENDING_WARMUP.pop(token_key, None)
        if pending:
            for entry in pending:
                try:
                    self._warmup(entry)
                except Exception:
                    pass  # warmup is best-effort; traffic compiles anyway

    # ------------------------------------------------------------- call ---
    def __call__(self, *args):
        if not _ENABLED:
            return self._jit(*args)
        sig = _sig_of(args)
        if sig is None:  # tracer inputs: nested trace, raw path
            return self._jit(*args)
        rec = self._seen.get(sig)
        if rec is not None:
            self._st[0] += 1
            if _distcheck.CACHE_TRACK:
                _distcheck.cache_event("service", self._site, sig, True)
            return rec(*args)
        return self._miss(sig, args)

    def lower(self, *args, **kwargs):
        """Pass-through to the wrapped jit's AOT lowering (no bookkeeping:
        ``_warmup`` and :func:`program_texts` replay recorded specs
        through it)."""
        return self._jit.lower(*args, **kwargs)

    def _miss(self, sig, args):
        _ensure_configured()
        st = self._st
        st[1] += 1
        if _distcheck.CACHE_TRACK:
            _distcheck.cache_event("service", self._site, sig, False)
        _flight.rec("compile.miss", self._site, self.__name__)
        canon = None if (_DIR is None or self._donating) \
            else _canon(self._token_key, sig)
        if canon is not None:
            key = _disk_key(canon + "||" + fingerprint())
            t0 = time.perf_counter()
            loaded = _disk_load(key, st)
            if loaded is not None:
                ms = (time.perf_counter() - t0) * 1e3
                st[2] += 1
                st[5] += ms
                self._seen[sig] = loaded
                # disk hits are warmup-worthy signatures too: keep the
                # manifest fresh for future pods
                _record_manifest(self._token_key, self._site, args)
                if _xcost_wanted(self._site):
                    _capture_analysis(self._site, self._token_key,
                                      compiled=loaded, source="disk")
                _profiler_compile(self._site, ms, "disk", st)
                return loaded(*args)
            # compile AOT so the executable can be serialized for the
            # next process
            t0 = time.perf_counter()
            compiled = self._jit.lower(*args).compile()
            ms = (time.perf_counter() - t0) * 1e3
            st[3] += 1
            st[4] += ms
            self._seen[sig] = compiled
            _record_manifest(self._token_key, self._site, args)
            _disk_store(key, compiled, self._site, canon,
                        _spec_tree(args))
            if _xcost_wanted(self._site):
                _capture_analysis(self._site, self._token_key,
                                  compiled=compiled, source="compile")
            _profiler_compile(self._site, ms, "compile", st)
            return compiled(*args)
        # memory mode (or non-persistable signature): the jit call itself
        # traces + compiles; its own cache serves subsequent hits
        t0 = time.perf_counter()
        out = self._jit(*args)
        ms = (time.perf_counter() - t0) * 1e3
        st[3] += 1
        st[4] += ms
        self._seen[sig] = self._jit
        entry = _record_manifest(self._token_key, self._site, args)
        if _xcost_wanted(self._site):
            # no Compiled object in hand on this path (the jit's own
            # executable is internal); one extra trace+lower buys the
            # cost analysis — no XLA backend compile happens here — and
            # is what program_texts prints from once the owner is gone
            try:
                lowered = self._jit.lower(*args)
                _capture_analysis(self._site, self._token_key,
                                  lowered=lowered, source="trace")
                if entry is not None:
                    _keep_lowered(entry, lowered)
            except Exception:
                pass
        _profiler_compile(self._site, ms, "compile", st)
        return out

    # ----------------------------------------------------------- warmup ---
    def _warmup(self, entry):
        """Replay one manifest entry: compile (or disk-load) the recorded
        signature ahead of traffic. Returns 'cached'|'disk'|'compiled'."""
        args = _spec_args(entry["args"])
        sig = _sig_of(args)
        if sig is None:
            raise ValueError("manifest entry signature not cacheable")
        if sig in self._seen:
            return "cached"
        st = self._st
        canon = None if (_DIR is None or self._donating) \
            else _canon(self._token_key, sig)
        if canon is not None:
            key = _disk_key(canon + "||" + fingerprint())
            t0 = time.perf_counter()
            loaded = _disk_load(key, st)
            if loaded is not None:
                st[2] += 1
                st[5] += (time.perf_counter() - t0) * 1e3
                self._seen[sig] = loaded
                return "disk"
        t0 = time.perf_counter()
        compiled = self.lower(*args).compile()
        ms = (time.perf_counter() - t0) * 1e3
        st[3] += 1
        st[4] += ms
        if _xcost_wanted(self._site):
            _capture_analysis(self._site, self._token_key,
                              compiled=compiled, source="warmup")
        if self._donating:
            # the compile above seeded jax's native compilation cache, so
            # the jit re-trace at first traffic skips backend-compile —
            # but the AOT object itself must never be CALLED with
            # donation (see __init__); drop it
            _profiler_compile(self._site, ms, "warmup", st)
            return "compiled"
        self._seen[sig] = compiled
        if canon is not None:
            _disk_store(key, compiled, self._site, canon, entry["args"])
        _profiler_compile(self._site, ms, "warmup", st)
        return "compiled"

    def __repr__(self):
        return f"ServiceFunction({self._site}:{self.__name__})"


def _profiler_compile(site, ms, source, st):
    if _profiler._RECORDING:
        _profiler.record_compile(site, ms, source, st[0], st[1])


def _token_key(site, token):
    return site + "|" + hashlib.sha1(repr(token).encode()).hexdigest()[:20]


def signature(args):
    """The signature nodes of ``args``, one per argument, as the service
    keys its executables: what :func:`call_spanned` takes as ``known``.
    None where the service would not use them (switched off, or a tracer
    among the leaves)."""
    return _sig_of(args) if _ENABLED else None


def call_spanned(fn, *args, known=None):
    """``fn(*args)`` for a caller that runs once a step, not once an op
    (the trainer): ``ServiceFunction.__call__``'s two halves each in a
    span of their own — ``compile.signature`` and ``compile.execute``
    (the dict probe and the executable's call: jit's C++ dispatch with
    its donation holds, or on a miss the compile). ``__call__`` itself,
    the per-op path, stays as it is.

    ``known`` is :func:`signature` of the leading ``len(known)``
    arguments, built earlier from these very objects (the caller has seen
    that by identity: an array's shape, dtype and sharding never change),
    so ``compile.signature`` walks the leaves of the remaining arguments
    only (the site's ``sig_hits``). Without it every leaf is walked
    (``sig_misses``)."""
    if not (_ENABLED and isinstance(fn, ServiceFunction)):
        with _trace.span("compile.execute"):
            return fn(*args)
    with _trace.span("compile.signature"):
        if known is None:
            fn._st[8] += 1
            sig = _sig_of(args)
        else:
            fn._st[7] += 1
            sig = _sig_of(args[len(known):])
            if sig is not None:
                sig = known + sig
    with _trace.span("compile.execute"):
        if sig is None:
            return fn._jit(*args)
        rec = fn._seen.get(sig)
        if rec is None:
            return fn._miss(sig, args)
        fn._st[0] += 1
        if _distcheck.CACHE_TRACK:
            _distcheck.cache_event("service", fn._site, sig, True)
        return rec(*args)


def jit(fn, *, site, token, **jit_kwargs):
    """The framework-wide replacement for ``jax.jit``.

    site : metric bucket — 'dispatch' | 'bulk' | 'cachedop' | 'executor'
        | 'trainer' | 'predictor' | 'serving' (new sites welcome;
        mxlint's ``raw-jit`` rule sends every new compile call here).
    token : the function's *stable identity across processes* — whatever
        deterministic hashable value distinguishes this function from any
        other the site builds (op name + frozen kwargs, bulk plan,
        CachedOp signature, symbol graph hash). Two functions sharing one
        token would cross-hit the disk cache; tokens must be injective
        per site.
    jit_kwargs : forwarded to ``jax.jit`` (in_shardings/out_shardings/
        donate_argnums). ``static_argnums``/``static_argnames`` are not
        service-managed — such calls get a raw ``jax.jit`` back
        (documented limitation; no current site uses them).

    With ``MXNET_TPU_COMPILE_SERVICE=0`` this returns the raw ``jax.jit``
    object (zero service overhead)."""
    if "static_argnums" in jit_kwargs or "static_argnames" in jit_kwargs \
            or not _ENABLED:
        return _ensure_jax().jit(fn, **jit_kwargs)
    _ensure_configured()
    if jit_kwargs.get("donate_argnums") and _DIR is not None \
            and _default_device().platform == "cpu":
        # CPU jaxlib corrupts the heap when a DESERIALIZED executable
        # (ours, or one from jax's own compilation cache) donates its
        # input buffers (malloc_consolidate aborts under the trainer
        # step). Donation is purely a memory optimisation, so on the CPU
        # backend the persistent cache wins: strip donation, keep the
        # executable serializable. TPU runtimes handle donation through
        # jax's cache normally and keep it (only OUR executable
        # serialization is skipped for donating fns there — see
        # ServiceFunction.__init__).
        jit_kwargs = dict(jit_kwargs, donate_argnums=())
    return ServiceFunction(fn, site, _token_key(site, token), jit_kwargs)
