"""Pallas kernel layer: registry + autotuned per-shape dispatch.

The reference earns its throughput from ~198k LoC of hand-fused CUDA
under ``src/operator/*.cu``; the TPU-native analogue is a *small* set of
Pallas kernels behind a **measured** dispatch seam. Each op family
registers here with

  * a Pallas implementation (``kernel``) — runs natively on TPU, in the
    Pallas interpreter on CPU (numerics test-assertable everywhere);
  * the XLA baseline callable (``xla``) — always correct, always
    available, and the fallback whenever the kernel is untuned,
    unavailable or disabled;
  * a shape-bucketing function (``bucket``) — pure function of the
    input avals, keying the persisted dispatch table;
  * a static-constraint predicate (``supports``) — the Mosaic
    alignment rules the kernel needs, checked before dispatch.

``dispatch(family, *arrays, **kw)`` consults the dispatch table that
``benchmark/opperf.py --kernels`` measured and persisted (same
tmp+fsync+rename/CRC discipline and backend fingerprint as the compile
cache, under ``MXNET_TPU_CACHE_DIR/kernels/`` — :mod:`.table`), so a
kernel only ever runs where it is *measurably* faster; an untuned bucket
takes the family's conservative default (kernel on TPU only for families
proven there, XLA otherwise). ``MXNET_TPU_KERNELS=0`` disables every
kernel — the end-to-end numerics-parity opt-out.

Families shipped (docs/PERFORMANCE.md "Pallas kernel layer"):

=================  ====================================================
flash_attention    blocked online-softmax attention (moved here from
                   ``ops/pallas_ops.py``; that module remains the op
                   registration shim); also writes the rows' log-sum-exp
flash_attention_   its backward, a decision of its own taken inside the
bwd                forward's ``custom_vjp``: one fused Pallas call (two,
                   dK/dV then dQ, for long sequences) over the block
                   pairs under the diagonal; its XLA side is the
                   gradient of the dense reference
int8_gemm          int8×int8→int32 GEMM with fused dequant+bias+relu
                   (the ``_contrib_quantized_*`` MXU path)
decode_attention   single-query flash against a padded KV cache (the
                   continuous-batching decode prerequisite)
twobit_compress /  2-bit gradient quantization with error feedback and
twobit_decompress  its rescale (kvstore gradient compression)
selective_scan     the recurrence of a Mamba-1 state-space layer, walked
                   in chunks with the float32 state resident in VMEM; its
                   XLA side is the same chunking as nested ``lax.scan``s
selective_scan_    its backward, decided inside the forward's
bwd                ``custom_vjp``: resumes from the states saved at chunk
                   boundaries and recomputes inside a chunk
grouped_matmul     an expert layer's grouped products over its
                   expert-ordered rows (megablox ``gmm`` / ``tgmm``), in
                   the first rung of its buffer; its XLA side is
                   ``jax.lax.ragged_dot``
=================  ====================================================

Fallbacks LATCH: Pallas-unavailable is probed once per process and
warned once per family (the PR 11 native-probe pattern — no silent
per-call degradation), with every fallback event counted in
``mxtpu_kernels_fallback_total{family,reason}``. Dispatch decisions are
counted in ``mxtpu_kernels_dispatch_total{family,choice}`` and the
bucket keys feed distcheck pass 4 (cache-churn sweep), so an unstable
bucketing function is flagged exactly like an unstable compile key.
"""
from __future__ import annotations

import functools as _functools
import os
import threading

from . import table

__all__ = ["KernelEntry", "register_kernel", "entry", "families",
           "dispatch", "choice_for", "enabled", "pallas_available",
           "on_tpu", "dispatch_stats", "fallback_report", "token_salt",
           "reset_stats", "table"]

_FAMILIES: dict = {}
_lock = threading.Lock()
_stats: dict = {}          # family -> {"kernel": n, "xla": n, reasons, buckets}
_warned_families = set()   # fallback warned once per family (latch)
_seen_buckets: dict = {}   # family -> set of bucket keys (distcheck pass 4)


class KernelEntry:
    """One registered op family (see module docstring for the fields)."""

    __slots__ = ("family", "kernel", "xla", "bucket", "supports",
                 "default_tpu", "tolerance")

    def __init__(self, family, kernel, xla, bucket, supports=None,
                 default_tpu=False, tolerance=""):
        self.family = family
        self.kernel = kernel
        self.xla = xla
        self.bucket = bucket
        self.supports = supports or (lambda *a, **k: True)
        self.default_tpu = bool(default_tpu)
        self.tolerance = tolerance


def register_kernel(family, *, kernel, xla, bucket, supports=None,
                    default_tpu=False, tolerance=""):
    """Register an op family. ``tolerance`` documents the kernel's
    numeric contract vs its XLA baseline (bit-exact, or the rtol/atol
    the tests assert)."""
    e = KernelEntry(family, kernel, xla, bucket, supports, default_tpu,
                    tolerance)
    _FAMILIES[family] = e
    return e


def entry(family):
    return _FAMILIES[family]


def families():
    """Registered family names, sorted (registry census)."""
    return sorted(_FAMILIES)


def enabled():
    """False when ``MXNET_TPU_KERNELS=0`` — every dispatch then takes
    the XLA baseline, restoring pre-kernel numerics bit-exactly."""
    return os.environ.get("MXNET_TPU_KERNELS", "1") != "0"


@_functools.lru_cache(maxsize=1)
def pallas_available():
    """Import-probe Pallas ONCE per process (the latch — never re-probe
    per call)."""
    try:
        from jax.experimental import pallas  # noqa: F401
        from jax.experimental.pallas import tpu  # noqa: F401

        return True
    except ImportError:
        return False


@_functools.lru_cache(maxsize=1)
def on_tpu():
    """Backend probe, cached for the process lifetime (dispatch runs at
    trace time, but trace time is still a hot path for eager ops). A
    backend that fails to start raises here — it must not read as "not
    on TPU" and route every kernel to the interpreter."""
    import jax

    return jax.devices()[0].platform == "tpu"


def _count(family, choice, reason, bucket=None):
    with _lock:
        rec = _stats.setdefault(family, {"kernel": 0, "xla": 0,
                                         "reasons": {}, "buckets": {}})
        rec[choice] += 1
        rec["reasons"][reason] = rec["reasons"].get(reason, 0) + 1
        if bucket is not None:
            per = rec["buckets"].setdefault(bucket, {"kernel": 0, "xla": 0})
            per[choice] += 1
    try:
        from ..telemetry import registry as _registry

        _registry.counter(
            "mxtpu_kernels_dispatch_total",
            "Kernel-layer dispatch decisions", ("family", "choice")
        ).inc(1, family, choice)
    except Exception:
        pass


def _fallback(family, reason, detail=""):
    """Count (and once per family, warn about) a kernel->XLA fallback.
    Mirrors the native-IO probe pattern: the *reason* is cached and
    surfaced once, every later event is a counter bump only."""
    try:
        from ..telemetry import registry as _registry

        _registry.counter(
            "mxtpu_kernels_fallback_total",
            "Kernel-layer dispatches that fell back to the XLA baseline",
            ("family", "reason")).inc(1, family, reason)
    except Exception:
        pass
    if reason == "pallas_unavailable" and family not in _warned_families:
        _warned_families.add(family)
        try:
            from .. import log as _log

            _log.get_logger("mxnet_tpu.kernels").warning(
                "Pallas unavailable — kernel family %r permanently on "
                "the XLA baseline this process%s (see tools/diagnose.py "
                "'Kernels')", family, f" ({detail})" if detail else "")
        except Exception:
            pass


def _decide(e, args, kwargs, interpret):
    """(choice, reason, bucket) for one dispatch. Pure w.r.t. the traced
    values — only aval shapes/dtypes and process-level state feed it, so
    the decision is stable per shape bucket (and bakes into whatever
    executable is tracing us)."""
    if not enabled():
        return "xla", "env_disabled", None
    if not pallas_available():
        return "xla", "pallas_unavailable", None
    try:
        ok = e.supports(*args, **kwargs)
    except Exception:
        ok = False
    if not ok:
        return "xla", "unsupported_shape", None
    bucket = e.bucket(*args, **kwargs)
    # distcheck pass 4: dispatch keys must not churn — same workload,
    # same bucket. First sighting is the one legitimate "miss".
    try:
        from ..analysis import distcheck as _distcheck

        if _distcheck.CACHE_TRACK:
            seen = _seen_buckets.setdefault(e.family, set())
            _distcheck.cache_event("dispatch", f"kernels.{e.family}",
                                   bucket, bucket in seen)
            seen.add(bucket)
    except Exception:
        pass
    if interpret is not None:
        # explicit request for the kernel: interpreted (tests, CPU
        # numerics checks) or, with interpret=False, Mosaic-compiled
        # (chip_smoke.py's proof that every family compiles)
        return "kernel", ("interpret_forced" if interpret
                          else "kernel_forced"), bucket
    row = table.lookup(e.family, bucket)
    if row is not None:
        return row.get("winner", "xla"), "tuned", bucket
    if e.default_tpu and on_tpu():
        return "kernel", "untuned_default_tpu", bucket
    return "xla", "untuned_default", bucket


def dispatch(family, *args, interpret=None, **kwargs):
    """Route one call: the family's Pallas kernel where the dispatch
    table proved it faster, the XLA baseline everywhere else. An explicit
    ``interpret`` forces the kernel for a supported shape: ``True`` in
    the Pallas interpreter, ``False`` compiled by Mosaic (an error off
    TPU). Safe to call under a jit trace — the decision depends only on
    shapes and process state, so it is baked into the traced executable
    exactly like any other static argument."""
    e = _FAMILIES[family]
    choice, reason, bucket = _decide(e, args, kwargs, interpret)
    _count(family, choice, reason, bucket)
    if choice == "kernel":
        # Pallas has no native CPU lowering: unless the caller said
        # which, off-TPU the kernel runs in the interpreter (numerics
        # seam; opperf records such rows with interpret=true so nobody
        # mistakes them for a speed claim)
        run_interpret = not on_tpu() if interpret is None \
            else bool(interpret)
        return e.kernel(*args, interpret=run_interpret, **kwargs)
    _fallback(family, reason)
    return e.xla(*args, **kwargs)


def choice_for(family, *args, **kwargs):
    """(choice, reason) dispatch WOULD make for these inputs — the
    introspection seam tests and diagnose use (no counters touched)."""
    e = _FAMILIES[family]
    choice, reason, _ = _decide(e, args, kwargs, None)
    return choice, reason


def dispatch_stats():
    """Per-family dispatch decision counts (process-local), in all and
    by shape bucket (the family's ``bucket`` key; decisions taken before
    a bucket exists, e.g. an unsupported shape, count in the totals
    only)."""
    with _lock:
        return {f: {"kernel": r["kernel"], "xla": r["xla"],
                    "reasons": dict(r["reasons"]),
                    "buckets": {b: dict(c)
                                for b, c in sorted(r["buckets"].items())}}
                for f, r in sorted(_stats.items())}


def fallback_report():
    """Families latched onto the XLA baseline and why (diagnose)."""
    return {"pallas_available": pallas_available(),
            "warned_families": sorted(_warned_families),
            "enabled": enabled()}


def reset_stats():
    with _lock:
        _stats.clear()
    _seen_buckets.clear()


def token_salt():
    """Short hash of the dispatch state (enabled flag + table identity +
    entry winners) for folding into compile-service tokens: a dispatch
    change must produce a different executable identity, never a silent
    reuse of one traced under the old routing."""
    import hashlib
    import json as _json

    t = table.load()
    blob = _json.dumps({"enabled": enabled(),
                        "fp": t.get("fingerprint"),
                        "entries": t.get("entries", {})},
                       sort_keys=True)
    return hashlib.sha1(blob.encode()).hexdigest()[:12]


# family registrations (import order is alphabetical, not load-bearing)
from . import flash  # noqa: E402,F401  (flash_attention, flash_attention_bwd)
from . import int8_gemm  # noqa: E402,F401  (int8_gemm)
from . import decode_attention  # noqa: E402,F401  (decode_attention)
from . import twobit  # noqa: E402,F401  (twobit_compress/_decompress)
from . import selective_scan  # noqa: E402,F401  (selective_scan, _bwd)
from . import grouped_matmul  # noqa: E402,F401  (grouped_matmul)
