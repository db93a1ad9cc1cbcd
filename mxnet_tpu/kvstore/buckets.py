"""Bucketed, priority-ordered, async cross-host gradient reduction.

The legacy ``_DistKVStore.push`` host-blocks on ONE collective per key in
push order — the ``sync`` phase of the PR 9 step timeline is a dead
serial tail after backward. This module is the overlap pipeline that
hides it (the reference hides the same cost with priority-ordered async
pushes through the dependency engine + ps-lite, SURVEY §L2/L7):

* **Bucketing** — pushed gradients are flattened and staged into
  size-capped buckets (``MXNET_TPU_BUCKET_BYTES``, default 4 MiB; ``0``
  restores the legacy per-key path exactly). Bucket assembly is a pure
  function of *registration order* (the ``init`` sequence), never of
  push arrival order, so every rank builds the identical plan and the
  distcheck pass-2 collective fingerprint stays rank-identical.
* **Priority / overlap** — a bucket dispatches its ONE fused collective
  the moment its last member arrives (backward pushes complete
  last-registered buckets first, so last-layer grads reduce while
  earlier layers are still computing); buckets still staged at a flush
  point dispatch in descending registration order (the MXNet
  ``priority=-index`` contract). Dispatch is JAX async — nothing blocks.
* **Resolution** — futures resolve at ``pull`` / ``barrier`` /
  optimizer-apply under the existing ``kvstore.sync`` watchdog point:
  a dead peer still surfaces as a structured
  :class:`~mxnet_tpu.kvstore.PeerLostError` (now carrying the bucket
  census, which also rides in the crash bundle), and only the *blocked*
  tail of each collective is accounted as ``sync`` time in the step
  timeline — the overlapped remainder is the win the
  ``mxtpu_kvstore_overlap_ratio`` gauge reports.

``MXNET_TPU_BUCKET_FORCE=1`` engages the pipeline even in a 1-process
group (the collective degenerates to identity) — the single-process
test/chaos seam for the full stage→fuse→dispatch→resolve path.
"""
from __future__ import annotations

import os
import threading
import time
import weakref

__all__ = ["DEFAULT_BUCKET_BYTES", "bucket_bytes", "bucket_force",
           "BucketPlan", "BucketPipeline", "census", "comm_stats"]

DEFAULT_BUCKET_BYTES = 4 << 20  # ~4 MiB, the classic DDP bucket size

#: process-lifetime pipelines (weak — dropped with their kvstore), read
#: by the telemetry collector, tools/diagnose.py and crash bundles
_LIVE: "weakref.WeakSet[BucketPipeline]" = weakref.WeakSet()


def bucket_bytes():
    """Effective bucket cap in bytes (``MXNET_TPU_BUCKET_BYTES``;
    0 disables bucketing — the legacy per-key collective path)."""
    raw = os.environ.get("MXNET_TPU_BUCKET_BYTES")
    if not raw:
        return DEFAULT_BUCKET_BYTES
    try:
        return max(0, int(raw))
    except ValueError:
        return DEFAULT_BUCKET_BYTES


def bucket_force():
    """True when ``MXNET_TPU_BUCKET_FORCE=1`` engages the pipeline even
    for a 1-process group (tests / chaos drills)."""
    return os.environ.get("MXNET_TPU_BUCKET_FORCE") == "1"


class BucketPlan:
    """Deterministic key → bucket assignment, keyed on registration
    order alone.

    Keys are appended greedily in ``init`` order: a key joins the
    newest bucket iff the dtype matches and the bucket stays under the
    byte cap, else it opens the next bucket. An oversized single
    gradient therefore gets a bucket of its own (and never blocks other
    keys from fusing). The assignment is stable under append — earlier
    buckets never change when new keys register — and identical on
    every rank that runs the same ``init`` sequence.
    """

    def __init__(self, cap_bytes):
        self.cap = int(cap_bytes)
        self.order = []    # keys, registration order
        self.info = {}     # key -> {shape, dtype, nelems, nbytes, bucket}
        self.buckets = []  # [{bid, keys, nbytes, dtype}]

    def register(self, key, shape, dtype):
        """Add `key` (idempotent). Returns its bucket id."""
        if key in self.info:
            return self.info[key]["bucket"]
        import numpy as _np

        shape = tuple(int(d) for d in shape)
        nelems = 1
        for d in shape:
            nelems *= d
        dtype = str(dtype)
        nbytes = nelems * _np.dtype(dtype).itemsize
        if self.buckets and self.buckets[-1]["dtype"] == dtype \
                and self.buckets[-1]["nbytes"] + nbytes <= self.cap:
            b = self.buckets[-1]
        else:
            b = {"bid": len(self.buckets), "keys": [], "nbytes": 0,
                 "dtype": dtype}
            self.buckets.append(b)
        b["keys"].append(key)
        b["nbytes"] += nbytes
        self.order.append(key)
        self.info[key] = {"shape": shape, "dtype": dtype,
                          "nelems": nelems, "nbytes": nbytes,
                          "bucket": b["bid"]}
        return b["bid"]

    def describe(self):
        return {"cap_bytes": self.cap, "keys": len(self.order),
                "buckets": [{"bid": b["bid"], "keys": len(b["keys"]),
                             "bytes": b["nbytes"], "dtype": b["dtype"]}
                            for b in self.buckets]}


class _InFlight:
    """One dispatched (not yet resolved) fused collective."""

    __slots__ = ("bid", "seq", "keys", "meta", "future", "mode", "nbytes",
                 "partial", "t_stage0", "t_fuse", "t_dispatch")

    def __init__(self, bid, seq, keys, meta, future, mode, nbytes,
                 partial, t_stage0, t_fuse, t_dispatch):
        self.bid = bid
        self.seq = seq
        self.keys = keys
        self.meta = meta
        self.future = future
        self.mode = mode
        self.nbytes = nbytes
        self.partial = partial
        self.t_stage0 = t_stage0
        self.t_fuse = t_fuse
        self.t_dispatch = t_dispatch


class BucketPipeline:
    """The staging/dispatch/resolve state machine for one dist kvstore.

    The owning store provides the collective hooks (duck-typed, so tests
    drive the pipeline with a stub):

    ``_bucket_mode()``            -> "sum" | "gather"
    ``_dispatch_bucket(raw, mode)`` -> future array (async dispatch)
    ``_apply_reduced(key, piece, mode, meta)``  scatter-back per key
    ``_note_bucket(mode, sig)``   collective-schedule fingerprint note
    ``rank`` / ``num_workers``    gang coordinates for error messages
    """

    def __init__(self, kv, cap_bytes):
        self._kv = kv
        self.plan = BucketPlan(cap_bytes)
        self._staged = {}    # bid -> {"vals": {k: raw}, "meta": {k: meta},
        #                             "t0": monotonic of first stage}
        self._inflight = []  # FIFO of _InFlight
        self._lock = threading.RLock()
        self._seq = 0
        self.stats = {"fused": 0, "keys": 0, "bytes": 0, "partial": 0,
                      "drains": 0, "resolved": 0,
                      "wait_ms": 0.0, "window_ms": 0.0, "max_pending": 0}
        _LIVE.add(self)

    # ------------------------------------------------------------ intake --
    def register(self, key, shape, dtype):
        with self._lock:
            return self.plan.register(key, shape, dtype)

    def wants(self, key):
        """True when `key` rides the bucket pipeline (registered at
        ``init``; unregistered keys keep the legacy per-key path)."""
        return key in self.plan.info

    def enqueue(self, key, raw, meta):
        """Stage one key's flattened payload; the bucket dispatches its
        fused collective the moment the last member arrives. A repeat
        push of a key whose bucket has not resolved yet first drains
        that bucket (legacy per-push semantics — every push is its own
        reduction round), which every rank hits at the same point."""
        with self._lock:
            bid = self.plan.info[key]["bucket"]
            st = self._staged.get(bid)
            if st is not None and key in st["vals"]:
                self.stats["drains"] += 1
                self._dispatch(bid)
                self._resolve_where(lambda inf: inf.bid == bid)
                st = None
            if st is None:
                st = self._staged[bid] = {"vals": {}, "meta": {},
                                          "t0": time.monotonic()}
            st["vals"][key] = raw
            st["meta"][key] = meta
            if len(st["vals"]) == len(self.plan.buckets[bid]["keys"]):
                self._dispatch(bid)

    # ---------------------------------------------------------- dispatch --
    def _dispatch(self, bid):
        st = self._staged.pop(bid, None)
        if st is None:
            return
        import jax.numpy as jnp

        bucket = self.plan.buckets[bid]
        keys = [k for k in bucket["keys"] if k in st["vals"]]
        partial = len(keys) < len(bucket["keys"])
        t_fuse = time.monotonic()
        parts = [st["vals"][k] for k in keys]
        fused = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        nbytes = int(fused.size) * fused.dtype.itemsize
        kv = self._kv
        mode = kv._bucket_mode()
        # the fingerprint entry every rank must agree on: bucket id +
        # member census + payload signature (registration-order keys)
        sig = (f"bucket{bid}:{len(keys)}keys:{int(fused.size)}:"
               f"{fused.dtype}" + ("?partial" if partial else ""))
        kv._note_bucket(mode, sig)
        future = kv._dispatch_bucket(fused, mode)
        self._seq += 1
        inf = _InFlight(bid, self._seq, keys, dict(st["meta"]), future,
                        mode, nbytes, partial, st["t0"], t_fuse,
                        time.monotonic())
        self._inflight.append(inf)
        self.stats["fused"] += 1
        self.stats["keys"] += len(keys)
        self.stats["bytes"] += nbytes
        if partial:
            self.stats["partial"] += 1
        self.stats["max_pending"] = max(self.stats["max_pending"],
                                        len(self._inflight))
        from ..telemetry import flight as _flight

        _flight.rec("kvstore.bucket.dispatch", "kvstore.sync",
                    f"bucket {bid} seq {inf.seq}: {len(keys)} keys, "
                    f"{nbytes}B, {mode}")

    # ----------------------------------------------------------- resolve --
    def resolve(self, key=None):
        """Resolve pending reductions: for `key`, the bucket holding it;
        for None (barrier / explicit flush), everything. Buckets still
        staged dispatch first, highest priority (latest-registered)
        first, so the flush order is a pure function of the plan."""
        with self._lock:
            if not self._staged and not self._inflight:
                return
            if key is not None and not self.wants(key):
                return
            for bid in sorted(self._staged, reverse=True):
                if key is None or bid == self.plan.info[key]["bucket"]:
                    self._dispatch(bid)
            if key is None:
                self._resolve_where(lambda inf: True)
            else:
                want = self.plan.info[key]["bucket"]
                self._resolve_where(lambda inf: inf.bid == want)

    def _resolve_where(self, pred):
        remaining = []
        for inf in self._inflight:  # FIFO = dispatch order
            if pred(inf):
                self._resolve_one(inf)
            else:
                remaining.append(inf)
        self._inflight = remaining

    def _resolve_one(self, inf):
        from .. import faults as _faults
        from .. import watchdog as _watchdog

        kv = self._kv
        t0 = time.monotonic()

        def _block():
            import jax

            # injectable: a 'kvstore.sync' hang == a peer stopped
            # reducing mid-bucket
            _faults.point("kvstore.sync")
            return jax.block_until_ready(inf.future)  # noqa: unbounded-sync — bounded by the enclosing watchdog.sync

        try:
            arr = _watchdog.sync(
                "kvstore.sync", _block,
                label=f"bucket {inf.bid} seq {inf.seq} "
                      f"({len(inf.keys)} keys, {inf.nbytes}B) rank "
                      f"{kv.rank}/{kv.num_workers}")
        except _watchdog.StallError as e:
            from .kvstore import PeerLostError

            err = PeerLostError("bucket_reduce", kv.rank, kv.num_workers,
                                e, census=self.describe())
            raise err from e
        now = time.monotonic()
        wait_ms = (now - t0) * 1e3
        window_ms = max((now - inf.t_dispatch) * 1e3, wait_ms)
        self.stats["resolved"] += 1
        self.stats["wait_ms"] += wait_ms
        self.stats["window_ms"] += window_ms
        off = 0
        for k in inf.keys:
            n = self.plan.info[k]["nelems"]
            kv._apply_reduced(k, arr[..., off:off + n], inf.mode,
                              inf.meta.get(k))
            off += n
        # only the BLOCKED tail is sync time in the step timeline — the
        # in-flight remainder overlapped compute (that is the headline)
        from ..telemetry import flight as _flight, steps as _tsteps

        _tsteps.phase("sync", wait_ms)
        _flight.rec("kvstore.bucket.resolve", "kvstore.sync",
                    f"bucket {inf.bid} seq {inf.seq}: waited "
                    f"{wait_ms:.2f}ms of {window_ms:.2f}ms in flight")
        self._trace(inf, t0, now, wait_ms)

    def _trace(self, inf, t_resolve, t_done, wait_ms):
        """Bucket lifecycle spans (enqueue→fuse→dispatch→resolve) for
        the PR 12 tracing plane — merged gang traces show the reduction
        window overlapping backward per rank."""
        from ..telemetry import trace as _trace

        if not _trace.enabled():
            return
        tid = f"kvbucket-{inf.bid}-{inf.seq}"
        lane = 300 + (inf.bid % 100)
        parent = _trace.commit(
            f"kvstore.bucket[{inf.bid}]", inf.t_stage0,
            (t_done - inf.t_stage0) * 1e3, kind="bucket", trace_id=tid,
            lane=lane,
            attrs={"keys": len(inf.keys), "bytes": inf.nbytes,
                   "mode": inf.mode, "partial": inf.partial,
                   "wait_ms": round(wait_ms, 3)})
        for name, a, b in (
                ("enqueue", inf.t_stage0, inf.t_fuse),
                ("fuse", inf.t_fuse, inf.t_dispatch),
                ("dispatch", inf.t_dispatch, t_resolve),
                ("resolve", t_resolve, t_done)):
            _trace.commit(name, a, max(0.0, (b - a) * 1e3), kind="phase",
                          trace_id=tid, parent=parent, lane=lane)

    # -------------------------------------------------------- inspection --
    @property
    def overlap_ratio(self):
        """1 - blocked/in-flight over the pipeline lifetime (1.0 = the
        collectives fully hid behind compute; None before any resolve)."""
        w = self.stats["window_ms"]
        if w <= 0.0:
            return None
        return round(max(0.0, 1.0 - self.stats["wait_ms"] / w), 4)

    def pending(self):
        # deliberately lock-free: the crash-bundle writer reads the
        # census from ANOTHER thread while the resolving thread may be
        # wedged inside watchdog.sync still holding the pipeline lock —
        # an advisory snapshot must never deadlock the post-mortem
        staged = dict(self._staged)
        return {"staged": {bid: len(st["vals"])
                           for bid, st in staged.items()},
                "inflight": len(self._inflight)}

    def describe(self):
        """JSON-able census (diagnose / crash bundles / PeerLostError).
        Lock-free by design — see :meth:`pending`."""
        return {"plan": self.plan.describe(),
                "pending": self.pending(),
                "stats": dict(self.stats),
                "overlap_ratio": self.overlap_ratio}


# ------------------------------------------------------- module-level views --

def census():
    """Per-pipeline censuses of every live bucket pipeline (crash
    bundles, tools/diagnose.py)."""
    return [p.describe() for p in list(_LIVE)]


def comm_stats():
    """Aggregate gradient-comms stats over live pipelines — the
    telemetry collector's source for ``mxtpu_kvstore_overlap_ratio`` and
    the fused-collective counters."""
    agg = {"fused": 0, "keys": 0, "bytes": 0, "partial": 0, "drains": 0,
           "resolved": 0, "wait_ms": 0.0, "window_ms": 0.0, "pending": 0,
           "max_pending": 0, "pipelines": 0}
    for p in list(_LIVE):
        st = p.stats
        agg["pipelines"] += 1
        for k in ("fused", "keys", "bytes", "partial", "drains",
                  "resolved", "wait_ms", "window_ms"):
            agg[k] += st[k]
        agg["max_pending"] = max(agg["max_pending"], st["max_pending"])
        agg["pending"] += p.pending()["inflight"]
    agg["wait_ms"] = round(agg["wait_ms"], 3)
    agg["window_ms"] = round(agg["window_ms"], 3)
    agg["overlap_ratio"] = (
        round(max(0.0, 1.0 - agg["wait_ms"] / agg["window_ms"]), 4)
        if agg["window_ms"] > 0 else None)
    return agg
