"""Span tracer with propagated context: per-request and per-step
timelines that survive across threads and — through the fleet shard
channel — across ranks.

The metrics registry answers "how much / how fast on average"; the
flight recorder answers "what was the process doing just before it
died". Neither answers "where did THIS request spend its 5ms?" or
"which rank is slow, and in which phase?". Spans do:

* **Serving requests** carry a request id from the HTTP front end (or a
  fresh one minted at ``submit``) through the batcher queue, the
  :class:`~mxnet_tpu.io.io.DeviceStager` h2d put, the compiled call and
  the response — producing a five-phase breakdown per request::

      queue_wait     submit -> popped by the batch collector
      batch_collect  coalescing + zero-padding into the bucket
      h2d            device staging of the padded batch
      compute        the compiled bucket execution (watchdog-spanned)
      respond        output slicing + future fulfilment

  The phases are exposed on the client handle
  (``ServingFuture.breakdown()``), in the HTTP response (``phases`` +
  ``request_id`` fields, ``X-Request-Id`` header echoed), and in
  ``tools/loadgen.py``'s per-phase percentile report.

* **Trainer steps**: ``ShardedTrainer.step`` runs inside one
  :func:`step` span keyed by ``(generation, rank, step)``; the pieces of
  host work in it (``trainer.put_batch`` ... ``trainer.bookkeeping``)
  are real :func:`span` children, measured where they happen, and the
  :mod:`~mxnet_tpu.telemetry.steps` phase split rides the parent as its
  ``phases`` attribute — the raw material of the fleet-level straggler
  verdict (:mod:`~mxnet_tpu.telemetry.fleet`).

* :func:`span` is the one way the program times a piece of host work:
  one pair of clock reads (``time.monotonic``, the clock of the flight
  recorder, the step records and the fleet shards) feeds the ring, the
  caller (``dur_ms``) and — through a ``jax.profiler.TraceAnnotation``
  entered beside them — the host plane of whatever profiler session is
  running, on the clock the device events are on. Spans nest through a
  per-thread stack and inherit the thread's propagated trace context
  (:func:`context`); :func:`carry`/:func:`adopt` hand both to a helper
  thread. A span recorded after the fact (:func:`commit`: request and
  bucket phases) has no live interval to annotate and stays in the ring
  only.

Committed spans live in a bounded ring (``MXNET_TPU_TRACE``, default
2048 spans; 0 turns the ring off, spans still time and annotate).
Overhead contract: telemetry off = one module-global check per hook; on,
the cost is per *request/step/batch*, never per op.

:func:`dump` folds spans, flight-recorder tails and (locally) the
profiler's chrome events into a ``trace.json`` that loads directly in
Perfetto / ``chrome://tracing`` — one lane (pid) per rank, clocks
aligned via the monotonic->wall offsets the telemetry shards carry.
``tools/traceview.py`` is the CLI over the multi-rank merge.
"""
from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
from collections import deque

from . import _state

__all__ = ["enabled", "configure", "size", "new_request_id", "coords",
           "context", "set_context", "get_context", "span", "commit",
           "carry", "adopt", "request_begin", "RequestTrace",
           "REQUEST_PHASES", "step", "step_span", "tail", "counts",
           "clear", "dump", "last_dump", "merged_events", "describe"]

#: the serving request phase vocabulary, in pipeline order
REQUEST_PHASES = ("queue_wait", "batch_collect", "h2d", "compute",
                  "respond")

try:
    _N = int(os.environ.get("MXNET_TPU_TRACE", "2048"))
except ValueError:
    _N = 2048
_N = max(0, _N)

_ring = deque(maxlen=(_N or 1))
_seq = itertools.count()
_ids = itertools.count(1)
_counts: dict = {}
_counts_lock = threading.Lock()
_tls = threading.local()
_last_dump = None


def enabled() -> bool:
    """True when spans are being recorded (telemetry on AND ring > 0).
    The one check every tracing hook performs before doing any work."""
    return _state.enabled and _N > 0


def configure(size):
    """Resize the span ring at runtime (0 disables tracing; the A/B
    perf-gate seam). Returns the previous size."""
    global _N, _ring
    prev = _N
    _N = max(0, int(size))
    _ring = deque(maxlen=(_N or 1))
    with _counts_lock:
        _counts.clear()
    return prev


def size():
    """Ring capacity (``MXNET_TPU_TRACE``; 0 = tracing disabled)."""
    return _N


def coords():
    """(rank, generation) gang coordinates of this process — 0/0 outside
    a supervised gang (``MXTPU_WORKER_ID`` / ``MXTPU_GANG_GENERATION``
    are exported by the supervisor / launcher)."""
    try:
        rank = int(os.environ.get("MXTPU_WORKER_ID", "0") or 0)
    except ValueError:
        rank = 0
    try:
        gen = int(os.environ.get("MXTPU_GANG_GENERATION", "0") or 0)
    except ValueError:
        gen = 0
    return rank, gen


def new_request_id():
    """A process-unique request id (pid-prefixed atomic counter —
    ``itertools.count`` is C-implemented and GIL-atomic, so concurrent
    submits can never collide)."""
    return f"{os.getpid():x}-{next(_ids):x}"


# ------------------------------------------------------- context plumbing --

def set_context(trace_id):
    """Bind `trace_id` as this thread's propagated trace context (spans
    and requests created on this thread inherit it). Returns the
    previous binding."""
    prev = getattr(_tls, "trace", None)
    _tls.trace = trace_id
    return prev


def get_context():
    """This thread's propagated trace id, or None."""
    return getattr(_tls, "trace", None)


class context:
    """``with trace.context(request_id): ...`` — scoped propagation (the
    HTTP front end wraps each handled request in one)."""

    def __init__(self, trace_id):
        self.trace_id = trace_id
        self._prev = None

    def __enter__(self):
        self._prev = set_context(self.trace_id)
        return self.trace_id

    def __exit__(self, *exc):
        _tls.trace = self._prev


# ------------------------------------------------------------- committing --

def commit(name, t0_mono, dur_ms, *, kind="span", trace_id=None,
           parent=None, lane=None, attrs=None):
    """Append one finished span to the ring (no-op when tracing is off).
    Returns the span id (None when off)."""
    if not enabled():
        return None
    sid = next(_seq)
    rec = {"seq": sid, "name": name, "kind": kind,
           "trace": trace_id if trace_id is not None else get_context(),
           "parent": parent,
           "t0": round(float(t0_mono), 6),
           "dur_ms": round(float(dur_ms), 4),
           "lane": int(lane) if lane is not None
           else (threading.get_ident() % 100000),
           "attrs": attrs or None}
    _append(rec)
    return sid


def _append(rec):
    _ring.append(rec)
    with _counts_lock:
        _counts[rec["kind"]] = _counts.get(rec["kind"], 0) + 1


_TraceAnnotation = None


def _annotation():
    """``jax.profiler.TraceAnnotation`` once jax is loaded (telemetry
    never imports it: a process without jax has no profiler session to
    annotate)."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return None
        _TraceAnnotation = jax.profiler.TraceAnnotation
    return _TraceAnnotation


class span:
    """Time a piece of host work: ``with trace.span("io.h2d") as sp: ...``.

    One pair of ``time.monotonic`` reads gives ``sp.dur_ms`` (what the
    caller feeds its :mod:`~mxnet_tpu.telemetry.steps` phase from) and
    the ring record; a ``jax.profiler.TraceAnnotation`` of the same name
    is entered beside them, so that under anybody's profiler session the
    span sits in the trace's host plane on the device events' clock (no
    session: an atomic flag check). Nesting is tracked per thread — an
    inner span's ``parent`` is the enclosing span's id, and both inherit
    the thread's trace context, which ``trace_id`` rebinds for the
    span's own extent. Telemetry off: nothing is read, ``dur_ms`` is 0.
    ``attrs`` ride the ring record and the annotation."""

    __slots__ = ("name", "kind", "attrs", "span_id", "dur_ms", "_t0",
                 "_ann", "_trace_id", "_lane", "_prev")

    def __init__(self, name, kind="span", trace_id=None, lane=None,
                 **attrs):
        self.name = name
        self.kind = kind
        self.attrs = attrs
        self.span_id = None
        self.dur_ms = 0.0
        self._t0 = None
        self._ann = None
        self._trace_id = trace_id
        self._lane = lane
        self._prev = None

    def __enter__(self):
        if not _state.enabled:
            return self
        ann = _annotation()
        if ann is not None:
            self._ann = ann(self.name, **self.attrs)
            self._ann.__enter__()
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        if _N > 0:
            # claim the id up front so children can reference it
            self.span_id = next(_seq)
        if self._lane is None and stack:
            self._lane = stack[-1]._lane   # drawn under its parent
        stack.append(self)
        if self._trace_id is not None:
            self._prev = set_context(self._trace_id)
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        t0 = self._t0
        if t0 is None:
            return
        self.dur_ms = (time.monotonic() - t0) * 1e3
        self._t0 = None
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        trace_id = get_context()
        if self._trace_id is not None:
            _tls.trace = self._prev
        stack = _tls.stack
        if stack and stack[-1] is self:
            stack.pop()
        if self.span_id is None or not enabled():
            return
        _append({"seq": self.span_id, "name": self.name,
                 "kind": self.kind, "trace": trace_id,
                 "parent": stack[-1].span_id if stack else None,
                 "t0": round(t0, 6), "dur_ms": round(self.dur_ms, 4),
                 "lane": self._lane if self._lane is not None
                 else threading.get_ident() % 100000,
                 "attrs": self.attrs or None})


def carry():
    """This thread's trace context and open spans, for :func:`adopt` on
    a thread that does the caller's work for it (the watchdog's bounded
    waiter)."""
    return get_context(), tuple(getattr(_tls, "stack", ()))


def adopt(carried):
    """Bind what :func:`carry` returned as this (fresh) thread's context
    and enclosing spans: spans opened here keep the caller's trace id
    and nest under the caller's open span."""
    _tls.trace, stack = carried
    _tls.stack = list(stack)


# -------------------------------------------------------- serving requests --

_lane = itertools.count()


class RequestTrace:
    """One serving request's propagated context: the batcher stamps
    monotonic marks as the request moves through the pipeline and
    :meth:`finish` turns them into the five-phase breakdown + committed
    spans. Marks are written by one thread at a time (submit thread ->
    collector -> runner), so no lock is needed."""

    __slots__ = ("request_id", "model", "rows", "marks", "breakdown",
                 "_lane")

    def __init__(self, request_id, model, rows=1):
        self.request_id = request_id
        self.model = model
        self.rows = rows
        self.marks = {"submit": time.monotonic()}
        self.breakdown = None
        self._lane = 1000 + next(_lane) % 256

    def mark(self, name, t=None):
        """Stamp pipeline mark `name` (submit / collected / assembled /
        staged / run_begin / run_end)."""
        self.marks[name] = time.monotonic() if t is None else t

    def _phase_bounds(self):
        m = self.marks
        return (("queue_wait", m.get("submit"), m.get("collected")),
                ("batch_collect", m.get("collected"), m.get("assembled")),
                ("h2d", m.get("assembled"), m.get("staged")),
                ("compute", m.get("run_begin"), m.get("run_end")),
                ("respond", m.get("run_end"), m.get("done")))

    def finish(self, error=None, bucket=None):
        """Close the request: compute the phase breakdown, commit the
        parent ``request`` span + one child span per measured phase."""
        self.mark("done")
        bd = {"request_id": self.request_id, "model": self.model,
              "rows": self.rows,
              "total_ms": round((self.marks["done"]
                                 - self.marks["submit"]) * 1e3, 4)}
        if error is not None:
            bd["error"] = str(error)
        if bucket is not None:
            bd["bucket"] = bucket
        for name, a, b in self._phase_bounds():
            bd[f"{name}_ms"] = round(max(0.0, (b - a) * 1e3), 4) \
                if (a is not None and b is not None) else None
        self.breakdown = bd
        if not enabled():
            return bd
        parent = commit(f"request[{self.model}]", self.marks["submit"],
                        bd["total_ms"], kind="request",
                        trace_id=self.request_id, lane=self._lane,
                        attrs={k: v for k, v in bd.items()
                               if k not in ("request_id", "model")})
        for name, a, b in self._phase_bounds():
            if a is None or b is None:
                continue
            commit(name, a, max(0.0, (b - a) * 1e3), kind="phase",
                   trace_id=self.request_id, parent=parent,
                   lane=self._lane)
        return bd


def request_begin(model, rows=1, request_id=None):
    """Open a :class:`RequestTrace` for one serving submit (None when
    tracing is off). The id is the thread's propagated context (the
    HTTP front end's ``X-Request-Id``) when bound, else freshly
    minted."""
    if not enabled():
        return None
    rid = request_id or get_context() or new_request_id()
    return RequestTrace(rid, model, rows=rows)


# ----------------------------------------------------------- trainer steps --

def _step_key(n):
    rank, gen = coords()
    return rank, gen, f"step-g{gen}-r{rank}-{n}", 500 + (rank % 100)


def step(n):
    """The span one trainer step runs in: ``trainer.step`` keyed
    ``(generation, rank, step)``, the trace id its children inherit."""
    rank, gen, trace_id, lane = _step_key(n)
    return span("trainer.step", kind="step", trace_id=trace_id,
                lane=lane, step=int(n), rank=rank, generation=gen)


def step_span(rec, t0_mono):
    """Hang the finished step record's phase split on the step's span
    (called by :func:`mxnet_tpu.telemetry.steps.end_step`, inside it):
    the span commits with it when the step returns, its real children
    are in the ring already. A record closed outside any :func:`step`
    span (a caller driving ``begin_step``/``end_step`` itself) commits a
    ``trainer.step`` of its own from the record's clock reads."""
    if not enabled():
        return
    for sp in reversed(getattr(_tls, "stack", ())):
        if sp.kind == "step":
            sp.attrs["phases"] = dict(rec["phases"])
            return
    rank, gen, trace_id, lane = _step_key(rec["step"])
    commit("trainer.step", t0_mono, rec["duration_ms"], kind="step",
           trace_id=trace_id, lane=lane,
           attrs={"step": rec["step"], "rank": rank, "generation": gen,
                  "phases": dict(rec["phases"])})


# ------------------------------------------------------------- inspection --

def tail(n=None):
    """The last `n` (default all retained) committed spans, oldest
    first, as JSON-able dicts."""
    items = list(_ring)
    if n is not None:
        items = items[-int(n):]
    return [dict(r) for r in items]


def counts():
    """Process-lifetime committed-span totals per kind."""
    with _counts_lock:
        return dict(_counts)


def clear():
    """Drop retained spans and counts (tests)."""
    _ring.clear()
    with _counts_lock:
        _counts.clear()


def describe():
    """Knobs + census (tools/diagnose.py "Tracing")."""
    return {"ring": _N, "enabled": enabled(), "spans": counts(),
            "retained": len(_ring), "last_dump": _last_dump}


def last_dump():
    """Path of the most recent :func:`dump` in this process, or None."""
    return _last_dump


# ------------------------------------------------------- chrome-trace dump --

def _span_event(rec, rank, offset, base_wall):
    ts = (rec["t0"] + offset - base_wall) * 1e6
    ev = {"name": rec["name"], "cat": f"trace.{rec['kind']}",
          "ph": "X", "pid": rank, "tid": rec.get("lane", 0),
          "ts": round(ts, 3), "dur": round(rec["dur_ms"] * 1e3, 3)}
    args = dict(rec.get("attrs") or {})
    if rec.get("trace"):
        args["trace"] = rec["trace"]
    if args:
        ev["args"] = args
    return ev


def _flight_event(rec, rank, offset, base_wall):
    ts = (rec["t_mono"] + offset - base_wall) * 1e6
    ev = {"name": rec["kind"], "cat": "flight", "ph": "i", "s": "p",
          "pid": rank, "tid": 0, "ts": round(ts, 3), "dur": 0}
    if rec.get("point") or rec.get("label") is not None:
        ev["args"] = {"point": rec.get("point"),
                      "label": rec.get("label")}
    return ev


def merged_events(shards):
    """Fold the rank shards' spans + flight tails into one list of
    chrome-trace events with per-rank lanes (``pid`` = rank) and clocks
    aligned via each shard's (t_wall, t_mono) heartbeat pair. Within a
    rank the alignment is a constant offset, so per-rank event order is
    preserved exactly (monotonicity test-asserted)."""
    return _merged(shards)[0]


def _merged(shards):
    lanes = []
    base_wall = None
    for rank in sorted(shards):
        sh = shards[rank]
        offset = float(sh["t_wall"]) - float(sh["t_mono"])
        spans = [r for r in sh.get("spans") or []
                 if isinstance(r, dict) and "t0" in r and "dur_ms" in r]
        flights = [r for r in sh.get("flight") or []
                   if isinstance(r, dict) and "t_mono" in r]
        for r in spans:
            wall = r["t0"] + offset
            base_wall = wall if base_wall is None else min(base_wall, wall)
        for r in flights:
            wall = r["t_mono"] + offset
            base_wall = wall if base_wall is None else min(base_wall, wall)
        lanes.append((rank, offset, spans, flights, sh))
    events = []
    if base_wall is None:
        base_wall = 0.0
    for rank, offset, spans, flights, sh in lanes:
        label = f"rank {rank}"
        if sh.get("generation"):
            label += f" (gen {sh['generation']})"
        events.append({"name": "process_name", "ph": "M", "pid": rank,
                       "tid": 0, "ts": 0, "dur": 0,
                       "cat": "__metadata", "args": {"name": label}})
        events.append({"name": "process_sort_index", "ph": "M",
                       "pid": rank, "tid": 0, "ts": 0, "dur": 0,
                       "cat": "__metadata", "args": {"sort_index": rank}})
        for r in spans:
            events.append(_span_event(r, rank, offset, base_wall))
        for r in flights:
            events.append(_flight_event(r, rank, offset, base_wall))
    return events, base_wall


def _local_shard():
    """This process's spans + flight tail shaped like a fleet shard (the
    single-process dump path)."""
    from . import flight as _flight

    rank, gen = coords()
    return rank, {"rank": rank, "generation": gen,
                  "t_wall": time.time(), "t_mono": time.monotonic(),
                  "spans": tail(), "flight": _flight.tail()}


def dump(path="trace.json", run_dir=None, include_profiler=True):
    """Write a merged Perfetto/chrome ``trace.json``.

    With ``run_dir`` (a gang run directory): fold EVERY rank's telemetry
    shard — spans, flight tails — into per-rank lanes, clock-aligned via
    the shards' heartbeat timestamps (torn/partial shards are skipped).
    Without it: this process's spans + flight tail, plus (when a
    profiler session recorded anything) the profiler's chrome events on
    the same timeline. Returns the written path."""
    global _last_dump
    if run_dir is not None:
        from . import fleet as _fleet

        shards = _fleet.read_shards(run_dir)
        rank, local = _local_shard()
        if local["spans"] and rank not in shards:
            shards[rank] = local
        events, _ = _merged(shards)
    else:
        rank, local = _local_shard()
        events, base_wall = _merged({rank: local})
        if include_profiler:
            offset = local["t_wall"] - local["t_mono"]
            events.extend(_profiler_events(rank, offset, base_wall))
    # profiler events recorded before the first span would land at a
    # negative timestamp; shift the whole timeline to start at 0
    neg = min((e["ts"] for e in events if e.get("ph") != "M"),
              default=0.0)
    if neg < 0:
        for e in events:
            if e.get("ph") != "M":
                e["ts"] = round(e["ts"] - neg, 3)
    payload = {"traceEvents": events, "displayTimeUnit": "ms"}
    with open(path, "w") as f:
        json.dump(payload, f)
    _last_dump = os.path.abspath(path)
    return _last_dump


def _profiler_events(rank, offset, base_wall):
    """The profiler's recorded chrome events, re-based onto this dump's
    timeline (profiler timestamps count from the ``time.monotonic``
    epoch its ``trace_info()`` carries)."""
    prof = sys.modules.get("mxnet_tpu.profiler")
    if prof is None or not hasattr(prof, "trace_info"):
        return []
    info = prof.trace_info()
    epoch_mono = info["epoch_mono"]
    out = []
    for ev in info["events"]:
        ev = dict(ev)
        wall = epoch_mono + ev["ts"] / 1e6 + offset
        ev["ts"] = round((wall - base_wall) * 1e6, 3)
        ev["pid"] = rank
        out.append(ev)
    return out
