"""Profiler: chrome-trace dump + aggregate stats.

Parity target: `src/profiler/profiler.h:251-299` (chrome-trace JSON dump,
`Profiler::DumpProfile`), `src/profiler/aggregate_stats.cc` (console table)
and the Python surface `python/mxnet/profiler.py:32-150` (`set_config`,
`set_state`, `pause`/`resume`, `dump`, `dumps`) plus the instrumentation
objects (`Domain`, `Task`, `Frame`, `Event`, `Counter`, `Marker`).

TPU-native: host-side op dispatch events are recorded by the imperative
dispatch path (`ndarray._invoke`) and CachedOp executions; device-side
traces come from XLA via ``jax.profiler`` when ``profile_device=True`` is
passed to :func:`set_config` (written next to the chrome trace as
``<filename>.device/`` in TensorBoard format — the XLA analogue of the
reference's per-stream GPU events; the session runs without the Python
tracer, so it exports in seconds, and its host plane holds every
``telemetry.trace.span`` of the program on the device events' clock).
The chrome trace loads directly in ``chrome://tracing`` / Perfetto.
"""
from __future__ import annotations

import json
import os
import threading
import time

__all__ = ["set_config", "set_state", "state", "pause", "resume", "dump",
           "dumps", "reset", "trace_info", "Domain", "Task", "Frame",
           "Event", "Counter", "Marker", "scope", "record_skip_step",
           "record_stall", "record_cache", "record_compile",
           "record_serving"]

_lock = threading.Lock()
_RECORDING = False       # master flag: a session is active and not paused
_REC_IMPERATIVE = False  # fast-path flag read by ndarray._invoke
_REC_SYMBOLIC = False    # fast-path flag read by CachedOp
_session = False         # between set_state('run') and set_state('stop')
_paused = False
_config = {
    "filename": "profile.json",
    "profile_all": False,
    "profile_symbolic": True,
    "profile_imperative": True,
    "profile_memory": False,
    "profile_api": False,
    "aggregate_stats": False,
    "continuous_dump": False,
    "dump_period": 1.0,
    "profile_device": False,
    "profile_process": "worker",
}
_events = []  # chrome trace events
_aggregate = {}  # name -> [count, total_us, min_us, max_us]
_epoch = time.monotonic()  # the clock of telemetry's spans and records
_device_trace_active = False


def _now_us():
    return (time.monotonic() - _epoch) * 1e6


def _refresh():
    """Recompute the per-category fast-path flags."""
    global _REC_IMPERATIVE, _REC_SYMBOLIC
    _REC_IMPERATIVE = _RECORDING and _config["profile_imperative"]
    _REC_SYMBOLIC = _RECORDING and _config["profile_symbolic"]


def set_config(**kwargs):
    """Configure the profiler (parity: profiler.py:32 set_config)."""
    unknown = set(kwargs) - set(_config)
    if unknown:
        raise ValueError(f"unknown profiler config keys: {sorted(unknown)}")
    _config.update(kwargs)
    if _config.get("profile_all"):
        for k in ("profile_symbolic", "profile_imperative", "profile_memory",
                  "profile_api", "aggregate_stats"):
            _config[k] = True
    _refresh()


def set_state(state="stop", profile_process="worker"):
    """Start ('run') or stop ('stop') profiling (parity: set_state)."""
    global _RECORDING, _paused, _session, _device_trace_active
    if state not in ("run", "stop"):
        raise ValueError("state must be 'run' or 'stop'")
    if state == "run":
        if not _session:
            _session = True
            if _config["profile_device"]:
                try:
                    import jax

                    # no Python tracer: one event per Python call swamps
                    # the trace (3 s of traffic took 6 minutes to export,
                    # PERF.md) and slows the host threads it measures
                    options = jax.profiler.ProfileOptions()
                    options.python_tracer_level = 0
                    jax.profiler.start_trace(
                        _config["filename"] + ".device",
                        profiler_options=options)
                    _device_trace_active = True
                except Exception:
                    _device_trace_active = False
        _RECORDING = True
        _paused = False
    else:
        if _session and _device_trace_active:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception:
                pass
            _device_trace_active = False
        _session = False
        _RECORDING = False
        _paused = False
    _refresh()


def state():
    return "run" if _RECORDING else "stop"


def pause(profile_process="worker"):
    """Temporarily stop recording without ending the session."""
    global _RECORDING, _paused
    if _session and _RECORDING:
        _RECORDING = False
        _paused = True
        _refresh()


def resume(profile_process="worker"):
    global _RECORDING, _paused
    if _session and _paused:
        _RECORDING = True
        _paused = False
        _refresh()


def reset():
    """Drop all recorded events and aggregate stats."""
    with _lock:
        _events.clear()
        _aggregate.clear()


def record_event(name, start_us, dur_us, cat="operator", tid=None,
                 args=None):
    """Append one complete ('X') chrome-trace event + aggregate stats.

    The hot-path entry used by ndarray._invoke / CachedOp (parity:
    profiler.h:251 ProfileOperator events on the engine workers)."""
    ev = {"name": name, "cat": cat, "ph": "X", "pid": os.getpid(),
          "tid": tid if tid is not None else threading.get_ident(),
          "ts": start_us, "dur": dur_us}
    if args:
        ev["args"] = args
    with _lock:
        _events.append(ev)
        agg = _aggregate.get(name)
        if agg is None:
            _aggregate[name] = [1, dur_us, dur_us, dur_us]
        else:
            agg[0] += 1
            agg[1] += dur_us
            agg[2] = min(agg[2], dur_us)
            agg[3] = max(agg[3], dur_us)


def record_bulk_segment(start_us, dur_us, op_names):
    """One complete event per flushed bulk segment (engine bulking,
    mxnet_tpu.bulk): op count + fused op list ride in args so traces show
    what each fused XLA executable contains — the observability the
    reference loses when ops merge into one engine job is kept here."""
    record_event(f"BulkSegment[{len(op_names)}]", start_us, dur_us,
                 cat="bulk",
                 args={"op_count": len(op_names),
                       "ops": ",".join(op_names)})


def record_skip_step(total, consecutive):
    """NaN/Inf-guarded optimizer step skipped (ShardedTrainer nan_guard):
    an instant marker at the skip plus a counter track of the running
    total, so diverging runs are visible in the trace. No-op unless a
    profiling session is recording."""
    if not _RECORDING:
        return
    record_instant("trainer.skip_step", cat="trainer",
                   args={"total": total, "consecutive": consecutive})
    record_counter("trainer.skipped_steps", total)


_stall_count = 0


def record_stall(point, elapsed_s, bundle):
    """Watchdog stall: an instrumented point blew its deadline and a crash
    bundle was written (mxnet_tpu.watchdog). Recorded as an instant marker
    plus a running counter track so hangs line up with the op timeline in
    the trace. No-op unless a profiling session is recording."""
    global _stall_count
    _stall_count += 1
    if not _RECORDING:
        return
    record_instant("watchdog.stall", cat="watchdog",
                   args={"point": point, "elapsed_s": round(elapsed_s, 3),
                         "bundle": bundle})
    record_counter("watchdog.stalls", _stall_count)


def record_cache(kind, hits, misses):
    """Dispatch/compile cache-hit/miss counter tracks (fed by
    ``analysis.distcheck.cache_event`` — per-op jit dispatch, bulk
    fused-segment, and CachedOp signature caches). Two counter tracks per
    cache family so hit ratio and recompile churn line up with the op
    timeline in the trace. No-op unless a session is recording (the
    caller checks ``_RECORDING`` first to stay off the dispatch hot
    path)."""
    record_counter(f"compile_cache.{kind}.hits", hits)
    record_counter(f"compile_cache.{kind}.misses", misses)


def record_compile(site, dur_ms, source, hits, misses):
    """One compile-service miss resolution (mxnet_tpu.compile): a complete
    event spanning the compile/disk-load ('compile' | 'disk' | 'warmup')
    plus the per-site hit/miss counter tracks, all under the existing
    ``compile_cache.*`` family so service traffic lines up with the
    dispatch/bulk/cachedop cache tracks in the trace. No-op unless a
    profiling session is recording."""
    if not _RECORDING:
        return
    now = _now_us()
    record_event(f"compile[{site}]", now - dur_ms * 1e3, dur_ms * 1e3,
                 cat="compile", args={"source": source})
    record_cache(f"service.{site}", hits, misses)


def record_serving(model, bucket, rows, dur_ms, queue_depth):
    """One served batch (mxnet_tpu.serving): a complete event spanning
    the compiled bucket execution plus queue-depth / batch-rows counter
    tracks, so serving latency and backlog line up with the compile-cache
    and dispatch tracks in the trace. No-op unless a profiling session is
    recording."""
    if not _RECORDING:
        return
    now = _now_us()
    record_event(f"serving[{model}]", now - dur_ms * 1e3, dur_ms * 1e3,
                 cat="serving",
                 args={"bucket": bucket, "rows": rows})
    record_counter(f"serving.{model}.queue_depth", queue_depth)
    record_counter(f"serving.{model}.batch_rows", rows)


def record_instant(name, cat="instant", args=None):
    # dur: 0 — instants/counters are durationless in the chrome-trace
    # model, but downstream consumers (and the subsystem tests) treat
    # ts/dur/ph as the universal event envelope; viewers ignore it
    ev = {"name": name, "cat": cat, "ph": "i", "pid": os.getpid(),
          "tid": threading.get_ident(), "ts": _now_us(), "dur": 0,
          "s": "p"}
    if args:
        ev["args"] = args
    with _lock:
        _events.append(ev)


def record_counter(name, value):
    with _lock:
        _events.append({"name": name, "cat": "counter", "ph": "C",
                        "pid": os.getpid(), "tid": 0, "ts": _now_us(),
                        "dur": 0, "args": {name: value}})


def trace_info():
    """The recorded chrome events plus the ``time.monotonic`` instant
    their timestamps count from — the clock of the span ring, the step
    records and the flight recorder, so ``telemetry.trace.dump()`` can
    re-base profiler events onto that timeline."""
    with _lock:
        return {"epoch_mono": _epoch, "events": list(_events)}


def dump(finished=True, profile_process="worker"):
    """Write the chrome-trace JSON to `filename` (parity: MXDumpProfile /
    Profiler::DumpProfile, profiler.h:266)."""
    with _lock:
        payload = {"traceEvents": list(_events), "displayTimeUnit": "ms"}
    with open(_config["filename"], "w") as f:
        json.dump(payload, f)
    if finished:
        set_state("stop")


def dumps(reset=False, format="table", sort_by="total", ascending=False):
    """Return aggregate statistics as a console table (parity:
    MXAggregateProfileStatsPrint, aggregate_stats.cc)."""
    with _lock:
        rows = [(name, c, tot / 1e3, mn / 1e3, mx / 1e3, tot / c / 1e3)
                for name, (c, tot, mn, mx) in _aggregate.items()]
    key = {"total": 2, "count": 1, "min": 3, "max": 4, "avg": 5,
           "name": 0}[sort_by]
    rows.sort(key=lambda r: r[key], reverse=not ascending)
    lines = ["Profile Statistics:",
             f"{'Name':<40s} {'Count':>8s} {'Total(ms)':>12s} "
             f"{'Min(ms)':>10s} {'Max(ms)':>10s} {'Avg(ms)':>10s}"]
    for name, c, tot, mn, mx, avg in rows:
        lines.append(f"{name[:40]:<40s} {c:>8d} {tot:>12.3f} {mn:>10.3f} "
                     f"{mx:>10.3f} {avg:>10.3f}")
    if reset:
        globals()["reset"]()
    return "\n".join(lines)


# ------------------------------------------------- instrumentation objects --

class Domain:
    """Named profiling domain (parity: profiler.py Domain)."""

    def __init__(self, name):
        self.name = name

    def new_task(self, name):
        return Task(self, name)

    def new_frame(self, name):
        return Frame(self, name)

    def new_counter(self, name, value=None):
        c = Counter(self, name)
        if value is not None:
            c.set_value(value)
        return c

    def new_marker(self, name):
        return Marker(self, name)

    def __str__(self):
        return self.name


class _Span:
    """start()/stop() span recorded as one complete event."""

    _cat = "task"

    def __init__(self, domain, name):
        self.domain = domain
        self.name = name
        self._start = None

    def start(self):
        self._start = _now_us()

    def stop(self):
        if self._start is None:
            return
        if _RECORDING:
            record_event(self.name, self._start, _now_us() - self._start,
                         cat=f"{self.domain}:{self._cat}")
        self._start = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()

    def __str__(self):
        return self.name


class Task(_Span):
    _cat = "task"


class Frame(_Span):
    _cat = "frame"


class Event(_Span):
    """Standalone event (no domain; parity: profiler.py Event)."""

    _cat = "event"

    def __init__(self, name):
        super().__init__("event", name)


class Counter:
    """Monotonic counter rendered as a chrome counter track."""

    def __init__(self, domain, name, value=None):
        self.domain = domain
        self.name = name
        self._value = 0
        if value is not None:
            self.set_value(value)

    def set_value(self, value):
        self._value = value
        if _RECORDING:
            record_counter(self.name, value)

    def increment(self, delta=1):
        self.set_value(self._value + delta)

    def decrement(self, delta=1):
        self.set_value(self._value - delta)

    def __iadd__(self, delta):
        self.increment(delta)
        return self

    def __isub__(self, delta):
        self.decrement(delta)
        return self


class Marker:
    """Instant marker (parity: profiler.py Marker.mark)."""

    def __init__(self, domain, name):
        self.domain = domain
        self.name = name

    def mark(self, scope="process"):
        if _RECORDING:
            record_instant(self.name, cat=f"{self.domain}:marker")


class scope:
    """Context manager tagging ops with a name scope (used by tests and
    gluon name scopes; minimal parity with profiler scope in the
    reference's imperative API)."""

    _current = ""

    def __init__(self, name):
        self.name = name
        self._prev = None

    def __enter__(self):
        self._prev = scope._current
        scope._current = self.name
        return self

    def __exit__(self, *exc):
        scope._current = self._prev
