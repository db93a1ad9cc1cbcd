"""Watchdog: hang detection, deadline-bounded syncs, crash-bundle dumps.

PR 2 (faults/checkpoint) made *crashes* survivable; this module covers the
other half of production failures — *hangs*: a stuck collective, a wedged
data fetch, a host sync that never returns. Large-scale TPU trainers run a
dead-man's switch for exactly these wedges; here it spans every layer of
this library that can block:

    ``engine.flush``   engine.wait_all barrier / BulkSegment.run (bulk.py)
    ``host.sync``      NDArray.wait_to_read / waitall block_until_ready
    ``trainer.step``   the whole compiled ShardedTrainer.step call
    ``io.fetch``       PrefetchingIter background-fetch join (io/io.py)
    ``kvstore.sync``   cross-host kvstore barrier / all-reduce
                       (kvstore/kvstore.py) — a deadline here surfaces as a
                       structured PeerLostError naming the lost gang
    ``kvstore.push`` / ``kvstore.pull``   liveness heartbeats only (the
                       aggregation itself is eager NDArray math; deadlines
                       apply to the blocking spans above)
    ``serving.batch``  one in-flight predict-server batch (serving/
                       batcher.py) — a wedged batch becomes a crash
                       bundle + StallError; the batch's requests fail
                       typed and the server keeps serving

Three cooperating pieces:

* **Heartbeat registry** — every instrumented point reports liveness
  (:func:`beat`) with a label and a monotonic timestamp into a bounded
  ring; the last N beats ship in every crash bundle, so a hang report
  shows what the process was doing *before* it wedged.
* **Monitor daemon** — a background thread that scans the table of open
  spans (blocking regions in flight) and walks the escalation ladder for
  any span past its per-point deadline:

      1. log a warning (at ``warn`` x deadline, default 0.5),
      2. write a **crash bundle** (all-thread tracebacks via faulthandler,
         last-N heartbeats, sanitizer sync-site history, live bulk-segment
         state, fault-injection and profiler counters) to the crash dir,
      3. surface the stall per the configured ``action``.

* **Deadline-bounded syncs** — :func:`sync` runs a blocking callable with
  a deadline. Under ``action:raise`` (default) or ``action:abort`` the
  callable runs in a joinable daemon *waiter* thread and the calling
  thread waits with a bound, so no library sync point can block
  unboundedly: at the deadline the caller writes the bundle (if the
  monitor hasn't already) and raises a catchable :class:`StallError` — or,
  as the configurable last resort, attempts a final checkpoint through the
  hook installed with :func:`set_last_resort` (e.g. a
  ``CheckpointManager``-backed trainer save) and aborts the process.
  Under ``action:observe`` the callable runs inline in the caller (zero
  thread churn — the CI default) and only the monitor escalates: a wedged
  test still produces a bundle before pytest's faulthandler fires, but
  nothing is interrupted.

Configuration mirrors ``MXNET_TPU_FAULTS``: the ``MXNET_TPU_WATCHDOG``
environment variable (read once, at first use, so subprocesses inherit) or
:func:`configure`. Grammar — entries separated by ``,`` or ``;``::

    <point>:<deadline-seconds>      per-point deadline (e.g. trainer.step:120)
    *:<deadline-seconds>            default deadline for every spanned point
    action:<raise|abort|observe>    escalation terminal (default raise)
    warn:<fraction>                 warn at fraction x deadline (default 0.5)
    interval:<seconds>              monitor poll period (default: adaptive)
    dir:<path>                      crash-bundle directory (default
                                    $MXNET_TPU_CRASH_DIR or ./mxtpu_crash)
    beats:<N>                       heartbeat ring size (default 256)

Examples::

    MXNET_TPU_WATCHDOG="trainer.step:120,io.fetch:30"
    MXNET_TPU_WATCHDOG="*:540,action:observe"          # the CI setting
    watchdog.configure({"engine.flush": 15}, action="abort")

The watchdog is **off by default** and costs one module-global ``is None``
check per sync point when disabled. Every path is deterministically
testable via the ``hang`` mode of :mod:`mxnet_tpu.faults`.
"""
from __future__ import annotations

import json
import os
import re
import tempfile
import threading
import time

from . import log as _log
from .telemetry import flight as _flight
from .telemetry import trace as _trace

__all__ = ["StallError", "configure", "configure_from_env", "enabled",
           "sync", "beat", "heartbeats", "set_last_resort", "last_resort",
           "crash_dir", "latest_bundle", "describe", "ABORT_EXIT_CODE"]

ABORT_EXIT_CODE = 86  # distinct from the interpreter's 1 and SIGKILL's 137

_logger = _log.get_logger("mxnet_tpu.watchdog")

_ACTIONS = ("raise", "abort", "observe")


class StallError(RuntimeError):
    """A watchdog-bounded sync point exceeded its deadline.

    Attributes: ``point``, ``label``, ``elapsed``, ``deadline`` (seconds)
    and ``bundle`` (crash-bundle directory path, or None if writing it
    failed). Catchable — a caller that knows how to recover (drop the
    batch, rebuild the iterator, re-queue the step) can do so; anything
    else should treat it like the crash it almost was.
    """

    def __init__(self, point, label, elapsed, deadline, bundle):
        self.point = point
        self.label = label
        self.elapsed = elapsed
        self.deadline = deadline
        self.bundle = bundle
        super().__init__(
            f"watchdog: {point!r}"
            + (f" ({label})" if label else "")
            + f" stalled for {elapsed:.1f}s (deadline {deadline:g}s)"
            + (f"; crash bundle: {bundle}" if bundle else ""))


class _Config:
    __slots__ = ("deadlines", "default", "action", "warn_fraction",
                 "interval", "crash_dir", "beats", "spec")

    def __init__(self):
        self.deadlines = {}     # point -> seconds
        self.default = None     # '*' entry: deadline for unlisted points
        self.action = "raise"
        self.warn_fraction = 0.5
        self.interval = None    # None = adaptive (min deadline / 4)
        self.crash_dir = None   # None = env/default resolution at write
        self.beats = 256
        self.spec = ""

    def deadline_for(self, point):
        d = self.deadlines.get(point)
        return self.default if d is None else d


class _Span:
    """One blocking region in flight, visible to the monitor."""

    __slots__ = ("point", "label", "start", "deadline", "thread",
                 "warned", "bundle", "bundled", "bundle_ready", "stalled")

    def __init__(self, point, label, deadline):
        self.point = point
        self.label = label
        self.start = time.monotonic()
        self.deadline = deadline
        self.thread = threading.current_thread().name
        self.warned = False
        self.bundle = None
        self.bundled = False                   # claimed by a writer
        self.bundle_ready = threading.Event()  # writer finished
        self.stalled = threading.Event()


_lock = threading.Lock()
_CFG: _Config | None = None
_loaded_env = False
_spans: dict[int, _Span] = {}
_span_seq = 0
_bundle_seq = 0
_beats = None          # deque, sized by config
_monitor_gen = 0       # bumping it retires the running monitor thread
_last_resort = None    # callable: final checkpoint attempt before abort
_exit_fn = os._exit    # test seam for the abort path


# ----------------------------------------------------------- configuration --

def _parse(spec):
    cfg = _Config()
    cfg.spec = spec
    for entry in re.split(r"[;,]", spec):
        entry = entry.strip()
        if not entry:
            continue
        key, sep, val = entry.partition(":")
        key, val = key.strip(), val.strip()
        if not sep or not val:
            raise ValueError(
                f"bad MXNET_TPU_WATCHDOG entry {entry!r}: expected "
                "<point>:<seconds> or <option>:<value>")
        if key == "action":
            if val not in _ACTIONS:
                raise ValueError(f"unknown watchdog action {val!r}; "
                                 f"expected one of {_ACTIONS}")
            cfg.action = val
        elif key == "warn":
            cfg.warn_fraction = float(val)
        elif key == "interval":
            cfg.interval = float(val)
        elif key == "dir":
            cfg.crash_dir = val
        elif key == "beats":
            cfg.beats = int(val)
        elif key == "*":
            cfg.default = float(val)
        else:
            cfg.deadlines[key] = float(val)
    if cfg.default is None and not cfg.deadlines:
        raise ValueError(
            f"MXNET_TPU_WATCHDOG spec {spec!r} configures no deadline; "
            "add '<point>:<seconds>' or '*:<seconds>' entries")
    return cfg


def configure(spec=None, **options):
    """Install a watchdog configuration (replacing any previous one).

    spec : str in the grammar above, dict ``{point: seconds}``, or None
        to disable the watchdog entirely.
    options : ``action=``, ``warn=``, ``interval=``, ``crash_dir=``,
        ``default=``, ``beats=`` keyword overrides applied on top.
    """
    global _CFG, _loaded_env, _beats, _monitor_gen
    if isinstance(spec, dict):
        spec = ",".join(f"{k}:{v}" for k, v in spec.items())
    cfg = _parse(spec) if spec else None
    if cfg is None and options:
        cfg = _Config()
        cfg.spec = "<programmatic>"
    if cfg is not None:
        for k, attr in (("action", "action"), ("warn", "warn_fraction"),
                        ("interval", "interval"), ("crash_dir", "crash_dir"),
                        ("default", "default"), ("beats", "beats")):
            if k in options:
                setattr(cfg, attr, options.pop(k))
        if options:
            raise TypeError(f"unknown watchdog options: {sorted(options)}")
        if cfg.action not in _ACTIONS:
            raise ValueError(f"unknown watchdog action {cfg.action!r}")
        if cfg.default is None and not cfg.deadlines:
            raise ValueError("watchdog configured with no deadline")
    from collections import deque

    with _lock:
        _loaded_env = True  # explicit configure overrides the env
        _CFG = cfg
        _monitor_gen += 1
        if cfg is not None:
            _beats = deque(_beats or (), maxlen=cfg.beats)
            _start_monitor(_monitor_gen)


def configure_from_env(force=True):
    """(Re-)read ``MXNET_TPU_WATCHDOG`` — used by tests to restore the
    ambient configuration after exercising explicit ones."""
    global _loaded_env
    if force:
        _loaded_env = False
    _ensure_env()


def _ensure_env():
    global _loaded_env
    if _loaded_env:
        return
    with _lock:
        if _loaded_env:
            return
        _loaded_env = True
    env = os.environ.get("MXNET_TPU_WATCHDOG", "")
    if env:
        try:
            configure(env)
        except ValueError as e:
            _logger.warning("ignoring invalid MXNET_TPU_WATCHDOG: %s", e)
            configure(None)


def enabled() -> bool:
    """True when a configuration with deadlines is installed."""
    _ensure_env()
    return _CFG is not None


def describe():
    """Effective configuration as a plain dict (diagnose.py, bundles)."""
    _ensure_env()
    cfg = _CFG
    if cfg is None:
        return {"enabled": False}
    return {"enabled": True, "spec": cfg.spec, "deadlines": dict(cfg.deadlines),
            "default_deadline": cfg.default, "action": cfg.action,
            "warn_fraction": cfg.warn_fraction, "interval": cfg.interval,
            "crash_dir": crash_dir(), "beats": cfg.beats}


def set_last_resort(fn):
    """Install the final-checkpoint hook run by ``action:abort`` after the
    bundle is written — typically ``lambda: trainer.save_checkpoint(
    manager, epoch)``. The SAME hook serves the graceful preemption drain
    (:func:`mxnet_tpu.preempt.drain`); ``ShardedTrainer.save_checkpoint``/
    ``resume`` register one automatically. Returns the previous hook.
    Pass None to clear."""
    global _last_resort
    prev, _last_resort = _last_resort, fn
    return prev


def last_resort():
    """The currently installed final-checkpoint hook (or None). Shared
    plumbing between ``action:abort`` and the preemption drain."""
    return _last_resort


# -------------------------------------------------------------- heartbeats --

def beat(point, label=None):
    """Report liveness at a named progress point (cheap; no-op when the
    watchdog is disabled). Thread-safe: deque.append is atomic."""
    if _CFG is None:
        return
    beats = _beats
    if beats is not None:
        beats.append({"t_mono": time.monotonic(), "t_wall": time.time(),
                      "point": point, "label": label,
                      "thread": threading.current_thread().name})


def heartbeats():
    """Snapshot of the last-N heartbeat records (newest last)."""
    beats = _beats
    return list(beats) if beats is not None else []


# ------------------------------------------------------------ crash bundle --

def crash_dir():
    """The effective crash-bundle directory (not created until needed)."""
    cfg = _CFG
    if cfg is not None and cfg.crash_dir:
        return cfg.crash_dir
    return os.environ.get("MXNET_TPU_CRASH_DIR") \
        or os.path.join(tempfile.gettempdir(), "mxtpu_crash")


def latest_bundle(directory=None):
    """Newest crash-bundle directory under `directory` (default: the
    effective crash dir), or None."""
    directory = directory or crash_dir()
    try:
        cands = [os.path.join(directory, n) for n in os.listdir(directory)
                 if n.startswith("bundle-")]
    except OSError:
        return None
    cands = [c for c in cands if os.path.isdir(c)]
    return max(cands, key=os.path.getmtime) if cands else None


def _active_spans_snapshot():
    now = time.monotonic()
    with _lock:
        spans = list(_spans.values())
    return [{"point": s.point, "label": s.label, "thread": s.thread,
             "elapsed_s": round(now - s.start, 3), "deadline_s": s.deadline}
            for s in spans]


def _write_bundle(span):
    """Write one crash bundle for `span`; idempotent per span (first
    writer — monitor or bounded caller — wins, the loser waits for the
    winner's path). Returns the bundle dir or None when writing failed
    (the stall is still surfaced)."""
    global _bundle_seq
    with _lock:
        if span.bundled:
            claimed = False
        else:
            span.bundled = True
            claimed = True
            _bundle_seq += 1
            seq = _bundle_seq
    if not claimed:
        span.bundle_ready.wait(timeout=15)
        return span.bundle
    try:
        root = crash_dir()
        stamp = time.strftime("%Y%m%d-%H%M%S")
        name = f"bundle-{stamp}-p{os.getpid()}-{seq}-" \
               + span.point.replace(".", "_")
        path = os.path.join(root, name)
        os.makedirs(path, exist_ok=True)
        _dump_tracebacks(os.path.join(path, "threads.txt"))
        with open(os.path.join(path, "heartbeats.json"), "w") as f:
            json.dump(heartbeats(), f, indent=1)
        with open(os.path.join(path, "report.json"), "w") as f:
            json.dump(_report(span), f, indent=1, default=repr)
        with open(os.path.join(path, "sanitize.json"), "w") as f:
            json.dump(_sanitizer_history(), f, indent=1)
        # the always-on flight recorder: the last-N event timeline (step
        # boundaries, syncs, compile misses, serving traffic) ships in
        # EVERY bundle, so the post-mortem does not depend on the
        # profiler having been running when the process wedged
        _flight.rec("watchdog.stall", span.point, span.label)
        with open(os.path.join(path, "flight.json"), "w") as f:
            json.dump(_flight.tail(), f, indent=1, default=repr)
        # the lock witness (analysis/concur pass 4), when armed: the
        # last-N lock acquisitions + any order inversion it saw — a
        # stall that is really a deadlock names both locks right here
        try:
            from .analysis import concur as _concur

            with open(os.path.join(path, "witness.json"), "w") as f:
                json.dump({"state": _concur.witness_state(),
                           "tail": _concur.witness_tail()},
                          f, indent=1, default=repr)
        except Exception:
            pass
        span.bundle = path
        _logger.error("watchdog: %r (%s) stalled %.1fs >= deadline %gs; "
                      "crash bundle written to %s", span.point,
                      span.label or "-", time.monotonic() - span.start,
                      span.deadline, path)
        try:
            from . import profiler as _profiler

            _profiler.record_stall(span.point,
                                   time.monotonic() - span.start, path)
        except Exception:
            pass
        return path
    except Exception as e:
        _logger.error("watchdog: failed to write crash bundle for %r: %s",
                      span.point, e)
        return None
    finally:
        span.bundle_ready.set()


def _dump_tracebacks(path):
    import faulthandler

    with open(path, "w") as f:
        f.write(f"# all-thread tracebacks, pid {os.getpid()}, "
                f"{time.strftime('%Y-%m-%d %H:%M:%S')}\n")
        f.flush()
        faulthandler.dump_traceback(file=f, all_threads=True)


def _report(span):
    from . import faults as _faults

    report = {
        "point": span.point,
        "label": span.label,
        "thread": span.thread,
        "elapsed_s": round(time.monotonic() - span.start, 3),
        "deadline_s": span.deadline,
        "time": time.strftime("%Y-%m-%d %H:%M:%S"),
        "pid": os.getpid(),
        "config": describe(),
        "active_spans": _active_spans_snapshot(),
        "faults": {k: {"invocations": c, "fires": fi}
                   for k, (c, fi) in _faults.stats().items()},
    }
    try:
        from . import bulk as _bulk

        report["live_bulk_segments"] = _bulk.live_segments()
    except Exception as e:
        report["live_bulk_segments"] = f"<unavailable: {e}>"
    try:
        from . import profiler as _profiler

        report["profiler"] = _profiler.dumps()
    except Exception as e:
        report["profiler"] = f"<unavailable: {e}>"
    try:
        # device-memory forensics: live/peak per device + the top-K
        # resident executables by XLA memory_analysis — the OOM half of
        # a stall post-mortem (a wedge is often an allocator death spiral)
        from .telemetry import memory as _tele_memory

        report["memory"] = _tele_memory.oom_report()
    except Exception as e:
        report["memory"] = f"<unavailable: {e}>"
    try:
        # gradient-comms forensics: which fused bucket reductions were
        # staged/in flight when the sync wedged (sys.modules-gated — a
        # process that never ran a dist kvstore reports nothing)
        import sys as _sys

        bmod = _sys.modules.get("mxnet_tpu.kvstore.buckets")
        if bmod is not None:
            report["kvstore_buckets"] = bmod.census()
    except Exception as e:
        report["kvstore_buckets"] = f"<unavailable: {e}>"
    return report


def _sanitizer_history():
    try:
        from .analysis import sanitize as _sanitize

        return [{"kind": e.kind, "site": e.site, "pending": e.pending,
                 "hazard": e.hazard, "message": e.message}
                for e in _sanitize.events()]
    except Exception:
        return []


# ----------------------------------------------------------------- monitor --

def _start_monitor(gen):
    t = threading.Thread(target=_monitor_loop, args=(gen,),
                         name="mxtpu-watchdog-monitor", daemon=True)
    t.start()


def _monitor_interval(cfg):
    if cfg.interval is not None:
        return max(0.02, cfg.interval)
    ds = list(cfg.deadlines.values())
    if cfg.default is not None:
        ds.append(cfg.default)
    return min(5.0, max(0.05, min(ds) / 4.0))


def _monitor_loop(gen):
    """Scan open spans; walk the warn -> bundle ladder for overdue ones.
    One thread per configure() generation; a newer configure retires it."""
    while True:
        cfg = _CFG
        if cfg is None or gen != _monitor_gen:
            return
        try:
            now = time.monotonic()
            with _lock:
                spans = list(_spans.values())
            for s in spans:
                elapsed = now - s.start
                if not s.warned and elapsed >= s.deadline * cfg.warn_fraction:
                    s.warned = True
                    _flight.rec("watchdog.warn", s.point, s.label)
                    _logger.warning(
                        "watchdog: %r (%s) has been blocking for %.1fs "
                        "(deadline %gs)", s.point, s.label or "-", elapsed,
                        s.deadline)
                if elapsed >= s.deadline:
                    if not s.bundled:
                        _write_bundle(s)
                    s.stalled.set()
        except Exception as e:  # the monitor must never die
            _logger.error("watchdog monitor error: %s", e)
        time.sleep(_monitor_interval(cfg))


# ----------------------------------------------------- deadline-bounded sync --

_tls = threading.local()


def _register(point, label, deadline):
    global _span_seq
    span = _Span(point, label, deadline)
    with _lock:
        _span_seq += 1
        key = _span_seq
        _spans[key] = span
    return key, span


def _unregister(key):
    with _lock:
        _spans.pop(key, None)


def _abort(span):
    """Last-resort terminal: attempt a final checkpoint, then abort."""
    hook = _last_resort
    if hook is not None:
        try:
            _logger.error("watchdog: attempting last-resort checkpoint "
                          "before abort")
            hook()
        except Exception as e:
            _logger.error("watchdog: last-resort checkpoint failed: %s", e)
    _logger.error("watchdog: aborting (exit %d) after stall at %r",
                  ABORT_EXIT_CODE, span.point)
    _exit_fn(ABORT_EXIT_CODE)


def sync(point, fn, label=None):
    """Run blocking `fn()` under the watchdog contract for `point`.

    Disabled, or no deadline configured for `point`: calls `fn` inline —
    the only cost is one global check and a dict lookup.

    ``action:observe``: `fn` runs inline inside a registered span; the
    monitor warns and writes a bundle if it overruns, nothing raises.

    ``action:raise`` / ``action:abort``: `fn` runs in a daemon waiter
    thread and this (calling) thread waits at most the deadline, so the
    caller can never block unboundedly. On completion `fn`'s result or
    exception propagates unchanged. On deadline: crash bundle, then
    :class:`StallError` (raise) or final-checkpoint + process abort
    (abort). The abandoned waiter keeps running as a daemon — its later
    result is discarded, exactly like a wedge that eventually unwedges
    after the job gave up on it.
    """
    # always-on flight breadcrumb: every spanned blocking point (syncs,
    # collectives, batches) lands in the post-mortem ring even when no
    # watchdog deadline is configured
    _flight.rec("sync", point, label)
    cfg = _CFG
    if cfg is None:
        if _loaded_env:
            return fn()
        _ensure_env()
        cfg = _CFG
        if cfg is None:
            return fn()
    deadline = cfg.deadline_for(point)
    if deadline is None or getattr(_tls, "in_sync", False):
        # nested syncs (e.g. a host read inside a bounded trainer step)
        # run inline: the outer span already bounds them
        return fn()
    key, span = _register(point, label, deadline)
    beat(point, f"begin {label or point}")
    try:
        if cfg.action == "observe":
            return fn()
        return _bounded(cfg, span, fn)
    finally:
        _unregister(key)
        beat(point, f"end {label or point}")


def _bounded(cfg, span, fn):
    box = {}
    done = threading.Event()
    carried = _trace.carry()

    def runner():
        _tls.in_sync = True  # inherit-suppress: the waiter IS the span
        # the caller's trace id and open spans: what fn() times nests
        # under the caller's span, as if it ran inline
        _trace.adopt(carried)
        try:
            box["value"] = fn()
        except BaseException as e:
            box["error"] = e
        finally:
            done.set()

    waiter = threading.Thread(
        target=runner, daemon=True,
        name=f"mxtpu-waiter-{span.point}")
    waiter.start()
    end = span.start + span.deadline
    warn_at = span.start + span.deadline * cfg.warn_fraction
    while True:
        now = time.monotonic()
        if now >= end:
            break
        nxt = end if span.warned else min(end, warn_at)
        if done.wait(timeout=max(0.005, min(nxt - now, 0.25))):
            if "error" in box:
                raise box["error"]
            return box["value"]
        if not span.warned and time.monotonic() >= warn_at:
            span.warned = True
            _logger.warning(
                "watchdog: %r (%s) has been blocking for %.1fs "
                "(deadline %gs)", span.point, span.label or "-",
                time.monotonic() - span.start, span.deadline)
    if done.is_set():  # finished exactly on the boundary: not a stall
        if "error" in box:
            raise box["error"]
        return box["value"]
    # deadline exceeded: escalate (the monitor may already have bundled)
    bundle = _write_bundle(span)
    span.stalled.set()
    if cfg.action == "abort":
        _abort(span)
    raise StallError(span.point, span.label,
                     time.monotonic() - span.start, span.deadline,
                     bundle or span.bundle)
