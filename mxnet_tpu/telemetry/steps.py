"""Per-step phase timeline: data-wait / h2d / host / compute / optimizer
/ sync.

One record per ``ShardedTrainer.step``. Every phase the trainer feeds is
the duration of a :func:`~mxnet_tpu.telemetry.trace.span` around the
work itself (one pair of clock reads, two views: the span in the ring
and the profiler's trace, the phase here):

    ``data_wait``  time the consumer blocked on the input pipeline
                   (PrefetchingIter's staged-batch join — reported by
                   ``io/io.py`` into the *next* step's record);
    ``h2d``        host-to-device placement of the batch
                   (``trainer.put_batch``; ~0 when the prefetcher
                   device-staged);
    ``host``       the trainer's own Python around the compiled call:
                   the two host scalars and the stream's current key
                   (``trainer.scalars``), gathering the handles' buffers
                   into its arguments (``trainer.gather``), and after it,
                   while the device runs, marking the donated buffers,
                   rebinding the handles and building the next step's
                   signature (``trainer.commit``) and letting go of the
                   donated inputs (``trainer.release``);
    ``compute``    getting the step's programs launched: the compiled
                   step call (``trainer.dispatch``), which returns once
                   the program is enqueued, and behind it the rng
                   stream's advance (``trainer.rng_key``: two small
                   device programs). The fused step runs
                   fwd+bwd+optimizer as ONE executable, so the optimizer
                   phase is folded in here;
    ``optimizer``  a separate optimizer executable's time (0 for the
                   fused ShardedTrainer step — present so the grammar is
                   stable across trainer styles);
    ``sync``       explicit post-step host reads (the nan-guard skip-flag
                   read, ``trainer.guard_sync``: it blocks until the
                   device has finished the step). With ``nan_guard=False``
                   dispatch is async and sync is 0 — wall-clock then
                   shows up in the NEXT step's phases.

``other`` is the record's duration less all of these: what has no span
(the fault and distcheck hooks, building the step on first use).

Each finished step publishes gauges (``mxtpu_step_time_ms``,
``mxtpu_step_phase_ms{phase}``), a duration histogram, a running step
counter, and — when the compile service captured ``cost_analysis()``
flops for the step executable — ``mxtpu_step_mfu_xla`` (measured flops ÷
the per-device-kind peak table), plus ``step.begin``/``step.end`` flight
events. ``ShardedTrainer.step_report()`` reads the same records.
"""
from __future__ import annotations

import threading
import time
from collections import deque

from . import _state, costs as _costs, flight as _flight
from . import registry as _registry

__all__ = ["PHASES", "begin_step", "phase", "end_step", "abort", "last",
           "history", "reset"]

PHASES = ("data_wait", "h2d", "host", "compute", "optimizer", "sync")

_lock = threading.Lock()
_HIST = deque(maxlen=256)
_cur = None
_pending: dict = {}   # phases measured before the step opened (data_wait)


def begin_step(step):
    """Open the record for `step` (folds in pending pre-step phases)."""
    global _cur
    if not _state.enabled:
        return
    phases = dict.fromkeys(PHASES, 0.0)
    with _lock:
        phases.update(_pending)
        _pending.clear()
    _cur = {"step": int(step), "t0": time.monotonic(), "phases": phases}
    _flight.rec("step.begin", "trainer.step", int(step))


def phase(name, ms):
    """Accrue `ms` into phase `name` of the open step — or, with no step
    open (the prefetcher measuring data-wait between steps), into the
    next one."""
    if not _state.enabled:
        return
    cur = _cur
    if cur is not None:
        cur["phases"][name] = cur["phases"].get(name, 0.0) + ms
    else:
        with _lock:
            _pending[name] = _pending.get(name, 0.0) + ms


def abort():
    """Discard the open record (the step raised — an injected fault, a
    drain request, a stall); its partial phases must not skew the
    timeline."""
    global _cur
    _cur = None


def end_step(flops=None, devices=1, device_kind=None):
    """Close the open record: total duration, phase splits, measured-MFU
    when `flops` (per-invocation, from ``cost_analysis``) is known.
    Publishes the step gauges and returns the record (None when no step
    is open)."""
    global _cur
    cur = _cur
    if cur is None:
        return None
    _cur = None
    dur_ms = (time.monotonic() - cur["t0"]) * 1e3
    rec = {"step": cur["step"], "duration_ms": round(dur_ms, 3),
           "phases": {k: round(v, 3) for k, v in cur["phases"].items()},
           "t_wall": time.time()}
    accounted = sum(cur["phases"].values())
    rec["phases"]["other"] = round(max(0.0, dur_ms - accounted), 3)
    if flops:
        rec["flops"] = flops
        try:
            mfu = _costs.mfu_xla(
                flops, 1e3 / dur_ms if dur_ms > 0 else 0.0,
                devices=devices, device_kind=device_kind)
        except LookupError:
            # no published peak for this device kind (the CPU backend):
            # the utilization is not measured, and the record says so by
            # leaving it out
            mfu = None
        if mfu is not None:
            rec["mfu_xla"] = round(mfu, 5)
    _HIST.append(rec)
    _registry.counter("mxtpu_train_steps_total",
                      "Trainer steps completed").inc()
    _registry.gauge("mxtpu_step_time_ms",
                    "Duration of the last trainer step").set(dur_ms)
    ph = _registry.gauge("mxtpu_step_phase_ms",
                         "Phase split of the last trainer step",
                         labels=("phase",))
    for k, v in rec["phases"].items():
        ph.set(v, k)
    _registry.histogram("mxtpu_step_time_ms_hist",
                        "Trainer step duration distribution").observe(
                            dur_ms)
    if rec.get("mfu_xla") is not None:
        _registry.gauge(
            "mxtpu_step_mfu_xla",
            "Measured-flops MFU of the last step (cost_analysis ÷ "
            "per-device-kind peak)").set(rec["mfu_xla"])
        _registry.gauge("mxtpu_step_flops",
                        "XLA-analyzed flops per step").set(flops)
    # the phase split rides the step's span, keyed (generation, rank,
    # step) — the raw material of the fleet straggler verdict and the
    # merged gang trace
    from . import trace as _trace

    _trace.step_span(rec, cur["t0"])
    _flight.rec("step.end", "trainer.step",
                f"step {rec['step']} {rec['duration_ms']}ms")
    from . import memory as _memory

    _memory.maybe_sample_step()
    return rec


def last():
    """The most recent finished step record, or None."""
    return dict(_HIST[-1]) if _HIST else None


def history(n=None):
    """The last `n` (default all retained) step records, oldest first."""
    items = list(_HIST)
    if n is not None:
        items = items[-int(n):]
    return [dict(r) for r in items]


def reset():
    """Drop records and pending phases (tests)."""
    global _cur
    with _lock:
        _pending.clear()
    _cur = None
    _HIST.clear()
