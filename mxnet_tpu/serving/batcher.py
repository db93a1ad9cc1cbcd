"""Continuous/dynamic batching: per-model request queue → padded buckets.

One :class:`BucketBatcher` per served model, two daemon threads:

* the **collector** pops waiting requests, coalesces them into the
  nearest padded bucket under the ``max_wait_ms`` admission deadline
  (an underfull batch launches as soon as the oldest request has waited
  the window; a full bucket launches immediately), pads with zero rows,
  and **stages** the batch onto the device through the shared
  :class:`~mxnet_tpu.io.io.DeviceStager` (the PrefetchingIter
  device-put stage) — so h2d for batch N+1 overlaps the compiled call
  for batch N;
* the **runner** executes each staged batch under a
  ``watchdog.sync("serving.batch", ...)`` deadline with the
  ``serving.batch`` fault-injection point inside the span, slices the
  outputs back per request, and fulfills the futures.

Continuous: the collector never waits for the runner — requests arriving
while a batch executes coalesce into the next one, so batches grow with
load (high fill ratio under pressure, low latency when idle).

Admission control: ``submit`` fast-rejects with
:class:`~mxnet_tpu.serving.errors.ServerBusyError` the moment the
queue-depth bound is hit (429 semantics — shed load, don't queue
unboundedly) and with :class:`ServerDrainingError` once a drain started.

QoS + deadlines: requests carry a **priority class** (``interactive`` /
``batch``) and an optional **deadline**. The collector always drains
interactive requests first and lets batch traffic fill the leftover
bucket capacity, so under overload batch starves before interactive p99
degrades; the admission bound is likewise partitioned (batch rows count
against the whole queue bound, interactive admission ignores the batch
backlog). Deadline-carrying requests that *provably* cannot meet their
deadline are dropped with :class:`DeadlineExceeded` BEFORE consuming a
batch slot — at submit time when the measured batch-execution estimate
already overshoots, and again at collect time when the deadline expired
(or the estimate overshoots) while the request waited.

Prediction cache: with ``serving.config`` ``cache:1`` a
content-addressed :class:`~mxnet_tpu.serving.cache.PredictionCache`
(key = model name x served version x input bytes) sits in front of
admission — a hit fulfils the future on the submit thread without
touching the queue or the device, and content-identical requests whose
leader is already queued/in flight attach as **followers** fulfilled by
the leader's batch (so a duplicated request — a hedge landing on the
same worker, a retry — never double-runs a donating batch). Entries are
only inserted when the executing version matches the version the key
was built under, so a model-bus version flip can never serve stale
predictions: the old generation's keys simply stop being generated.

Tracing: when :mod:`mxnet_tpu.telemetry.trace` is on, every request
carries a :class:`~mxnet_tpu.telemetry.trace.RequestTrace` on its
future — the collector/runner stamp pipeline marks (popped, padded,
staged, compiled-call begin/end) and fulfilment commits the five-phase
queue_wait / batch_collect / h2d / compute / respond breakdown
(``ServingFuture.breakdown()``; docs/OBSERVABILITY.md "Tracing").

Robustness: a hung batch (wedged device, poisoned input) blows its
watchdog deadline → crash bundle + StallError; the batch's requests fail
with a :class:`RequestError` carrying the cause and the batcher KEEPS
SERVING the next batch. Nothing in this module blocks unboundedly —
every wait carries a timeout (the ``serving-blocking-call`` mxlint rule
gates this contract).
"""
from __future__ import annotations

import queue as _qmod
import threading
import time
from collections import deque

import numpy as _np

from . import config as _config
from . import cache as _pcache
from ..telemetry import trace as _trace
from .errors import (DeadlineExceeded, RequestError, RequestTimeout,
                     ServerBusyError, ServerDrainingError)
from .metrics import ModelMetrics

__all__ = ["ServingFuture", "BucketBatcher", "PRIORITIES"]

PRIORITIES = ("interactive", "batch")


class ServingFuture:
    """Client handle for one in-flight request. ``result`` is ALWAYS
    deadline-bounded: with no explicit timeout the configured
    ``timeout_ms`` default applies."""

    __slots__ = ("model", "t_submit", "t_done", "_event", "_result",
                 "_error", "_trace", "model_version", "priority",
                 "deadline_ms", "cache_hit")

    def __init__(self, model, priority="interactive", deadline_ms=None):
        self.model = model
        self.t_submit = time.monotonic()
        self.t_done = None
        self._event = threading.Event()
        self._result = None
        self._error = None
        self._trace = None
        # the model-bus version the answering batch executed under
        # (stamped at fulfilment; None until then / on failure)
        self.model_version = None
        self.priority = priority
        self.deadline_ms = deadline_ms
        self.cache_hit = False   # answered from the prediction cache

    def done(self):
        return self._event.is_set()

    def result(self, timeout=None):
        """The response (one numpy array, or a list for multi-output
        models), or raises the request's failure. Bounded: raises
        :class:`RequestTimeout` after ``timeout`` seconds (default: the
        configured ``timeout_ms``)."""
        if timeout is None:
            timeout = _config.effective()["timeout_ms"] / 1e3
        if not self._event.wait(timeout):
            raise RequestTimeout(
                f"request to {self.model!r} not answered within "
                f"{timeout:g}s")
        if self._error is not None:
            raise self._error
        return self._result

    def latency_ms(self):
        if self.t_done is None:
            return None
        return (self.t_done - self.t_submit) * 1e3

    @property
    def request_id(self):
        """The propagated trace/request id (None with tracing off)."""
        return self._trace.request_id if self._trace is not None else None

    def breakdown(self):
        """The five-phase per-request breakdown (queue_wait /
        batch_collect / h2d / compute / respond, milliseconds) once the
        request finished — None before completion or with tracing off."""
        return self._trace.breakdown if self._trace is not None else None

    def _fulfill(self, result):
        self.t_done = time.monotonic()
        self._result = result
        self._event.set()

    def _fail(self, error):
        self.t_done = time.monotonic()
        self._error = error
        self._event.set()


class _Request:
    __slots__ = ("arr", "n", "fut", "deadline", "key", "key_version",
                 "followers")

    def __init__(self, arr, n, fut, deadline=None, key=None,
                 key_version=None):
        self.arr = arr
        self.n = n
        self.fut = fut
        self.deadline = deadline       # absolute monotonic, or None
        self.key = key                 # prediction-cache content key
        self.key_version = key_version  # served version the key names
        self.followers = []            # deduped futures riding this one


class BucketBatcher:
    """The per-model queue + continuous-batching worker pair."""

    def __init__(self, model, metrics=None, max_queue=None,
                 max_wait_ms=None, stage=None, cache=None,
                 cache_entries=None):
        cfg = _config.effective()
        self.model = model
        self.metrics = metrics or ModelMetrics(model.name)
        self._max_queue = int(cfg["max_queue"] if max_queue is None
                              else max_queue)
        self._max_wait = (cfg["max_wait_ms"] if max_wait_ms is None
                          else float(max_wait_ms)) / 1e3
        self._qi = deque()       # interactive: always drained first
        self._qb = deque()       # batch: fills leftover bucket capacity
        self._rows = 0           # total rows waiting (the batch bound)
        self._rows_i = 0         # interactive rows waiting (its own bound)
        self._inflight = 0       # batches popped but not yet finished
        self._cond = threading.Condition()
        self._leaders = {}       # content key -> queued/in-flight _Request
        self._est_ms = None      # EWMA batch-execution estimate
        use_cache = cfg["cache"] if cache is None else bool(cache)
        self.cache = _pcache.PredictionCache(
            cfg["cache_entries"] if cache_entries is None
            else cache_entries) if use_cache else None
        self._staged = _qmod.Queue(maxsize=1)
        self._draining = False
        self._stopping = False
        self._threads = ()
        do_stage = cfg["stage"] if stage is None else bool(stage)
        self._stager = None
        if do_stage:
            import jax

            from ..io.io import DeviceStager

            self._stager = DeviceStager(device=jax.devices()[0])

    # ----------------------------------------------------------- control --
    def start(self):
        if self._threads:
            return self
        self._collector = threading.Thread(
            target=self._collect_loop, daemon=True,
            name=f"mxtpu-serve-{self.model.name}-collect")
        self._runner = threading.Thread(
            target=self._run_loop, daemon=True,
            name=f"mxtpu-serve-{self.model.name}-run")
        self._threads = (self._collector, self._runner)
        self._collector.start()
        self._runner.start()
        return self

    def queue_depth(self):
        """Rows waiting for a batch (the bound admission checks)."""
        return self._rows

    def ladder_census(self):
        """The bucket ladder with its observed batch counts and the
        model's dtypes — the int8-serving proof surface (diagnose's
        Quantization report, chaos phase 12): every ladder bucket that
        warmed must still be servable after a fault."""
        with self.metrics._lock:
            census = dict(sorted(self.metrics.bucket_census.items()))
        return {"buckets": list(self.model.buckets),
                "bucket_census": census,
                "dtype": self.model.dtype,
                "weight_dtype": self.model.weight_dtype}

    @property
    def draining(self):
        return self._draining

    def drain(self, timeout=30.0):
        """Stop admission, answer everything already admitted (queued AND
        in flight). Returns True when fully drained within `timeout`."""
        with self._cond:
            self._draining = True
            self._cond.notify_all()
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            with self._cond:
                if not self._qi and not self._qb and self._inflight == 0:
                    return True
            time.sleep(0.005)
        return False

    def stop(self, timeout=5.0):
        """Stop the worker threads; queued-but-unanswered requests fail
        with ServerDrainingError (call :meth:`drain` first for a graceful
        shutdown that answers them)."""
        with self._cond:
            self._stopping = True
            self._draining = True
            self._cond.notify_all()
        for t in self._threads:
            t.join(timeout=timeout)
        self._threads = ()
        with self._cond:
            leftovers = list(self._qi) + list(self._qb)
            self._qi.clear()
            self._qb.clear()
            self._rows = 0
            self._rows_i = 0
            self._leaders.clear()
        for r in leftovers:
            err = ServerDrainingError(self.model.name, "stopped")
            for fut in (r.fut, *r.followers):
                fut._fail(err)
                self.metrics.record_fail()

    # ------------------------------------------------------------ submit --
    def submit(self, arr, priority="interactive", deadline_ms=None):
        """Admit one request (fast-reject on a full queue, a draining
        server, or a provably unmeetable deadline) and return its
        :class:`ServingFuture`. ``priority`` picks the QoS class
        (interactive is drained first; batch fills leftover capacity and
        is the first to starve under overload); ``deadline_ms`` bounds
        how stale an answer is still useful — a request that cannot meet
        it is dropped before consuming a batch slot."""
        arr = self.model.validate(arr)
        if priority not in PRIORITIES:
            raise ValueError(f"unknown priority {priority!r}: expected "
                             f"one of {PRIORITIES}")
        n = arr.shape[0]
        deadline_ms = None if deadline_ms is None else float(deadline_ms)
        fut = ServingFuture(self.model.name, priority=priority,
                            deadline_ms=deadline_ms)
        if _trace.enabled():
            # propagated context: the HTTP front end binds X-Request-Id
            # on this thread; in-process callers get a fresh id
            fut._trace = _trace.request_begin(self.model.name, rows=n)
        deadline = (fut.t_submit + deadline_ms / 1e3
                    if deadline_ms is not None else None)
        key = key_version = None
        if self.cache is not None:
            key_version = self.model.version
            self.cache.observe_version(key_version)
            key = _pcache.content_key(self.model.name, key_version, arr)
            hit = self.cache.get(key)
            self.metrics.record_cache(hit is not None)
            if hit is not None:
                # hit path: fulfilled on the submit thread, no queue, no
                # device — this is the >=10x-faster-than-compute path
                self.metrics.record_submit()
                fut.cache_hit = True
                fut.model_version = key_version
                fut._fulfill(hit)
                if fut._trace is not None:
                    fut._trace.finish()
                self.metrics.record_complete(fut.latency_ms(), priority)
                if deadline_ms is not None:
                    self.metrics.record_deadline_outcome(True)
                return fut
        if deadline_ms is not None and self._est_ms is not None \
                and deadline_ms < self._est_ms:
            # provably doomed: even dispatched immediately, the measured
            # batch execution alone overshoots the deadline
            self.metrics.record_deadline_drop("submit")
            raise DeadlineExceeded(self.model.name, deadline_ms,
                                   self._est_ms, where="submit")
        with self._cond:
            if self._draining or self._stopping:
                self.metrics.record_reject()
                raise ServerDrainingError(self.model.name)
            if key is not None:
                leader = self._leaders.get(key)
                if leader is not None:
                    # content-identical request already queued/in flight:
                    # ride the donating batch instead of re-running it
                    leader.followers.append(fut)
                    self.metrics.record_coalesced()
                    self.metrics.record_submit()
                    return fut
            bound_rows = self._rows_i if priority == "interactive" \
                else self._rows
            if bound_rows + n > self._max_queue:
                self.metrics.record_reject()
                raise ServerBusyError(self.model.name, bound_rows,
                                      self._max_queue)
            req = _Request(arr, n, fut, deadline=deadline, key=key,
                           key_version=key_version)
            if priority == "interactive":
                self._qi.append(req)
                self._rows_i += n
            else:
                self._qb.append(req)
            self._rows += n
            if key is not None:
                self._leaders[key] = req
            self._cond.notify_all()
        self.metrics.record_submit()
        return fut

    # --------------------------------------------------------- collector --
    def _doomed(self, r, now):
        """True when `r` provably cannot meet its deadline: it already
        expired, or the measured batch-execution estimate overshoots the
        time it has left. Checked at pop time, BEFORE a batch slot."""
        if r.deadline is None:
            return False
        if now >= r.deadline:
            return True
        return (self._est_ms is not None
                and now + self._est_ms / 1e3 > r.deadline)

    def _drop_doomed_locked(self, r):
        """Fail one popped-but-doomed request (and its followers) with
        DeadlineExceeded — its rows were already uncounted by the pop,
        so no batch slot is consumed. _cond held."""
        if r.key is not None and self._leaders.get(r.key) is r:
            del self._leaders[r.key]
        err = DeadlineExceeded(self.model.name, r.fut.deadline_ms,
                               self._est_ms, where="queue")
        for fut in (r.fut, *r.followers):
            fut._fail(err)
            if fut._trace is not None:
                fut._trace.finish(error="DeadlineExceeded")
            self.metrics.record_deadline_drop("queue")

    def _collect(self):
        """Pop one coalesced batch (requests, rows) under the admission
        deadline, or None when stopping. Interactive requests pop first;
        batch traffic fills whatever bucket capacity is left — the
        starvation order the QoS contract promises."""
        with self._cond:
            while True:
                while not self._qi and not self._qb:
                    if self._stopping:
                        return None
                    self._cond.wait(timeout=0.1)
                cap = self.model.max_bucket
                head = self._qi[0] if self._qi else self._qb[0]
                deadline = head.fut.t_submit + self._max_wait
                while ((self._qi or self._qb) and self._rows < cap
                       and not self._stopping and not self._draining):
                    now = time.monotonic()
                    if now >= deadline:
                        break
                    self._cond.wait(timeout=min(deadline - now, 0.05))
                reqs, rows = [], 0
                now = time.monotonic()
                for q, interactive in ((self._qi, True), (self._qb, False)):
                    while q and rows + q[0].n <= cap:
                        r = q.popleft()
                        self._rows -= r.n
                        if interactive:
                            self._rows_i -= r.n
                        if self._doomed(r, now):
                            self._drop_doomed_locked(r)
                            continue
                        reqs.append(r)
                        rows += r.n
                if reqs:
                    break  # else: every pop was doomed (or stop() raced)
                if self._stopping and not self._qi and not self._qb:
                    return None
            self._inflight += 1
            t_pop = time.monotonic()
            for r in reqs:   # queue_wait ends here for the whole batch
                if r.fut._trace is not None:
                    r.fut._trace.mark("collected", t_pop)
            return reqs, rows

    def _pad(self, reqs, rows, bucket):
        shape = (bucket,) + self.model.example_shape
        out = _np.zeros(shape, dtype=self.model.dtype)
        off = 0
        for r in reqs:
            out[off:off + r.n] = r.arr
            off += r.n
        return out

    def _collect_loop(self):
        while True:
            batch = self._collect()
            if batch is None:
                return
            reqs, rows = batch
            bucket = self.model.bucket_for(rows)
            x = self._pad(reqs, rows, bucket)
            t_pad = time.monotonic()
            if self._stager is not None:
                # h2d on this thread overlaps the runner's compiled call;
                # a transfer the device refuses fails its batch (and only
                # it) instead of passing the host array on in silence
                try:
                    x = self._stager.put(x)
                except Exception as e:
                    self._fail_batch(reqs, RequestError(
                        f"model {self.model.name!r}: staging a batch of "
                        f"{rows} rows failed: {type(e).__name__}: {e}",
                        cause=e))
                    continue
            t_staged = time.monotonic()
            for r in reqs:   # batch_collect = pad; h2d = the staged put
                if r.fut._trace is not None:
                    r.fut._trace.mark("assembled", t_pad)
                    r.fut._trace.mark("staged", t_staged)
            while True:
                try:
                    self._staged.put((reqs, x, rows, bucket), timeout=0.25)
                    break
                except _qmod.Full:
                    if self._stopping:
                        self._fail_batch(reqs, ServerDrainingError(
                            self.model.name, "stopped"))
                        return

    # ------------------------------------------------------------ runner --
    def _retire_leaders(self, reqs):
        """Unregister each request's content key BEFORE fulfilment so no
        new follower can attach to a request whose followers list is
        being drained (attach happens under the same lock)."""
        with self._cond:
            for r in reqs:
                if r.key is not None and self._leaders.get(r.key) is r:
                    del self._leaders[r.key]

    def _fail_batch(self, reqs, err):
        self._retire_leaders(reqs)
        n = 0
        for r in reqs:
            for fut in (r.fut, *r.followers):
                fut._fail(err)
                if fut._trace is not None:
                    fut._trace.finish(error=type(err).__name__)
                n += 1
        self.metrics.record_fail(n)
        with self._cond:
            self._inflight -= 1
            self._cond.notify_all()

    def _run_loop(self):
        from .. import faults as _faults
        from .. import watchdog as _watchdog

        model = self.model
        while True:
            try:
                item = self._staged.get(timeout=0.25)
            except _qmod.Empty:
                if self._stopping and not self._collector.is_alive():
                    return
                continue
            reqs, x, rows, bucket = item

            def run():
                # 'serving.batch' injection: raise = failed batch, hang =
                # the wedged-device scenario the watchdog converts into a
                # crash bundle + StallError, preempt = SIGTERM mid-load
                _faults.point("serving.batch")
                return model.run_versioned(x, rows)

            t0 = time.monotonic()
            for r in reqs:
                if r.fut._trace is not None:
                    r.fut._trace.mark("run_begin", t0)
            try:
                outs, model_version = _watchdog.sync(
                    "serving.batch", run,
                    label=f"{model.name} bucket={bucket} rows={rows}")
            except BaseException as e:
                if isinstance(e, _watchdog.StallError):
                    self.metrics.record_stall()
                self._fail_batch(reqs, RequestError(
                    f"model {model.name!r}: batch of {rows} rows failed: "
                    f"{type(e).__name__}: {e}", cause=e))
                continue
            t_run_end = time.monotonic()
            dur_ms = (t_run_end - t0) * 1e3
            # EWMA execution estimate feeding deadline admission (the
            # "provably cannot meet" proof needs a measured floor)
            self._est_ms = dur_ms if self._est_ms is None \
                else 0.8 * self._est_ms + 0.2 * dur_ms
            self._retire_leaders(reqs)
            off = 0
            now = t_run_end
            for r in reqs:
                sliced = [o[off:off + r.n] for o in outs]
                value = sliced[0] if len(sliced) == 1 else sliced
                if r.fut._trace is not None:
                    r.fut._trace.mark("run_end", t_run_end)
                if self.cache is not None and r.key is not None \
                        and model_version == r.key_version:
                    # insert only when the executing version matches the
                    # version the key names — a flip mid-flight must
                    # never populate the new generation with old math
                    self.cache.put(r.key, value, model_version)
                for fut in (r.fut, *r.followers):
                    fut.model_version = model_version
                    fut._fulfill(value)
                    if fut._trace is not None:
                        fut._trace.finish(bucket=bucket)
                    self.metrics.record_complete(
                        (now - fut.t_submit) * 1e3, fut.priority)
                    if fut.deadline_ms is not None:
                        self.metrics.record_deadline_outcome(
                            (now - fut.t_submit) * 1e3 <= fut.deadline_ms)
                off += r.n
            self.metrics.record_batch(bucket, rows, dur_ms,
                                      self.queue_depth())
            with self._cond:
                self._inflight -= 1
                self._cond.notify_all()
