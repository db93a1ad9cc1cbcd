#!/usr/bin/env python
"""loadgen — closed + open-loop load generator for the serving stack.

The acceptance harness for ROADMAP item 1 ("a load-test harness
demonstrating sustained thousands of requests/s with bounded tail
latency"): builds a small multi-model container in-process (or targets a
running HTTP front end), drives it for a fixed duration, and reports
sustained requests/s, client-side p50/p95/p99 latency, admission
rejects, the server's batch fill ratio — and whether ANY recompile
happened during the run (after warmup the compile service must show
only cache hits). When span tracing is on (the default), the report
also carries ``phase_breakdown``: p50/p99/mean per request phase
(queue_wait / batch_collect / h2d / compute / respond) from the serving
span tracer — cross-checked against ``serving.stats()`` percentiles in
the test suite.

Modes
-----
closed   N worker threads, each submit → wait → repeat (throughput finds
         the natural concurrency-limited operating point).
open     a scheduler thread injects requests at a fixed --rate
         regardless of completions (the tail-latency-under-offered-load
         view); completions are collected by a waiter pool.

Targets
-------
default      in-process ModelServer over --models small MLPs
--via-http   same server, but driven through the JSON/HTTP front end
             (socket path exercised end to end)
--url URL    an already-running external front end
--workers N  multi-process mode: an N-worker ``ServingFleet`` (one
             ModelServer process per worker behind the router front
             door) driven closed-loop over HTTP — the 1→N rps scaling
             measurement (run it at workers=1 and workers=4)
--dtype D    model-pair mode: ONE embedding-lookup fixture served as
             fp32 and as its entropy-calibrated int8 twin from the same
             warm ladder; ``--dtype both`` drives each variant with the
             identical closed loop and prints the matched-p99
             int8-vs-float rps ratio as one JSON line (the ROADMAP
             item-4 acceptance measurement)

Every HTTP path drives **persistent keep-alive connections** (one
``http.client`` connection per worker thread, reconnect on error):
per-request TCP connects would dominate router-path measurements and
understate rps. Connect time is measured separately from request time
and reported as ``connects`` / ``reconnects`` / ``connect_ms_mean``
alongside the request-latency percentiles.

QoS knobs (every target): ``--priority-mix 4:1`` stamps
interactive/batch priority classes in that ratio and splits the report
per class; ``--deadline-ms`` stamps per-request deadlines (admission
drops are counted per class, never as errors); ``--hot-key-frac``
re-sends ONE hot (model, input) pair for that fraction of requests,
driving the prediction cache (``cache_hit_ratio`` in the report). Fleet
mode additionally reports hedge outcomes + straggler flags from the
router.

Examples::

    JAX_PLATFORMS=cpu python tools/loadgen.py --duration 30
    python tools/loadgen.py --mode open --rate 2000 --duration 10
    python tools/loadgen.py --via-http --duration 5
    python tools/loadgen.py --workers 4 --duration 10
    python tools/loadgen.py --workers 2 --priority-mix 4:1 \
        --deadline-ms 50 --hot-key-frac 0.3 --duration 10

The last stdout line is one JSON report.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# ------------------------------------------------------------ demo models --

def build_demo_container(models=2, dim=16, classes=10, hidden=32, seed=0,
                         buckets=None):
    """N distinct small MLPs — enough weight diversity that responses
    differ per model, small enough that CPU serves thousands of rps."""
    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.gluon import nn

    container = serving.ModelContainer()
    for i in range(models):
        mx.random.seed(seed + i * 101)
        net = nn.HybridSequential()
        net.add(nn.Dense(hidden + 8 * i, activation="relu"),
                nn.Dense(classes))
        net.initialize(mx.init.Xavier())
        net(mx.nd.zeros((2, dim)))
        container.add_block(f"model{i}", net, example_shape=(dim,),
                            buckets=buckets)
    return container


def _percentiles(lats):
    from mxnet_tpu.serving.metrics import percentile

    return {k: (round(percentile(lats, q), 3)
                if percentile(lats, q) is not None else None)
            for q, k in ((50, "p50_ms"), (95, "p95_ms"), (99, "p99_ms"))}


# ------------------------------------------------- int8-vs-float pair mode --

def build_pair_container(vocab=50_000, embed_dim=512, seq_len=1024,
                         seed=0, calib_mode="entropy",
                         calib_examples=64, buckets=None,
                         granularity="channel-wise"):
    """The int8-vs-float fixture: ONE embedding-lookup model served
    twice — as fp32 and as its ``contrib.quantization`` int8 twin — in a
    single container/ladder.

    The model is an embedding-lookup service (request: a bag of ids;
    response: the table rows) — the feature-store / two-tower-retrieval
    serving pattern, and the workload where int8 pays on EVERY backend:
    the table gather is memory-bandwidth-bound and int8 storage moves
    and ships 4x fewer bytes (the int8 variant responds with the int8
    rows; the per-tensor dequantize scale is a static model constant,
    reported in the pair meta, that clients apply lazily — the
    weights-only serving contract). On the MXU quantized conv/dense
    additionally run at 2x the bf16 rate; this CPU jaxlib scalarizes
    every int8 elementwise/GEMM kernel, so compute-bound fixtures
    cannot show the serving win there (docs/PERFORMANCE.md "Int8
    inference" walks the whole story).

    The int8 twin comes out of the full quantize_model pipeline
    (entropy calibration included); its serving graph is the quantized
    graph's int8 gather output — ``internals["<name>_output0"]`` —
    i.e. the rows BEFORE the dequantize that a pooled classifier would
    fuse downstream.
    """
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.contrib import quantization as quant

    rng = np.random.RandomState(seed)
    data = mx.sym.var("data")
    sym = mx.sym.Embedding(data, input_dim=vocab, output_dim=embed_dim,
                           name="pair_embed")
    args = {
        "pair_embed_weight": mx.nd.array(
            (rng.randn(vocab, embed_dim) * 0.05).astype(np.float32)),
    }
    calib = rng.randint(0, vocab, (calib_examples, seq_len)) \
        .astype(np.float32)
    it = mx.io.NDArrayIter(calib, batch_size=32, label_name=None)
    qfull, qargs, _ = quant.quantize_model(
        sym, args, {}, data_names=("data",), calib_data=it,
        calib_mode=calib_mode, quantize_granularity=granularity)
    # serve the int8 rows themselves (output 0 of the quantized gather)
    qsym = qfull.get_internals()["pair_embed_output0"]
    scale = float(qargs["pair_embed_weight_max"].asnumpy()[0]) / 127.0
    container = serving.ModelContainer()
    container.add_symbol("emblookup_float32", sym, args,
                         example_shape=(seq_len,), buckets=buckets)
    container.add_symbol("emblookup_int8", qsym, qargs,
                         example_shape=(seq_len,), buckets=buckets)
    meta = {"vocab": vocab, "embed_dim": embed_dim, "seq_len": seq_len,
            "calib_mode": calib_mode, "granularity": granularity,
            "seed": seed, "int8_dequantize_scale": round(scale, 9)}
    return container, meta


def _drive_closed(server, names, pool, duration, concurrency):
    """One closed-loop drive (the run_inproc worker loop, reusable per
    variant): returns (sorted latencies ms, completed, rejected, errors,
    elapsed seconds)."""
    from mxnet_tpu import serving

    lock = threading.Lock()
    lats, completed, rejected, errors = [], [0], [0], []
    stop_at = time.perf_counter() + duration

    def worker(tid):
        i = 0
        while time.perf_counter() < stop_at:
            name = names[(tid + i) % len(names)]
            x = pool[(tid * 7 + i) % len(pool)]
            t0 = time.perf_counter()
            try:
                server.submit(name, x).result(10.0)
                with lock:
                    lats.append((time.perf_counter() - t0) * 1e3)
                    completed[0] += 1
            except serving.ServerBusyError:
                with lock:
                    rejected[0] += 1
                time.sleep(0.001)
            except Exception as e:
                with lock:
                    errors.append(f"{type(e).__name__}: {e}")
                if len(errors) > 100:
                    return
            i += 1

    threads = [threading.Thread(target=worker, args=(t,), daemon=True)
               for t in range(concurrency)]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=duration + 30.0)
    return sorted(lats), completed[0], rejected[0], errors, \
        time.perf_counter() - t_start


def run_pair(duration=20.0, concurrency=16, vocab=50_000, embed_dim=512,
             seq_len=1024, seed=0, calib_mode="entropy", warmup=True,
             variants=("float32", "int8"), buckets=None, max_wait_ms=0.5):
    """Drive the float and int8 variants of the SAME model through one
    warm server, sequentially, with the identical closed-loop harness —
    the int8-vs-float acceptance measurement. Returns one JSON-able
    report with per-variant rps/percentiles, the rps ratio, whether the
    p99s matched (int8 must not buy throughput with a worse tail), the
    int8 ladder's bucket census and ``recompiles_during_run`` (must be 0
    on a warm server — the int8 ladder compiles/loads at warmup, never
    under traffic)."""
    import numpy as np

    from mxnet_tpu import compile as _compile
    from mxnet_tpu import serving

    container, meta = build_pair_container(
        vocab=vocab, embed_dim=embed_dim, seq_len=seq_len, seed=seed,
        calib_mode=calib_mode, buckets=buckets)
    # a tight admission window: the A/B measures the MODEL, not the
    # collector's idle batching wait (under the saturating closed loop
    # batches fill and launch immediately anyway)
    server = serving.ModelServer(container, max_wait_ms=max_wait_ms).start()
    if warmup:
        server.warmup()
    pre_misses = _compile.stats().get("serving", {}).get("misses", 0)
    pool = [np.random.RandomState(seed + i)
            .randint(0, vocab, (1, seq_len)).astype(np.float32)
            for i in range(64)]
    per_variant = duration / max(len(variants), 1)
    sides = {}
    for variant in variants:
        name = f"emblookup_{variant}"
        lats, completed, rejected, errors, elapsed = _drive_closed(
            server, [name], pool, per_variant, concurrency)
        side = {"completed": completed, "rejected": rejected,
                "errors": len(errors), "first_errors": errors[:3],
                "duration_s": round(elapsed, 2),
                "rps": round(completed / elapsed, 1) if elapsed else 0.0}
        side.update(_percentiles(lats))
        sides[variant] = side
    post_misses = _compile.stats().get("serving", {}).get("misses", 0)
    stats = server.stats()["models"]
    report = {
        "harness": "loadgen-pair",
        "model": "emblookup",
        "mode": "closed",
        "concurrency": concurrency,
        "variants": sides,
        "recompiles_during_run": post_misses - pre_misses,
        "weight_dtype_int8": stats.get("emblookup_int8", {})
        .get("weight_dtype"),
        "bucket_census_int8": stats.get("emblookup_int8", {})
        .get("bucket_census"),
        **meta,
    }
    f32, i8 = sides.get("float32"), sides.get("int8")
    if f32 and i8 and f32["rps"]:
        report["rps_float32"] = f32["rps"]
        report["rps_int8"] = i8["rps"]
        report["rps_ratio_int8_vs_float"] = round(i8["rps"] / f32["rps"], 3)
        report["p99_float32_ms"] = f32.get("p99_ms")
        report["p99_int8_ms"] = i8.get("p99_ms")
        # matched p99: the int8 rps win must come at an equal-or-better
        # tail, not by trading latency for throughput
        report["matched_p99"] = bool(
            f32.get("p99_ms") and i8.get("p99_ms")
            and i8["p99_ms"] <= f32["p99_ms"] * 1.05)
    server.drain(timeout=10.0)
    return report


# ----------------------------------------------------------- QoS harness --

def parse_priority_mix(spec):
    """``'4:1'`` -> 0.8, the interactive fraction of an
    interactive:batch traffic mix (None passes through: single-class
    traffic, no per-class report)."""
    if spec is None:
        return None
    try:
        i, b = (float(t) for t in str(spec).split(":"))
    except ValueError:
        raise ValueError(f"bad --priority-mix {spec!r}: expected "
                         "interactive:batch weights, e.g. 4:1")
    if i < 0 or b < 0 or i + b <= 0:
        raise ValueError(f"bad --priority-mix {spec!r}: weights must be "
                         ">= 0 and not both zero")
    return i / (i + b)


class _QoSPlan:
    """Per-request deterministic QoS decisions for a load worker: which
    priority class (from the interactive fraction), whether to reuse the
    ONE hot input (driving prediction-cache hits), and the deadline to
    stamp. Pure arithmetic on (tid, i) so runs reproduce."""

    def __init__(self, priority_mix=None, hot_key_frac=0.0,
                 deadline_ms=None):
        self.frac = parse_priority_mix(priority_mix)
        self.hot = min(max(float(hot_key_frac or 0.0), 0.0), 1.0)
        self.deadline_ms = deadline_ms
        self.active = (self.frac is not None or self.hot > 0.0
                       or deadline_ms is not None)

    def klass(self, tid, i):
        if self.frac is None:
            return "interactive"
        return "interactive" \
            if ((tid * 7919 + i) % 1000) < self.frac * 1000 else "batch"

    def hot_key(self, tid, i):
        return self.hot > 0.0 \
            and ((tid * 104729 + i * 31) % 1000) < self.hot * 1000

    def body_fields(self, tid, i):
        """The extra JSON request fields for this (tid, i) request:
        ``{}`` when every knob is off (byte-identical legacy bodies)."""
        out = {}
        if self.frac is not None:
            out["priority"] = self.klass(tid, i)
        if self.deadline_ms is not None:
            out["deadline_ms"] = self.deadline_ms
        return out


class _QoSAgg:
    """Per-class latency/drop/cache accounting folded into the report:
    ``by_class`` per-class p50/p99 + deadline drops, plus the flat
    cache-hit and deadline-miss counters."""

    def __init__(self, lock):
        self._lock = lock
        self.lats = {}        # class -> [ms]
        self.dropped = {}     # class -> deadline drops (504 dropped)
        self.cache_hits = 0

    def record(self, klass, ms=None, dropped=False, cache_hit=False):
        with self._lock:
            if dropped:
                self.dropped[klass] = self.dropped.get(klass, 0) + 1
            elif ms is not None:
                self.lats.setdefault(klass, []).append(ms)
            if cache_hit:
                self.cache_hits += 1

    def fold(self, report, plan):
        with self._lock:
            completed = sum(len(v) for v in self.lats.values())
            report["deadline_dropped"] = sum(self.dropped.values())
            report["cache_hits"] = self.cache_hits
            report["cache_hit_ratio"] = (
                round(self.cache_hits / completed, 4) if completed
                else None)
            if plan.frac is not None:
                report["by_class"] = {
                    k: dict(_percentiles(sorted(v)), completed=len(v),
                            deadline_dropped=self.dropped.get(k, 0))
                    for k, v in sorted(self.lats.items())}
                for k, n in sorted(self.dropped.items()):
                    if k not in report["by_class"]:
                        report["by_class"][k] = {
                            "completed": 0, "deadline_dropped": n}
        return report


# ------------------------------------------------- keep-alive HTTP client --

class KeepAliveClient:
    """One persistent HTTP/1.1 connection per load-worker thread.

    A new TCP connect per request (the old urllib path) costs more than
    a router-dispatched predict on loopback, so it both understates rps
    and drowns the router's own overhead in the measurement. This client
    reuses the connection, transparently reconnecting on a
    connection-level failure, and accounts **connect time separately**
    from request time: :meth:`request` returns the milliseconds spent
    (re)connecting for that call so the caller can keep the request
    latency sample clean and report the connect cost on its own line.
    """

    def __init__(self, url, timeout=10.0):
        import urllib.parse

        p = urllib.parse.urlsplit(url)
        self._host = p.hostname or "127.0.0.1"
        self._port = p.port or (443 if p.scheme == "https" else 80)
        self._timeout = timeout
        self._conn = None
        self.connects = 0
        self.connect_ms = 0.0

    def _ensure(self):
        import http.client

        if self._conn is None:
            import socket

            t0 = time.perf_counter()
            conn = http.client.HTTPConnection(self._host, self._port,
                                              timeout=self._timeout)
            conn.connect()
            # a reused connection without TCP_NODELAY eats the Nagle x
            # delayed-ACK stall (~40ms) on every request — even loopback
            conn.sock.setsockopt(socket.IPPROTO_TCP,
                                 socket.TCP_NODELAY, 1)
            dt = (time.perf_counter() - t0) * 1e3
            self.connects += 1
            self.connect_ms += dt
            self._conn = conn
            return conn, dt
        return self._conn, 0.0

    def close(self):
        if self._conn is not None:
            try:
                self._conn.close()
            except OSError:
                pass
            self._conn = None

    def request(self, method, path, body=None, headers=None):
        """-> (status, payload bytes, connect_ms for THIS call). Retries
        once through a fresh connection when the reused one died (the
        server closed an idle keep-alive)."""
        import http.client

        connect_ms = 0.0
        for attempt in (0, 1):
            conn, dt = self._ensure()
            connect_ms += dt
            try:
                conn.request(method, path, body=body,
                             headers=headers or {})
                resp = conn.getresponse()
                return resp.status, resp.read(), connect_ms
            except (ConnectionError, http.client.HTTPException, OSError):
                self.close()
                if attempt:
                    raise
        raise RuntimeError("unreachable")  # pragma: no cover


def _connect_fields(report, clients, threads):
    """Fold the per-thread keep-alive connect accounting into a report:
    connect time is reported SEPARATELY from the request-latency
    percentiles (which exclude it)."""
    connects = sum(c.connects for c in clients)
    connect_ms = sum(c.connect_ms for c in clients)
    report["connects"] = connects
    report["reconnects"] = max(0, connects - threads)
    report["connect_ms_total"] = round(connect_ms, 3)
    report["connect_ms_mean"] = round(connect_ms / connects, 3) \
        if connects else None
    return report


_PHASES = ("queue_wait", "batch_collect", "h2d", "compute", "respond",
           "total")
_PHASE_CAP = 200000  # bound the per-phase sample memory on long runs


class _PhaseAgg:
    """Collects per-request phase breakdowns (from the serving span
    tracer) and reduces them to p50/p99/mean per phase. Accepts both
    the in-process ``ServingFuture.breakdown()`` shape (``<phase>_ms``
    keys) and the HTTP response ``phases`` object (bare phase keys)."""

    def __init__(self, lock):
        self._lock = lock
        self.samples = {k: [] for k in _PHASES}
        self.traced = 0

    def record(self, bd):
        if not bd:
            return
        with self._lock:
            self.traced += 1
            for k in _PHASES:
                v = bd.get("total_ms") if k == "total" \
                    else bd.get(f"{k}_ms", bd.get(k))
                if isinstance(v, (int, float)) \
                        and len(self.samples[k]) < _PHASE_CAP:
                    self.samples[k].append(float(v))

    def report(self):
        from mxnet_tpu.serving.metrics import percentile

        out = {}
        with self._lock:
            for k, vals in self.samples.items():
                if not vals:
                    continue
                vals = sorted(vals)
                out[k] = {"p50_ms": round(percentile(vals, 50), 3),
                          "p99_ms": round(percentile(vals, 99), 3),
                          "mean_ms": round(sum(vals) / len(vals), 3),
                          "n": len(vals)}
        return out or None


# -------------------------------------------------------------- in-process --

def run_inproc(duration=30.0, mode="closed", concurrency=8, rate=2000.0,
               models=2, dim=16, warmup=True, server=None, via_http=False,
               max_wait_ms=None, priority_mix=None, hot_key_frac=0.0,
               deadline_ms=None):
    """Drive a ModelServer (built here unless `server` is passed) and
    return the report dict. With ``via_http`` the same traffic goes
    through the JSON front end on a loopback socket. The QoS knobs
    behave as in :func:`run_http` (per-class report, hot-key cache
    traffic, per-request deadlines — drops counted, not errors)."""
    import numpy as np

    from mxnet_tpu import compile as _compile
    from mxnet_tpu import serving

    own_server = server is None
    if own_server:
        container = build_demo_container(models=models, dim=dim)
        # hot-key traffic implies the prediction-cache scenario: turn
        # the (default-off) cache on so hits are measurable
        cache = True if float(hot_key_frac or 0.0) > 0.0 else None
        server = serving.ModelServer(container, cache=cache).start()
    names = server.models()
    if warmup:
        server.warmup()
    pre = _compile.stats().get("serving", {})
    pre_misses = pre.get("misses", 0)
    plan = _QoSPlan(priority_mix, hot_key_frac, deadline_ms)

    front = None
    clients, tl = [], threading.local()
    client_lock = threading.Lock()
    if via_http:
        front = serving.HttpFrontEnd(server).start()

        def do_request(name, x, tid, i):
            # one keep-alive connection per worker thread: connect time
            # is measured inside the client and subtracted from the
            # request latency sample by the caller
            cl = getattr(tl, "client", None)
            if cl is None:
                cl = tl.client = KeepAliveClient(front.url)
                with client_lock:
                    clients.append(cl)
            req = {"data": x.tolist()}
            req.update(plan.body_fields(tid, i))
            body = json.dumps(req).encode()
            status, payload, connect_ms = cl.request(
                "POST", f"/v1/models/{name}:predict", body=body,
                headers={"Content-Type": "application/json"})
            if status in (429, 503):
                raise serving.ServerBusyError(name, 0, 0)
            if status != 200:
                try:
                    data = json.loads(payload)
                except ValueError:
                    data = {}
                if status == 504 and data.get("dropped"):
                    raise serving.DeadlineExceeded(
                        name, plan.deadline_ms)
                raise RuntimeError(f"HTTP {status}: {payload[:120]!r}")
            data = json.loads(payload)
            return data.get("phases"), connect_ms, \
                data.get("model_version"), bool(data.get("cache_hit"))
    else:
        def do_request(name, x, tid, i):
            fut = server.submit(name, x, priority=plan.klass(tid, i),
                                deadline_ms=plan.deadline_ms)
            fut.result(10.0)
            return fut.breakdown(), 0.0, fut.model_version, \
                bool(fut.cache_hit)

    pool = [np.random.RandomState(i).randn(1, dim).astype(np.float32)
            for i in range(64)]
    lock = threading.Lock()
    lats, completed, rejected, errors = [], [0], [0], []
    versions = set()   # distinct model-bus versions seen in responses
    phases = _PhaseAgg(lock)
    qos = _QoSAgg(lock)
    stop_at = time.perf_counter() + duration

    def record(ms, ver=None):
        with lock:
            lats.append(ms)
            completed[0] += 1
            if ver is not None:
                versions.add(ver)

    def closed_worker(tid):
        i = 0
        while time.perf_counter() < stop_at:
            if plan.hot_key(tid, i):
                name, x = names[0], pool[0]
            else:
                name = names[(tid + i) % len(names)]
                x = pool[(tid * 7 + i) % len(pool)]
            klass = plan.klass(tid, i)
            t0 = time.perf_counter()
            try:
                bd, connect_ms, ver, cache_hit = do_request(
                    name, x, tid, i)
                ms = (time.perf_counter() - t0) * 1e3 - connect_ms
                record(ms, ver)
                qos.record(klass, ms, cache_hit=cache_hit)
                phases.record(bd)
            except serving.DeadlineExceeded:
                qos.record(klass, dropped=True)
            except serving.ServerBusyError:
                with lock:
                    rejected[0] += 1
                time.sleep(0.001)
            except Exception as e:  # keep driving; report at the end
                with lock:
                    errors.append(f"{type(e).__name__}: {e}")
                if len(errors) > 100:
                    return
            i += 1

    def open_loop():
        # scheduler: submit at the offered rate; waiter pool collects
        import queue as qmod

        inflight = qmod.Queue()
        done = threading.Event()

        def waiter():
            while True:
                try:
                    item = inflight.get(timeout=0.25)
                except qmod.Empty:
                    if done.is_set():
                        return
                    continue
                t0, klass, fut = item
                try:
                    fut.result(10.0)
                    ms = (time.perf_counter() - t0) * 1e3
                    record(ms, fut.model_version)
                    qos.record(klass, ms,
                               cache_hit=bool(fut.cache_hit))
                    phases.record(fut.breakdown())
                except serving.DeadlineExceeded:
                    qos.record(klass, dropped=True)
                except serving.ServerBusyError:
                    with lock:
                        rejected[0] += 1
                except Exception as e:
                    with lock:
                        errors.append(f"{type(e).__name__}: {e}")

        waiters = [threading.Thread(target=waiter, daemon=True)
                   for _ in range(max(2, concurrency))]
        for w in waiters:
            w.start()
        period = 1.0 / max(rate, 1.0)
        nxt = time.perf_counter()
        i = 0
        while time.perf_counter() < stop_at:
            now = time.perf_counter()
            if now < nxt:
                time.sleep(min(nxt - now, 0.002))
                continue
            nxt += period
            if plan.hot_key(0, i):
                name, x = names[0], pool[0]
            else:
                name = names[i % len(names)]
                x = pool[i % len(pool)]
            klass = plan.klass(0, i)
            t0 = time.perf_counter()
            try:
                fut = server.submit(name, x, priority=klass,
                                    deadline_ms=plan.deadline_ms)
                inflight.put((t0, klass, fut))
            except serving.DeadlineExceeded:
                qos.record(klass, dropped=True)
            except serving.ServerBusyError:
                with lock:
                    rejected[0] += 1
            i += 1
        done.set()
        for w in waiters:
            w.join(timeout=15.0)

    t_start = time.perf_counter()
    if mode == "open" and not via_http:
        open_loop()
    else:
        threads = [threading.Thread(target=closed_worker, args=(t,),
                                    daemon=True)
                   for t in range(concurrency)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=duration + 30.0)
    elapsed = time.perf_counter() - t_start

    post = _compile.stats().get("serving", {})
    stats = server.stats()
    fills = [m.get("batch_fill_ratio") for m in stats["models"].values()
             if m.get("batch_fill_ratio")]
    report = {
        "harness": "loadgen",
        "mode": mode,
        "via_http": bool(via_http),
        "duration_s": round(elapsed, 2),
        "models": names,
        "concurrency": concurrency,
        "requests": completed[0] + rejected[0] + len(errors),
        "completed": completed[0],
        "rejected": rejected[0],
        "errors": len(errors),
        "first_errors": errors[:3],
        "rps": round(completed[0] / elapsed, 1) if elapsed else 0.0,
        "batch_fill_ratio": round(sum(fills) / len(fills), 4)
        if fills else None,
        "recompiles_during_run": post.get("misses", 0) - pre_misses,
        # distinct model-bus versions stamped into responses (>1 means
        # live weight updates flipped mid-run; 0 = load-time weights)
        "model_versions": sorted(versions) if versions else None,
        "server_stats": stats["models"],
        # per-phase latency split from the serving span tracer
        # (queue_wait/batch_collect/h2d/compute/respond; None when
        # tracing is off) — the "where did my p99 go" answer
        "phase_breakdown": phases.report(),
        "traced_requests": phases.traced,
    }
    report.update(_percentiles(sorted(lats)))
    qos.fold(report, plan)
    if via_http:
        _connect_fields(report, clients, concurrency)
        for cl in clients:
            cl.close()
    if front is not None:
        front.close()
    if own_server:
        server.drain(timeout=10.0)
    return report


# --------------------------------------------------------------- over HTTP --

def run_http(url, duration=30.0, concurrency=8, dim=16,
             priority_mix=None, hot_key_frac=0.0, deadline_ms=None):
    """Closed-loop drive of an EXTERNAL front end at `url` (model list
    discovered via GET /v1/models) over per-thread keep-alive
    connections; connect time reported separately from request time.

    QoS knobs: ``priority_mix`` ('4:1' interactive:batch weights) stamps
    a priority class per request and splits the latency report per
    class; ``hot_key_frac`` re-sends ONE hot (model, input) pair for
    that fraction of requests (driving prediction-cache hits);
    ``deadline_ms`` stamps a deadline on every request — deadline drops
    (504 + ``dropped``) are counted per class, NOT as errors."""
    import urllib.request

    import numpy as np

    with urllib.request.urlopen(f"{url.rstrip('/')}/v1/models",
                                timeout=10.0) as resp:
        names = json.loads(resp.read())["models"]
    pool = [np.random.RandomState(i).randn(1, dim).astype(np.float32)
            for i in range(64)]
    lock = threading.Lock()
    lats, completed, rejected, errors = [], [0], [0], []
    versions = set()
    clients = []
    phases = _PhaseAgg(lock)
    plan = _QoSPlan(priority_mix, hot_key_frac, deadline_ms)
    qos = _QoSAgg(lock)
    stop_at = time.perf_counter() + duration

    def worker(tid):
        cl = KeepAliveClient(url)
        with lock:
            clients.append(cl)
        i = 0
        while time.perf_counter() < stop_at:
            if plan.hot_key(tid, i):
                # the hot pair: ONE model x ONE input -> one cache key
                name, x = names[0], pool[0]
            else:
                name = names[(tid + i) % len(names)]
                x = pool[(tid * 7 + i) % len(pool)]
            klass = plan.klass(tid, i)
            req = {"data": x.tolist()}
            req.update(plan.body_fields(tid, i))
            body = json.dumps(req).encode()
            t0 = time.perf_counter()
            try:
                status, payload, connect_ms = cl.request(
                    "POST", f"/v1/models/{name}:predict", body=body,
                    headers={"Content-Type": "application/json"})
            except Exception as e:
                with lock:
                    errors.append(f"{type(e).__name__}: {e}")
                i += 1
                continue
            try:
                data = json.loads(payload)
            except ValueError:
                data = {}
            if status in (429, 503):
                with lock:
                    rejected[0] += 1
                time.sleep(0.001)
            elif status == 504 and data.get("dropped"):
                # admission refused a provably-unmeetable deadline
                # BEFORE compute: QoS working as designed, not an error
                qos.record(klass, dropped=True)
            elif status != 200:
                with lock:
                    errors.append(f"HTTP {status}")
            else:
                ms = (time.perf_counter() - t0) * 1e3 - connect_ms
                with lock:
                    lats.append(ms)
                    completed[0] += 1
                    if data.get("model_version") is not None:
                        versions.add(data["model_version"])
                qos.record(klass, ms,
                           cache_hit=bool(data.get("cache_hit")))
                phases.record(data.get("phases"))
            i += 1

    threads = [threading.Thread(target=worker, args=(t,), daemon=True)
               for t in range(concurrency)]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=duration + 30.0)
    elapsed = time.perf_counter() - t_start
    report = {
        "harness": "loadgen", "mode": "closed", "via_http": True,
        "url": url, "duration_s": round(elapsed, 2), "models": names,
        "concurrency": concurrency, "completed": completed[0],
        "rejected": rejected[0], "errors": len(errors),
        "first_errors": errors[:3],
        "rps": round(completed[0] / elapsed, 1) if elapsed else 0.0,
        "model_versions": sorted(versions) if versions else None,
        "phase_breakdown": phases.report(),
        "traced_requests": phases.traced,
    }
    report.update(_percentiles(sorted(lats)))
    qos.fold(report, plan)
    _connect_fields(report, clients, concurrency)
    for cl in clients:
        cl.close()
    return report


# ------------------------------------------------- multi-process (fleet) --

def run_fleet(workers=2, duration=10.0, concurrency=8, models=2, dim=16,
              policy=None, run_dir=None, beat=0.25, hosts=None,
              config=None, priority_mix=None, hot_key_frac=0.0,
              deadline_ms=None):
    """Multi-process mode: an N-worker :class:`ServingFleet` (one
    ModelServer process per worker behind the router) driven by the
    same keep-alive closed loop as ``--url``. The report carries the
    fleet's router counters (retries/rejects), hedge outcomes +
    straggler flags, and per-worker census so the 1→N scaling number is
    auditable. Autoscaling is pinned off (min == max == workers): this
    harness measures the router path at a fixed census. ``hosts``
    places workers multi-host (the fleet grammar); ``config`` overlays
    extra fleet options; the QoS knobs pass through to
    :func:`run_http`."""
    import tempfile

    from mxnet_tpu.serving import fleet as fleet_mod
    from mxnet_tpu.serving import worker as worker_mod

    root = run_dir or tempfile.mkdtemp(prefix="loadgen_fleet_")
    model_dir = os.path.join(root, "models")
    worker_mod.write_spec(model_dir,
                          worker_mod.demo_spec(models=models, dim=dim))
    cfg = {"min": workers, "max": workers, "beat": beat}
    cfg.update(config or {})
    env = None
    if float(hot_key_frac or 0.0) > 0.0:
        # hot-key traffic implies the prediction cache: enable it in
        # every worker (the env grammar composes with any ambient one)
        spec = os.environ.get("MXNET_TPU_SERVING", "")
        env = {"MXNET_TPU_SERVING":
               (spec + ",cache:1").lstrip(",")}
    fl = fleet_mod.ServingFleet(
        model_dir, workers=workers, run_dir=os.path.join(root, "run"),
        policy=policy, hosts=hosts, config=cfg, env=env,
        name=f"loadgen-{workers}w")
    t0 = time.perf_counter()
    fl.start()
    startup_s = time.perf_counter() - t0
    try:
        report = run_http(fl.url, duration=duration,
                          concurrency=concurrency, dim=dim,
                          priority_mix=priority_mix,
                          hot_key_frac=hot_key_frac,
                          deadline_ms=deadline_ms)
        stats = fl.stats()
    finally:
        fl.stop()
    report.update({
        "harness": "loadgen-fleet",
        "workers": workers,
        "policy": stats["policy"],
        "fleet_startup_s": round(startup_s, 2),
        "router": stats["router"],
        "hedges": stats.get("hedges"),
        "stragglers": stats.get("stragglers"),
        "hosts": stats.get("hosts"),
        "per_worker": {
            slot: {k: w.get(k) for k in ("rps", "queue_depth", "p99_ms",
                                         "restarts", "host", "locality")}
            for slot, w in stats["workers"].items()},
        "run_dir": fl.run_dir,
    })
    return report


# --------------------------------------------------------------------- cli --

def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="loadgen", description="serving load generator")
    ap.add_argument("--duration", type=float, default=30.0,
                    help="seconds of sustained load (default 30)")
    ap.add_argument("--mode", choices=("closed", "open"), default="closed")
    ap.add_argument("--concurrency", type=int, default=8,
                    help="closed-loop workers / open-loop waiters")
    ap.add_argument("--rate", type=float, default=2000.0,
                    help="open-loop offered requests/s")
    ap.add_argument("--models", type=int, default=2,
                    help="demo MLPs in the in-process container")
    ap.add_argument("--dim", type=int, default=16,
                    help="demo model feature dim")
    ap.add_argument("--via-http", action="store_true",
                    help="drive the in-process server through the HTTP "
                         "front end (socket path end to end)")
    ap.add_argument("--url", default=None,
                    help="drive an EXTERNAL front end instead of building "
                         "an in-process server")
    ap.add_argument("--workers", type=int, default=None,
                    help="multi-process mode: spawn an N-worker "
                         "ServingFleet and drive the router closed-loop "
                         "(the 1->N rps scaling measurement)")
    ap.add_argument("--policy", default=None,
                    choices=("least_loaded", "hash", "round_robin"),
                    help="fleet routing policy (--workers mode; default "
                         "least_loaded)")
    ap.add_argument("--priority-mix", default=None, metavar="I:B",
                    help="interactive:batch traffic weights (e.g. 4:1); "
                         "the report then splits p50/p99 and deadline "
                         "drops per class")
    ap.add_argument("--hot-key-frac", type=float, default=0.0,
                    help="fraction of requests re-sending ONE hot "
                         "(model, input) pair — drives prediction-cache "
                         "hits (reported as cache_hit_ratio)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="stamp this deadline on every request; "
                         "admission drops (504 dropped) are counted per "
                         "class, not as errors")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip the pre-traffic bucket warmup (recompiles "
                         "will then land inside the measured window)")
    ap.add_argument("--dtype", choices=("float32", "int8", "both"),
                    default=None,
                    help="model-pair mode: serve the embedding-lookup "
                         "fixture as fp32 AND its entropy-calibrated int8 "
                         "twin; 'both' drives each for duration/2 with the "
                         "same harness and prints the matched-p99 rps "
                         "ratio as one JSON line")
    ap.add_argument("--pair-vocab", type=int, default=50_000,
                    help="pair-mode embedding vocab (table size drives "
                         "the bandwidth win)")
    ap.add_argument("--pair-embed-dim", type=int, default=512)
    ap.add_argument("--pair-seq-len", type=int, default=1024)
    ap.add_argument("--calib-mode", default="entropy",
                    choices=("entropy", "naive", "percentile"),
                    help="pair-mode calibration mode for the int8 twin")
    args = ap.parse_args(argv)

    if args.dtype:
        variants = ("float32", "int8") if args.dtype == "both" \
            else (args.dtype,)
        report = run_pair(
            duration=args.duration, concurrency=args.concurrency,
            vocab=args.pair_vocab, embed_dim=args.pair_embed_dim,
            seq_len=args.pair_seq_len, calib_mode=args.calib_mode,
            warmup=not args.no_warmup, variants=variants)
        ratio = report.get("rps_ratio_int8_vs_float")
        print("loadgen pair: " + ", ".join(
            f"{v}: {s['rps']} req/s p99 {s.get('p99_ms')}ms"
            for v, s in report["variants"].items()) +
            (f" -> int8/float = {ratio}x "
             f"(matched_p99={report.get('matched_p99')})"
             if ratio is not None else ""),
            file=sys.stderr, flush=True)
        print(json.dumps(report), flush=True)
        errs = sum(s["errors"] for s in report["variants"].values())
        return 0 if errs == 0 else 1

    qos_kw = {"priority_mix": args.priority_mix,
              "hot_key_frac": args.hot_key_frac,
              "deadline_ms": args.deadline_ms}

    if args.workers:
        report = run_fleet(workers=args.workers, duration=args.duration,
                           concurrency=args.concurrency,
                           models=args.models, dim=args.dim,
                           policy=args.policy, **qos_kw)
        hedges = report.get("hedges") or {}
        print(f"loadgen fleet: {args.workers} worker(s) -> "
              f"{report['rps']} req/s, p50 {report.get('p50_ms')}ms "
              f"p99 {report.get('p99_ms')}ms, "
              f"{report['router'].get('retries', 0)} router retries, "
              f"{hedges.get('fired', 0)} hedges "
              f"({hedges.get('won', 0)} won), "
              f"{report.get('deadline_dropped', 0)} deadline drops, "
              f"cache hit ratio {report.get('cache_hit_ratio')}, "
              f"{report['reconnects']} reconnects "
              f"(connect {report.get('connect_ms_mean')}ms mean)",
              file=sys.stderr, flush=True)
        print(json.dumps(report), flush=True)
        return 0 if report.get("errors", 0) == 0 else 1

    if args.url:
        report = run_http(args.url, duration=args.duration,
                          concurrency=args.concurrency, dim=args.dim,
                          **qos_kw)
    else:
        report = run_inproc(
            duration=args.duration, mode=args.mode,
            concurrency=args.concurrency, rate=args.rate,
            models=args.models, dim=args.dim, warmup=not args.no_warmup,
            via_http=args.via_http, **qos_kw)
    print(f"loadgen: {report['completed']} completed in "
          f"{report['duration_s']}s -> {report['rps']} req/s, "
          f"p50 {report.get('p50_ms')}ms p99 {report.get('p99_ms')}ms, "
          f"{report['rejected']} rejected, "
          f"{report.get('recompiles_during_run', 'n/a')} recompiles "
          "during the run", file=sys.stderr, flush=True)
    print(json.dumps(report), flush=True)
    return 0 if report.get("errors", 0) == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
