"""Mode ``serve_open``: the serving stack under an open loop, in process.

``ModelContainer.add_block`` -> ``ModelServer.start()`` -> ``warmup()``
over the bucket ladder, then requests arrive on a seeded schedule
(``harness/arrivals.py``) at the rate fixed in the cell, whether or not
earlier ones have finished. Each request is timed FROM WHEN IT WAS DUE to
the instant the server fulfilled its future (``ServingFuture.t_done``, the
same ``time.monotonic`` clock), so a generator that falls behind shows as
latency, and as ``late``. A request that is rejected, fails or is not
answered within ``request_timeout_s`` counts as failed and, in the
percentiles, as having taken that timeout: over every limit.

Traffic keys: those of ``arrivals.schedule`` plus ``buckets`` and
``max_wait_ms`` (null = the defaults of ``serving/config.py``),
``waiters`` (threads that collect futures), ``request_timeout_s``,
``latency_limit_ms`` (attainment is printed, not judged),
``trace_seconds`` (traffic profiled in a ``--trace 1`` run).
"""
import queue
import threading
import time

import numpy as np

from chipbench.harness import arrivals, check, stats
from chipbench.harness.bench import Outcome

MODEL = "cell"


def setup(bench):
    """The served network, its server (started and warmed over exactly
    the ladder it serves) and the seeded payload pool."""
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import serving

    traffic, cfg = bench.traffic, bench.cfg
    net = bench.model.build(cfg, mx.tpu(), bench.seed)
    container = serving.ModelContainer()
    container.add_block(MODEL, net, bench.model.example_shape(cfg),
                        dtype=cfg["dtype"], buckets=traffic.get("buckets"))
    server = serving.ModelServer(
        container, max_wait_ms=traffic.get("max_wait_ms")).start()
    warm = server.warmup()
    # float32 payloads, a request of k rows is k consecutive pool rows
    # (a view: the generator copies nothing); rounded to the served type
    # up front so that the server's cast is exact and the reference sees
    # the same numbers
    most = max(int(k) for k in traffic["rows"])
    rng = np.random.default_rng([bench.seed, 0x9001])
    pool = rng.random((int(traffic["pool"]) + most,)
                      + bench.model.example_shape(cfg), dtype=np.float32)
    pool = np.asarray(pool.astype(jnp.dtype(cfg["dtype"]))) \
        .astype(np.float32)
    return net, server, pool, warm


def drive(server, pool, traffic, seed, seconds, span, out_shape):
    """Offer the seeded schedule for ``seconds`` and wait for every
    answer. Returns the per-request arrays and the futures."""
    from mxnet_tpu.serving import errors as serrors

    due, rows, offset = arrivals.schedule(traffic, seed, seconds)
    n = len(due)
    sent = np.full(n, np.nan)
    done = np.full(n, np.nan)
    good = np.zeros(n, bool)
    futs = [None] * n
    outcomes = {"rejected": 0, "errors": {}}
    timeout = float(traffic["request_timeout_s"])
    inflight = queue.SimpleQueue()

    def waiter():
        while True:
            item = inflight.get()
            if item is None:
                return
            i, fut = item
            try:
                with span("serve.wait"):
                    out = fut.result(timeout)
            except serrors.ServingError as e:
                kind = type(e).__name__
                outcomes["errors"][kind] = \
                    outcomes["errors"].get(kind, 0) + 1  # one waiter/slot
                continue
            done[i] = fut.t_done
            good[i] = (out.shape == (rows[i],) + out_shape
                       and bool(np.isfinite(out).all()))

    waiters = [threading.Thread(target=waiter, name=f"chipbench-wait-{k}",
                                daemon=True)
               for k in range(int(traffic["waiters"]))]
    for w in waiters:
        w.start()
    t0 = time.monotonic()
    for i in range(n):
        wait = t0 + due[i] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        x = pool[offset[i]:offset[i] + rows[i]]
        with span("serve.generator"):
            sent[i] = time.monotonic()
            try:
                futs[i] = server.submit(MODEL, x)
            except serrors.ServingError:
                outcomes["rejected"] += 1
                continue
        inflight.put((i, futs[i]))
    for _ in waiters:
        inflight.put(None)
    for w in waiters:
        w.join(timeout=timeout + 30.0)
    if any(w.is_alive() for w in waiters):
        raise RuntimeError("a waiter thread did not finish: requests are "
                           "still unanswered after their timeout")
    answered = good & np.isfinite(done)
    latency_ms = np.where(answered, (done - (t0 + due)) * 1e3,
                          timeout * 1e3)
    return {"n": n, "due": due, "rows": rows, "sent": sent - t0,
            "latency_ms": latency_ms, "answered": answered,
            "bad_answers": int((np.isfinite(done) & ~good).sum()),
            "outcomes": outcomes, "futs": futs,
            "elapsed_s": time.monotonic() - t0}


def _model_stats(server):
    m = server.stats()["models"][MODEL]
    return {k: m[k] for k in ("batches", "rows", "padded_rows",
                              "bucket_census")}


def _check_served(bench, server, net, pool):
    """Served answers replayed after the window against the reference."""
    n = int(bench.cfg["check"]["samples"])
    got, off = [], 0
    for k in (1, 2, 4, 8):
        k = min(k, n - off)
        if k <= 0:
            break
        got.append(np.asarray(server.predict(
            MODEL, pool[off:off + k],
            timeout=float(bench.traffic["request_timeout_s"]))))
        off += k
    return check.against_reference(bench, net, pool[:off],
                                   np.concatenate(got))


def summary(res, seconds):
    """What one ``drive`` showed, for the ``serve`` line and for
    ``sweep_knee.py``."""
    lat = res["latency_ms"].tolist()
    late_ms = (res["sent"] - res["due"]) * 1e3
    late_ms = late_ms[np.isfinite(late_ms)].tolist()
    n = res["n"]
    return {
        "attempted": n, "failed": int(n - res["answered"].sum()),
        "outcomes": res["outcomes"], "bad_answers": res["bad_answers"],
        "offered_per_s": n / seconds,
        "offered_rows_per_s": float(res["rows"].sum()) / seconds,
        "completed_rows_per_s": float(
            res["rows"][res["answered"]].sum()) / res["elapsed_s"],
        "p50_ms": stats.percentile(lat, 50),
        "p99_ms": stats.percentile(lat, 99),
        "samples_beyond_p99": int(n * 0.01),
        "generator_late_p99_ms": stats.percentile(late_ms, 99),
        "drain_s": res["elapsed_s"] - seconds}, lat, late_ms


def run(bench):
    traffic = bench.traffic
    net, server, pool, warm = setup(bench)
    out_shape = (bench.cfg["classes"],)
    try:
        bench.setup_done()
        before = _model_stats(server)
        tracer = None
        if bench.trace:
            def traced():
                time.sleep(bench.seconds / 3.0)
                bench.trace_start()
                time.sleep(float(traffic["trace_seconds"]))
                bench.trace_stop()

            tracer = threading.Thread(target=traced, name="chipbench-trace")
            tracer.start()
        res = drive(server, pool, traffic, bench.seed, bench.seconds,
                    bench.span, out_shape)
        if tracer is not None:
            tracer.join()
        bench.window_closed()
        after = _model_stats(server)
        served_ok, served_note = _check_served(bench, server, net, pool)
    finally:
        server.stop()

    note, lat, late_ms = summary(res, bench.seconds)
    limit = float(traffic["latency_limit_ms"])
    delta = {k: after[k] - before[k]
             for k in ("batches", "rows", "padded_rows")}
    breakdowns = [f.breakdown() for f in res["futs"] if f is not None] \
        if bench.trace else []
    run_bag = {
        "mode": "serve_open", "workload": bench.name, "traffic": traffic,
        "cfg": bench.cfg, "model": bench.model,
        "latency_ms": lat, "late_ms": late_ms, "stats_delta": delta,
        "breakdowns": [b for b in breakdowns if b],
    }
    note.update({
        "knee_rows_per_s": traffic.get("knee_rows_per_s"),
        "latency_limit_ms": limit,
        "attainment": float(np.mean(res["latency_ms"] <= limit))
        if res["n"] else None,
        "stats_delta": delta, "bucket_census": after["bucket_census"],
        "warmup": warm["models"][MODEL]})
    notes = {"serve": note, "checks": {"served": served_note}}
    # a rejected or late request is a failure; a wrong answer is an error
    correct = bool(served_ok and res["bad_answers"] == 0)
    return Outcome(
        correct=correct, attempted=note["attempted"], failed=note["failed"],
        end_to_end={"serve_p50_ms": note["p50_ms"],
                    "serve_p99_ms": note["p99_ms"]},
        run=run_bag, notes=notes)
