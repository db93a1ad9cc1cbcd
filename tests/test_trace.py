"""Gang-wide tracing plane (PR 12): propagated spans, fleet metric
aggregation, straggler detection, merged multi-rank Perfetto traces.

Headline guarantees under test:

* span correctness: nesting through the per-thread stack, propagated
  trace context, request-id uniqueness under concurrent submits;
* the serving pipeline commits a five-phase per-request breakdown
  (queue_wait / batch_collect / h2d / compute / respond) available on
  ``ServingFuture.breakdown()``, in the HTTP response (with the
  ``X-Request-Id`` propagated end to end) and in ``tools/loadgen.py``'s
  ``phase_breakdown`` report — cross-checked against
  ``serving.stats()`` percentiles;
* fleet aggregation: rank telemetry shards round-trip atomically, torn
  or partial shards are SKIPPED at merge, and the ``mxtpu_fleet_*``
  counter sums agree exactly with the per-rank scrapes;
* straggler detection: the cross-rank skew verdict flags a seeded slow
  rank, persistence requires consecutive NEW common steps, and the
  ``gang.straggler`` flight event is recorded once per episode;
* merged traces: clock-offset alignment preserves per-rank event order
  (monotonicity), and the merged ``trace.json`` validates against the
  Chrome trace-event schema with per-rank lanes;
* the overhead contract, counted: the eager per-op path opens no span;
  with the ring off nothing is appended and a request gets no
  ``RequestTrace``; with telemetry off a span reads no clock and enters
  no ``TraceAnnotation``;
* the end-to-end drill: a 2-rank supervised run under load produces one
  fleet scrape whose sums agree with the per-rank scrapes, a straggler
  detection naming the delay-injected rank 1, and a merged trace with
  per-rank lanes and a serving request span showing all five phases.
"""
import json
import os
import re
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, serving
from mxnet_tpu.telemetry import export, fleet, flight, registry, trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PHASES = trace.REQUEST_PHASES


def _metric(text, name, **labels):
    pat = name + (r"\{" if labels else r"[ {]")
    for ln in text.splitlines():
        if not re.match(pat, ln):
            continue
        if all(f'{k}="{v}"' in ln for k, v in labels.items()):
            return float(ln.rsplit(" ", 1)[1])
    return None


def small_server(name="tr", seed=11, dim=6, buckets=(2,), max_wait_ms=1.0):
    mx.random.seed(seed)
    net = gluon.nn.Dense(4, in_units=dim)
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((2, dim)))
    cont = serving.ModelContainer()
    cont.add_block(name, net, example_shape=(dim,), buckets=buckets)
    srv = serving.ModelServer(cont, max_wait_ms=max_wait_ms).start()
    srv.warmup()
    return srv


# ------------------------------------------------------------------ spans ---

def test_span_nesting_and_context():
    trace.clear()
    with trace.context("job-1"):
        with trace.span("outer") as outer:
            with trace.span("inner"):
                time.sleep(0.002)
    spans = {s["name"]: s for s in trace.tail()}
    assert spans["inner"]["parent"] == outer.span_id
    assert spans["outer"]["parent"] is None
    assert spans["inner"]["trace"] == spans["outer"]["trace"] == "job-1"
    assert spans["outer"]["dur_ms"] >= spans["inner"]["dur_ms"] > 0
    # context is scoped: outside the with-block nothing is bound
    assert trace.get_context() is None


def test_span_ring_bounded_and_configure():
    prev = trace.configure(16)
    try:
        for i in range(50):
            trace.commit(f"s{i}", time.monotonic(), 0.1)
        assert len(trace.tail()) == 16
        assert trace.tail()[-1]["name"] == "s49"
        # 0 disables: hooks become a single check, commits drop
        trace.configure(0)
        assert not trace.enabled()
        assert trace.commit("off", time.monotonic(), 0.1) is None
        assert trace.tail() == []
    finally:
        trace.configure(prev)


def test_request_id_uniqueness_under_concurrent_submits():
    """Request ids are minted from a GIL-atomic counter: concurrent
    submitters can never collide (and a served burst keeps one id per
    request end to end)."""
    ids, lock = set(), threading.Lock()

    def mint(n):
        got = [trace.new_request_id() for _ in range(n)]
        with lock:
            ids.update(got)

    threads = [threading.Thread(target=mint, args=(200,))
               for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(ids) == 8 * 200

    srv = small_server("uniq", seed=3)
    try:
        futs = []

        def submit_some(tid):
            for i in range(10):
                futs.append(srv.submit(
                    "uniq", np.zeros((1, 6), np.float32)))

        workers = [threading.Thread(target=submit_some, args=(t,))
                   for t in range(4)]
        for w in workers:
            w.start()
        for w in workers:
            w.join()
        for f in futs:
            f.result(10.0)
        rids = [f.request_id for f in futs]
        assert None not in rids and len(set(rids)) == len(rids)
    finally:
        srv.drain(timeout=10.0)
        srv.stop()


# ------------------------------------------------------- serving pipeline ---

def test_serving_request_span_five_phases():
    trace.clear()
    srv = small_server("fp", seed=5)
    try:
        fut = srv.submit("fp", np.zeros((1, 6), np.float32))
        fut.result(10.0)
        bd = fut.breakdown()
        assert bd is not None and bd["request_id"] == fut.request_id
        for k in PHASES:
            assert isinstance(bd[f"{k}_ms"], float) \
                and bd[f"{k}_ms"] >= 0.0, (k, bd)
        # the phases can never sum past the measured total
        assert sum(bd[f"{k}_ms"] for k in PHASES) \
            <= bd["total_ms"] * 1.05 + 0.5
        spans = trace.tail()
        req = [s for s in spans if s["kind"] == "request"
               and s["trace"] == fut.request_id]
        assert len(req) == 1 and req[0]["attrs"]["rows"] == 1
        children = [s for s in spans if s["kind"] == "phase"
                    and s["trace"] == fut.request_id]
        assert sorted(c["name"] for c in children) == sorted(PHASES)
        assert all(c["parent"] == req[0]["seq"] for c in children)
    finally:
        srv.drain(timeout=10.0)
        srv.stop()


def test_http_front_end_propagates_request_id_and_phases():
    srv = small_server("hp", seed=7)
    front = serving.HttpFrontEnd(srv).start()
    try:
        req = urllib.request.Request(
            front.url + "/v1/models/hp:predict",
            data=json.dumps(
                {"data": np.zeros((1, 6)).tolist()}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Request-Id": "caller-id-7"})
        with urllib.request.urlopen(req, timeout=10.0) as r:
            body = json.loads(r.read())
            assert r.headers.get("X-Request-Id") == "caller-id-7"
        assert body["request_id"] == "caller-id-7"
        for k in PHASES:
            assert body["phases"][k] is not None
        assert body["phases"]["total_ms"] > 0
        # the span ring keyed the whole pipeline on the caller's id
        kinds = {s["kind"] for s in trace.tail()
                 if s["trace"] == "caller-id-7"}
        assert kinds == {"request", "phase"}
        # without the header an id is minted and echoed
        req = urllib.request.Request(
            front.url + "/v1/models/hp:predict",
            data=json.dumps(
                {"data": np.zeros((1, 6)).tolist()}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10.0) as r:
            body2 = json.loads(r.read())
            assert r.headers.get("X-Request-Id") == body2["request_id"]
        assert body2["request_id"] != "caller-id-7"
    finally:
        front.close()
        srv.drain(timeout=10.0)
        srv.stop()


def test_loadgen_phase_breakdown_cross_checks_server_stats():
    """Satellite: loadgen's JSON line carries p50/p99 per phase from the
    spans, consistent with the server's own latency percentiles."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import loadgen

    rep = loadgen.run_inproc(duration=1.0, mode="closed", concurrency=4,
                             models=1, dim=8)
    assert rep["completed"] > 0 and rep["errors"] == 0
    pb = rep["phase_breakdown"]
    assert pb is not None and rep["traced_requests"] > 0
    for k in PHASES + ("total",):
        assert k in pb and pb[k]["p50_ms"] >= 0.0 \
            and pb[k]["p99_ms"] >= pb[k]["p50_ms"], (k, pb)
    # cross-check against serving.stats(): the span total measures the
    # same submit->fulfil interval the server's latency ring does
    stats = next(iter(rep["server_stats"].values()))
    assert stats["p50_ms"] is not None
    assert abs(pb["total"]["p50_ms"] - stats["p50_ms"]) \
        <= max(5.0, stats["p50_ms"] * 1.0), (pb["total"], stats)
    assert pb["total"]["p99_ms"] <= max(10.0, stats["p99_ms"] * 3.0)
    # the phase split accounts for (almost all of) the measured total
    phase_p50_sum = sum(pb[k]["p50_ms"] for k in PHASES)
    assert phase_p50_sum <= pb["total"]["p99_ms"] * 1.5 + 1.0


# ------------------------------------------------------------ rank shards ---

def _synthetic_shard(rank, *, generation=1, t_wall=None, t_mono=None,
                     counters=(), gauges=(), steps=(), spans=(),
                     flights=()):
    metrics = {}
    for name, labels, series in counters:
        metrics[name] = {"kind": "counter", "help": "", "labels": labels,
                         "series": [{"labels": lv, "value": v}
                                    for lv, v in series]}
    for name, labels, series in gauges:
        metrics[name] = {"kind": "gauge", "help": "", "labels": labels,
                         "series": [{"labels": lv, "value": v}
                                    for lv, v in series]}
    return {"version": 1, "rank": rank, "generation": generation,
            "pid": 1000 + rank, "seq": 1,
            "t_wall": time.time() if t_wall is None else t_wall,
            "t_mono": time.monotonic() if t_mono is None else t_mono,
            "metrics": metrics, "steps": list(steps),
            "spans": list(spans), "flight": list(flights)}


def test_shard_write_read_roundtrip(tmp_path):
    path = fleet.write_shard(tmp_path, rank=0, generation=3)
    assert os.path.basename(path) == "telemetry-rank-0.json"
    shards = fleet.read_shards(tmp_path)
    assert set(shards) == {0}
    sh = shards[0]
    assert sh["generation"] == 3 and sh["pid"] == os.getpid()
    assert isinstance(sh["metrics"], dict) and "t_mono" in sh
    # generation filter
    assert fleet.read_shards(tmp_path, generation=2) == {}
    assert set(fleet.read_shards(tmp_path, generation=3)) == {0}
    assert fleet.shard_ages(tmp_path)[0] < 60.0


def test_torn_and_partial_shards_skipped_at_merge(tmp_path):
    good = _synthetic_shard(0, spans=[
        {"seq": 1, "name": "s", "kind": "span", "trace": None,
         "parent": None, "t0": 1.0, "dur_ms": 2.0, "lane": 1}])
    with open(fleet.shard_path(tmp_path, 0), "w") as f:
        json.dump(good, f)
    # torn: truncated mid-object (a writer died between open and replace)
    with open(fleet.shard_path(tmp_path, 1), "w") as f:
        f.write(json.dumps(_synthetic_shard(1))[:40])
    # partial: parseable JSON but missing the clock pair
    with open(fleet.shard_path(tmp_path, 2), "w") as f:
        json.dump({"rank": 2, "spans": []}, f)
    # not even json
    with open(fleet.shard_path(tmp_path, 3), "w") as f:
        f.write("\x00\x01 garbage")
    shards = fleet.read_shards(tmp_path)
    assert set(shards) == {0}
    events = trace.merged_events(shards)
    assert {e["pid"] for e in events} == {0}


def test_fleet_counter_sums_and_straggler_gauges(tmp_path):
    mk = lambda r, total, ms: _synthetic_shard(
        r,
        counters=[("mxtpu_ttest_requests_total", ["outcome"],
                   [({"outcome": "completed"}, total)])],
        gauges=[("mxtpu_step_time_ms", [], [({}, ms)])],
        steps=[{"step": s, "duration_ms": ms,
                "phases": {"sync": ms * 0.1}} for s in (1, 2, 3)])
    for rank, total, ms in ((0, 5.0, 10.0), (1, 7.0, 40.0)):
        with open(fleet.shard_path(tmp_path, rank), "w") as f:
            json.dump(mk(rank, total, ms), f)
    fleet.install(tmp_path)
    try:
        text = export.render_prometheus()
    finally:
        fleet.uninstall()
    assert _metric(text, "mxtpu_fleet_ranks") == 2
    assert _metric(text, "mxtpu_fleet_ttest_requests_total",
                   outcome="completed") == 12.0
    # curated per-rank gauge re-export
    assert _metric(text, "mxtpu_fleet_step_time_ms", rank="0") == 10.0
    assert _metric(text, "mxtpu_fleet_step_time_ms", rank="1") == 40.0
    # straggler gauges ride the same scrape (single update: flagged,
    # not yet persistent)
    assert _metric(text, "mxtpu_gang_straggler_rank") == 1
    assert _metric(text, "mxtpu_gang_straggler_skew_ms") == 30.0
    assert _metric(text, "mxtpu_gang_straggler_score", rank="1") == 4.0
    assert _metric(text, "mxtpu_gang_straggler_persistent") == 0


def test_straggler_detector_persistence_and_flight_event(tmp_path):
    det = fleet.StragglerDetector(factor=1.5, persist=3)
    flight.clear()

    def shards(upto, slow_ms=80.0):
        out = {}
        for rank in (0, 1):
            ms = slow_ms if rank == 1 else 20.0
            out[rank] = _synthetic_shard(rank, steps=[
                {"step": s, "duration_ms": ms,
                 "phases": {"sync": 2.0 if rank == 0 else 0.5}}
                for s in range(1, upto + 1)])
        return out

    v = det.update(shards(1))
    assert v["status"] == "ok" and v["slowest_rank"] == 1
    assert not v["persistent"] and v["streak"] == 1
    # re-reading UNCHANGED shards must not advance the streak
    v = det.update(shards(1))
    assert v["streak"] == 1
    v = det.update(shards(2))
    assert v["streak"] == 2 and not v["persistent"]
    v = det.update(shards(3))
    assert v["persistent"] and v["streak"] == 3
    assert det.events == 1
    ev = [e for e in flight.tail() if e["kind"] == "gang.straggler"]
    assert len(ev) == 1 and ev[0]["point"] == "rank1"
    # still persistent on the next step: the episode records only once
    det.update(shards(4))
    assert det.events == 1
    # recovery (skew gone) clears the flag and re-arms the episode
    v = det.update(shards(5, slow_ms=21.0))
    assert not v["persistent"] and v["slowest_rank"] is None
    # sync-wait share computed per rank
    assert 0 < v["per_rank"][0]["sync_share"] <= 0.15


def test_straggler_detector_degenerate_inputs():
    det = fleet.StragglerDetector()
    assert det.update({})["status"] == "insufficient-ranks"
    one = {0: _synthetic_shard(0, steps=[{"step": 1,
                                          "duration_ms": 1.0}])}
    assert det.update(one)["status"] == "insufficient-ranks"
    disjoint = {
        0: _synthetic_shard(0, steps=[{"step": 1, "duration_ms": 1.0}]),
        1: _synthetic_shard(1, steps=[{"step": 9, "duration_ms": 1.0}])}
    assert det.update(disjoint)["status"] == "no-common-steps"


# ----------------------------------------------------------- merged trace ---

def _span(seq, name, t0, dur_ms, kind="span", trace_id=None,
          parent=None, lane=1):
    return {"seq": seq, "name": name, "kind": kind, "trace": trace_id,
            "parent": parent, "t0": t0, "dur_ms": dur_ms, "lane": lane}


def test_clock_offset_alignment_is_monotone_per_rank():
    """Two ranks whose wall clocks disagree by minutes: the merge aligns
    each via its own (t_wall, t_mono) pair, so within a rank the
    original monotonic order is preserved exactly and no event lands at
    a negative timestamp."""
    shards = {
        0: _synthetic_shard(
            0, t_wall=1000.0, t_mono=50.0,
            spans=[_span(i, f"a{i}", 40.0 + i * 0.5, 1.0)
                   for i in range(6)]),
        # rank 1's wall clock is 120s ahead and its mono epoch differs
        1: _synthetic_shard(
            1, t_wall=1120.0, t_mono=9050.0,
            spans=[_span(i, f"b{i}", 9041.0 + i * 0.25, 1.0)
                   for i in range(6)]),
    }
    events = trace.merged_events(shards)
    for rank in (0, 1):
        xs = [e for e in events if e["pid"] == rank and e["ph"] == "X"]
        names = [e["name"] for e in xs]
        assert names == sorted(names, key=lambda n: int(n[1:]))
        stamps = [e["ts"] for e in xs]
        assert stamps == sorted(stamps)
        assert all(ts >= 0 for ts in stamps)
    # per-rank lanes + metadata
    assert {e["pid"] for e in events} == {0, 1}
    meta = [e for e in events if e["ph"] == "M"
            and e["name"] == "process_name"]
    assert {m["pid"] for m in meta} == {0, 1}


def _validate_chrome(payload):
    assert set(payload) >= {"traceEvents", "displayTimeUnit"}
    events = payload["traceEvents"]
    assert events
    for ev in events:
        for key in ("name", "ph", "ts", "pid", "tid"):
            assert key in ev, (key, ev)
        assert ev["ph"] in ("X", "i", "C", "M"), ev
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
        if ev["ph"] == "i":
            assert "s" in ev
    return events


def test_merged_dump_validates_chrome_schema(tmp_path):
    rid = "req-x"
    shards = {
        0: _synthetic_shard(0, spans=[
            _span(0, "request[m]", 10.0, 5.0, kind="request",
                  trace_id=rid),
            _span(1, "queue_wait", 10.0, 1.0, kind="phase",
                  trace_id=rid, parent=0)],
            flights=[{"seq": 0, "t_mono": 10.5, "t_wall": 0.0,
                      "kind": "serving.batch", "point": "m",
                      "label": None}]),
        1: _synthetic_shard(1, spans=[
            _span(0, "trainer.step", 12.0, 30.0, kind="step",
                  trace_id="step-g1-r1-3")]),
    }
    for rank, sh in shards.items():
        with open(fleet.shard_path(tmp_path, rank), "w") as f:
            json.dump(sh, f)
    out = trace.dump(str(tmp_path / "trace.json"), run_dir=tmp_path)
    assert trace.last_dump() == out
    with open(out) as f:
        events = _validate_chrome(json.load(f))
    assert {e["pid"] for e in events} == {0, 1}
    cats = {e.get("cat") for e in events}
    assert {"trace.request", "trace.phase", "trace.step",
            "flight"} <= cats


def test_local_dump_rebases_profiler_events(tmp_path):
    from mxnet_tpu import profiler

    trace.clear()
    profiler.reset()
    profiler.set_config(filename=str(tmp_path / "p.json"))
    profiler.set_state("run")
    try:
        v = mx.nd.ones((4, 4))
        (v * 2).wait_to_read()
    finally:
        profiler.set_state("stop")
    with trace.span("local-span"):
        time.sleep(0.001)
    out = trace.dump(str(tmp_path / "local.json"))
    with open(out) as f:
        events = _validate_chrome(json.load(f))
    names = {e["name"] for e in events}
    assert "local-span" in names
    # profiler op events rode along, on the same (non-negative) timeline
    prof = [e for e in events if e.get("cat") not in
            ("flight", "__metadata") and not str(e.get("cat", ""))
            .startswith("trace.")]
    assert prof and all(e["ts"] >= 0 for e in prof)
    profiler.reset()


# -------------------------------------------------------------- satellites --

def test_diagnose_tracing_section(capsys):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import diagnose

    out = diagnose.check_tracing()
    text = capsys.readouterr().out
    assert "MXNET_TPU_TRACE" in text and "straggler" in text
    assert "effective" in out and out["effective"]["ring"] >= 0
    report = diagnose.collect(echo=False)
    assert "tracing" in report
    assert "straggler" in report["tracing"]


def test_tracing_off_appends_nothing_and_annotates_nothing(monkeypatch):
    """The overhead contract as counts, not a clock. Ring on or off, the
    eager per-op path opens no span. Ring off (``MXNET_TPU_TRACE=0``):
    nothing is appended by a span, a commit, or a serving request, which
    gets no ``RequestTrace`` at all; a span still times and annotates
    (the profiler's host plane needs no ring). Telemetry off: a span
    reads no clock and enters no ``TraceAnnotation``."""
    from mxnet_tpu import telemetry

    appended, annotated = [], []
    real_append = trace._append

    class Annotation:
        def __init__(self, name, **attrs):
            annotated.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(trace, "_append", lambda rec: (
        appended.append(rec["name"]), real_append(rec)))
    monkeypatch.setattr(trace, "_TraceAnnotation", Annotation)

    def chain():
        y = mx.nd.ones((16,))
        for _ in range(8):
            y = y * 1.5 + 1
        y.wait_to_read()

    srv = small_server("off", seed=13)
    x = np.zeros((1, 6), np.float32)
    prev = trace.configure(64)
    try:
        chain()
        assert appended == [] and annotated == []     # never per op
        srv.predict("off", x, timeout=10.0)
        assert "request[off]" in appended              # per request
        trace.configure(0)
        del appended[:], annotated[:]
        chain()
        srv.predict("off", x, timeout=10.0)
        assert trace.request_begin("off") is None
        assert trace.commit("late", 0.0, 1.0) is None
        with trace.span("ring.off") as sp:
            pass
        assert sp.span_id is None and sp.dur_ms >= 0.0
        assert appended == [] and trace.tail() == [] \
            and trace.counts() == {}
        assert annotated == ["ring.off"]
        was = telemetry.set_enabled(False)
        try:
            with trace.span("telemetry.off") as sp:
                pass
        finally:
            telemetry.set_enabled(was)
        assert sp.dur_ms == 0.0 and sp._t0 is None
        assert appended == [] and annotated == ["ring.off"]
    finally:
        trace.configure(prev)
        srv.drain(timeout=10.0)
        srv.stop()


# -------------------------------------------------- end-to-end gang drill ---

def test_gang_tracing_drill(tmp_path):
    """The PR 12 acceptance drill: a supervised 2-rank gang under load
    (trainer steps on both ranks + serving on rank 0, rank 1 slowed by
    a seeded trainer.step delay) must produce

    (a) ONE fleet scrape whose ``mxtpu_fleet_*`` counter sums agree
        exactly with the per-rank scrapes,
    (b) a live straggler detection naming rank 1 on the supervisor
        endpoint (persistent + gang.straggler flight event), and
    (c) a merged ``trace.json`` that validates against the chrome
        trace-event schema with per-rank lanes and at least one serving
        request span carrying all five phases."""
    child = os.path.join(REPO, "tests", "_gang_child.py")
    launch = os.path.join(REPO, "tools", "launch.py")
    run_dir = str(tmp_path / "run")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": REPO + os.pathsep
           + os.environ.get("PYTHONPATH", ""),
           "GC_BASE_DEVICES": "1", "GC_TOTAL": "16", "GC_EPOCH": "16",
           "GC_STEP_SLEEP": "0.03", "GC_STRAGGLE_RANK": "1",
           "GC_STRAGGLE_MS": "300", "GC_METRICS": "1", "GC_SERVE": "1",
           "GC_CKPT_DIR": str(tmp_path / "ckpt"),
           "MXNET_TPU_GANG_BEAT": "0.2"}
    for k in ("MXNET_TPU_FAULTS", "XLA_FLAGS", "MXTPU_GANG_DIR",
              "MXTPU_COORDINATOR", "MXTPU_NUM_WORKERS",
              "MXTPU_WORKER_ID", "MXTPU_GANG_GENERATION"):
        env.pop(k, None)
    proc = subprocess.Popen(
        [sys.executable, launch, "--supervise", "-n", "2",
         "--run-dir", run_dir, "--max-restarts", "0", "--poll", "0.05",
         "--metrics-port", "0", sys.executable, child],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    lines, errs = [], []
    threading.Thread(target=lambda: lines.extend(proc.stdout),
                     daemon=True).start()
    threading.Thread(target=lambda: errs.extend(proc.stderr),
                     daemon=True).start()
    try:
        url = None
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline and url is None:
            for ln in list(lines):
                m = re.search(r"gang metrics: (http://\S+)/metrics", ln)
                if m:
                    url = m.group(1)
            time.sleep(0.1)
        assert url, "supervisor never announced its metrics endpoint"

        # (b) poll the ONE supervisor endpoint for the live straggler
        # verdict while the gang runs
        live = None
        deadline = time.monotonic() + 240.0
        while time.monotonic() < deadline and proc.poll() is None:
            try:
                text = urllib.request.urlopen(
                    url + "/metrics", timeout=5).read().decode()
            except OSError:
                time.sleep(0.2)
                continue
            if _metric(text, "mxtpu_gang_straggler_rank") == 1 \
                    and _metric(text,
                                "mxtpu_gang_straggler_persistent") == 1:
                live = text
                break
            time.sleep(0.2)
        rc = proc.wait(timeout=240.0)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10.0)
    assert rc == 0, f"gang exited {rc}:\n{''.join(errs[-30:])}"
    assert live is not None, \
        f"straggler never flagged live:\n{''.join(lines[-20:])}"
    assert _metric(live, "mxtpu_fleet_ranks") == 2
    assert _metric(live, "mxtpu_gang_straggler_score", rank="1") >= 1.5
    assert _metric(live, "mxtpu_flight_events_total",
                   kind="gang.straggler") >= 1

    # (a) fleet sums == per-rank scrape sums, exactly: each rank froze
    # its own /metrics text + a final shard at exit; re-render the
    # fleet view from the surviving shards and compare counters
    scrapes = []
    for rank in (0, 1):
        with open(os.path.join(run_dir,
                               f"rank-scrape-{rank}.txt")) as f:
            scrapes.append(f.read())
    registry.reset()
    fleet.install(run_dir)
    try:
        fleet_text = export.render_prometheus()
    finally:
        fleet.uninstall()
    checks = [("mxtpu_train_steps_total", {}),
              ("mxtpu_flight_events_total", {"kind": "step.end"}),
              ("mxtpu_serving_requests_total",
               {"model": "gangserve", "outcome": "completed"})]
    for name, labels in checks:
        per_rank = [_metric(s, name, **labels) or 0.0 for s in scrapes]
        fname = "mxtpu_fleet_" + name[len("mxtpu_"):]
        got = _metric(fleet_text, fname, **labels)
        assert got == sum(per_rank) > 0, (name, per_rank, got)
    # both ranks trained every step; only rank 0 served
    assert _metric(fleet_text, "mxtpu_fleet_train_steps_total") == 32.0
    assert _metric(fleet_text, "mxtpu_fleet_serving_requests_total",
                   model="gangserve", outcome="completed") == 4.0

    # (c) the merged trace: chrome-schema-valid, per-rank lanes, and a
    # serving request span showing all five phases
    out = trace.dump(str(tmp_path / "trace.json"), run_dir=run_dir)
    with open(out) as f:
        events = _validate_chrome(json.load(f))
    assert {0, 1} <= {e["pid"] for e in events}
    meta = [e for e in events if e["ph"] == "M"
            and e["name"] == "process_name"]
    assert {m["pid"] for m in meta} >= {0, 1}
    reqs = [e for e in events if e.get("cat") == "trace.request"
            and e["pid"] == 0]
    assert reqs, "no serving request span in the merged trace"
    rid = reqs[0]["args"]["trace"]
    phases = {e["name"] for e in events
              if e.get("cat") == "trace.phase"
              and e.get("args", {}).get("trace") == rid}
    assert phases >= set(PHASES), phases
    # step spans from BOTH ranks landed in their lanes
    for rank in (0, 1):
        assert any(e.get("cat") == "trace.step" and e["pid"] == rank
                   for e in events), rank
