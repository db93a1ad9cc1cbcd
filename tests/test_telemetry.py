"""Unified telemetry layer (mxnet_tpu/telemetry/, docs/OBSERVABILITY.md).

Headline guarantees under test:

* the metrics registry renders valid Prometheus text with bounded label
  cardinality, and a ``/metrics`` scrape on a live serving front end
  carries serving (rps/p99/queue depth), compile (hits/misses/
  compile_ms), watchdog (stalls) and memory (live/peak bytes) series
  whose values AGREE with ``serving.stats()`` / ``compile.stats()``;
* the flight recorder is always-on, constant-size, and its tail is
  embedded in every watchdog crash bundle (``flight.json``) and every
  preemption drain event (``flight_tail``) — an injected hang's bundle
  names the wedged point and carries the preceding step events;
* the compile service captures XLA ``cost_analysis``/``memory_analysis``
  per executable, from which ``ShardedTrainer.step_report()`` derives
  ``mfu_xla`` and the per-step phase breakdown;
* trace integrity: a full ``profiler.dump()`` of a bulked + compile +
  serving run is a valid Chrome-trace envelope with monotone-timestamped
  counter tracks;
* the overhead contract: telemetry-enabled ``opperf --dispatch`` stays
  within noise of disabled (perf-marked A/B gate, like the compile
  service's).
"""
import json
import os
import sys
import time
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import compile as C
from mxnet_tpu import faults, gluon, serving, telemetry, watchdog
from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer
from mxnet_tpu.telemetry import costs, flight, memory, registry, steps

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def small_trainer(seed=0, dim=8, nan_guard=True):
    mx.random.seed(seed)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
    net.initialize(mx.init.Xavier())
    x = mx.nd.array(np.random.RandomState(seed).randn(8, dim)
                    .astype(np.float32))
    y = mx.nd.array(np.random.RandomState(seed + 1).randn(8, 4)
                    .astype(np.float32))
    net(x)
    trainer = ShardedTrainer(net, gluon.loss.L2Loss(), "sgd",
                             {"learning_rate": 0.01},
                             mesh=DeviceMesh({"dp": 1}),
                             nan_guard=nan_guard)
    return trainer, x, y


# ---------------------------------------------------------------- registry --

def test_registry_counter_gauge_histogram_render():
    c = registry.counter("mxtpu_t_reg_total", "a counter",
                         labels=("site",))
    c.inc(2, "a")
    c.inc(1, "a")
    c.inc(5, "b")
    g = registry.gauge("mxtpu_t_reg_gauge", "a gauge")
    g.set(2.5)
    h = registry.histogram("mxtpu_t_reg_hist", "a histogram")
    h.observe(3.0)
    h.observe(700.0)
    text = registry.render_prometheus()
    assert '# TYPE mxtpu_t_reg_total counter' in text
    assert 'mxtpu_t_reg_total{site="a"} 3' in text
    assert 'mxtpu_t_reg_total{site="b"} 5' in text
    assert 'mxtpu_t_reg_gauge 2.5' in text
    assert 'mxtpu_t_reg_hist_bucket{le="5"} 1' in text
    assert 'mxtpu_t_reg_hist_bucket{le="+Inf"} 2' in text
    assert 'mxtpu_t_reg_hist_count 2' in text
    # every non-comment line is "name{labels} value" — parseable
    for line in text.splitlines():
        if line.startswith("#") or not line.strip():
            continue
        name, _, value = line.rpartition(" ")
        assert name and (value == "+Inf" or float(value) is not None)


def test_registry_label_cardinality_bounded():
    c = registry.counter("mxtpu_t_card_total", "bounded", labels=("k",))
    for i in range(registry.MAX_SERIES + 50):
        c.inc(1, f"v{i}")
    series = c.series()
    assert len(series) <= registry.MAX_SERIES + 1
    assert ("__other__",) in series and series[("__other__",)] >= 50


def test_registry_kind_mismatch_rejected():
    registry.counter("mxtpu_t_kind_total", "x")
    with pytest.raises(ValueError):
        registry.gauge("mxtpu_t_kind_total", "x")


# ------------------------------------------------------------------ flight --

def test_flight_ring_constant_size_and_order():
    flight.clear()
    n = flight.size()
    assert n > 0
    for i in range(n + 100):
        flight.rec("t.ring", "p", i)
    tail = flight.tail()
    assert len(tail) == n  # constant memory: never grows past the ring
    seqs = [e["seq"] for e in tail]
    assert seqs == sorted(seqs)
    assert tail[-1]["label"] == n + 99  # newest survives a full lap
    assert flight.counts()["t.ring"] == n + 100
    assert len(flight.tail(5)) == 5
    flight.clear()


def test_flight_disabled_is_noop():
    flight.clear()
    prev = telemetry.set_enabled(False)
    try:
        flight.rec("t.off", "p")
        assert flight.tail() == []
    finally:
        telemetry.set_enabled(prev)
    flight.clear()


# ----------------------------------------------------------- cost / peaks ---

def test_peak_table_per_device_kind():
    assert costs.nominal_peak_tflops("TPU v5p chip") == 459.0
    assert costs.nominal_peak_tflops("TPU v5e") == 197.0
    assert costs.nominal_peak_tflops("TPU v5 lite") == 197.0
    assert costs.nominal_peak_tflops("TPU v6e") == 918.0
    assert costs.nominal_peak_tflops("TPU v4") == 275.0
    # a device the table does not know is an error, never a default
    for kind in ("cpu", "unknown accelerator"):
        with pytest.raises(LookupError, match="no peak"):
            costs.nominal_peak_tflops(kind)


def test_peak_env_override(monkeypatch):
    monkeypatch.setenv("BENCH_PEAK_TFLOPS", "123.5")
    assert costs.peak_tflops(env="BENCH_PEAK_TFLOPS") == 123.5
    monkeypatch.setenv("BENCH_PEAK_TFLOPS", "0")  # 0 = auto-detect
    assert costs.peak_tflops("TPU v5 lite", env="BENCH_PEAK_TFLOPS") \
        == 197.0


def test_mfu_xla_arithmetic():
    # 1 TFLOP/step at 100 steps/s on a 200-TFLOPS part = 0.5 MFU
    assert costs.mfu_xla(1e12, 100.0, devices=1, peak=200.0) \
        == pytest.approx(0.5)
    assert costs.mfu_xla(1e12, 100.0, devices=2, peak=200.0) \
        == pytest.approx(0.25)
    assert costs.mfu_xla(None, 100.0) is None
    assert costs.mfu_xla(1e12, 0.0) is None


def test_trainer_cost_capture_and_step_report():
    trainer, x, y = small_trainer(seed=3)
    for _ in range(3):
        trainer.step(x, y)
    rep = trainer.step_report()
    assert rep is not None and rep["step"] >= 3
    phases = rep["phases"]
    for key in ("data_wait", "h2d", "compute", "optimizer", "sync"):
        assert key in phases
    assert phases["h2d"] >= 0 and phases["compute"] > 0
    # the compile service captured cost_analysis for the step executable
    assert rep.get("flops", 0) > 0
    # the CPU backend has no published peak: utilization is not measured
    assert "mfu_xla" not in rep
    token = trainer._step_fn._token_key
    assert costs.flops_for(token) == rep["flops"]
    # and the step gauges flow into the registry
    snap = telemetry.metrics_snapshot()
    assert snap["mxtpu_step_time_ms"]["series"][0]["value"] > 0
    assert any(s["labels"]["phase"] == "compute"
               for s in snap["mxtpu_step_phase_ms"]["series"])


def test_step_abort_on_injected_fault():
    # earlier tests may have filled the 256-record ring to its cap, where
    # "len grows by one" can never hold — start from a known-empty ring
    # (regression guard for the full-suite order dependency)
    steps.reset()
    trainer, x, y = small_trainer(seed=4)
    trainer.step(x, y)
    before = len(steps.history())
    faults.configure("trainer.step:raise@1", seed=0)
    try:
        with pytest.raises(faults.InjectedFault):
            trainer.step(x, y)
    finally:
        faults.reset()
    # the raising step abandoned its record instead of logging a torn one
    assert len(steps.history()) == before
    trainer.step(x, y)
    assert len(steps.history()) == before + 1


def test_step_history_semantics_at_ring_cap():
    """The abandoned-record contract must hold even when the history ring
    is already at its maxlen cap — the exact state the full suite leaves
    behind (the pre-fix flake: len(history()) can't grow at the cap, so
    assertions must key on record identity, not length)."""
    steps.reset()
    trainer, x, y = small_trainer(seed=4)
    trainer.step(x, y)
    template = steps.last()
    cap = steps._HIST.maxlen
    while len(steps._HIST) < cap:
        steps._HIST.append(dict(template, step=len(steps._HIST)))
    last_before = steps.last()
    faults.configure("trainer.step:raise@1", seed=0)
    try:
        with pytest.raises(faults.InjectedFault):
            trainer.step(x, y)
    finally:
        faults.reset()
    # aborted step left no record: the newest entry is unchanged
    assert steps.last() == last_before
    trainer.step(x, y)
    assert len(steps.history()) == cap  # ring stays at cap...
    assert steps.last() != last_before  # ...but the new record landed
    steps.reset()


def test_memory_sample_and_oom_report(tmp_path, monkeypatch):
    recs = memory.sample(reason="test")
    assert recs, "memory sample must produce at least a host record"
    for r in recs:
        assert r["live_bytes"] >= 0 and r["peak_bytes"] >= r["live_bytes"]
    # with a cache dir the trainer compiles AOT -> memory_analysis lands
    d = str(tmp_path / "cache")
    monkeypatch.setenv("MXNET_TPU_CACHE_DIR", d)
    C.configure(cache_dir=d)
    try:
        trainer, x, y = small_trainer(seed=11)
        trainer.step(x, y)
        top = memory.top_executables(5)
        assert top and top[0]["resident_bytes"] > 0
        assert any(r["site"] == "trainer" for r in top)
        rep = memory.oom_report()
        assert rep["top_executables"] and rep["devices"] is not None
        assert "trainer" in rep["aggregate"]
    finally:
        C.configure(cache_dir=None)


# ------------------------------------------------------- /metrics endpoint --

def _scrape(url, path="/metrics"):
    with urllib.request.urlopen(url + path, timeout=10) as resp:
        return resp.read().decode(), resp.headers.get("Content-Type")


def _metric_value(text, name, **labels):
    for line in text.splitlines():
        if not line.startswith(name):
            continue
        if line.startswith(name + "{") or line.startswith(name + " "):
            if all(f'{k}="{v}"' in line for k, v in labels.items()):
                return float(line.rsplit(" ", 1)[1])
    return None


def test_http_metrics_agree_with_stats():
    """Acceptance: curl /metrics on a running ModelServer returns
    Prometheus text with serving, compile, watchdog and memory series
    whose values agree with serving.stats()/compile.stats()."""
    mx.random.seed(21)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(3))
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((2, 6)))
    container = serving.ModelContainer()
    container.add_block("tel_model", net, example_shape=(6,),
                        buckets=(2, 4))
    server = serving.ModelServer(container, max_wait_ms=1.0).start()
    try:
        server.warmup()
        front = serving.HttpFrontEnd(server).start()
        try:
            rows = np.random.RandomState(0).randn(1, 6).astype(np.float32)
            for _ in range(12):
                server.predict("tel_model", rows, timeout=10.0)
            text, ctype = _scrape(front.url)
            assert ctype.startswith("text/plain")
            st = server.stats()["models"]["tel_model"]
            # serving series agree with server.stats()
            assert _metric_value(text, "mxtpu_serving_requests_total",
                                 model="tel_model",
                                 outcome="completed") == st["completed"]
            assert _metric_value(text, "mxtpu_serving_queue_depth",
                                 model="tel_model") == st["queue_depth"]
            assert _metric_value(text, "mxtpu_serving_latency_ms",
                                 model="tel_model",
                                 quantile="p99") == pytest.approx(
                                     st["p99_ms"], rel=0.01)
            if st["rps"]:
                assert _metric_value(text, "mxtpu_serving_rps",
                                     model="tel_model") > 0
            # compile series agree with compile.stats()
            cstats = C.stats()["serving"]
            assert _metric_value(text, "mxtpu_compile_cache_hits_total",
                                 site="serving") == cstats["hits"]
            assert _metric_value(text, "mxtpu_compile_cache_misses_total",
                                 site="serving") == cstats["misses"]
            assert _metric_value(text, "mxtpu_compile_ms_total",
                                 site="serving") == pytest.approx(
                                     cstats["compile_ms"], rel=0.01)
            # watchdog + memory series present
            assert _metric_value(text,
                                 "mxtpu_watchdog_stalls_total") is not None
            assert [l for l in text.splitlines()
                    if l.startswith("mxtpu_device_memory_live_bytes")]
            # the JSON twin parses and carries the same families
            jtext, jtype = _scrape(front.url, "/metrics.json")
            snap = json.loads(jtext)
            assert jtype.startswith("application/json")
            assert "mxtpu_serving_requests_total" in snap
        finally:
            front.close()
    finally:
        server.drain(timeout=10.0)
        server.stop()


def test_standalone_metrics_server():
    from mxnet_tpu.telemetry import MetricsServer

    srv = MetricsServer(port=0).start()
    try:
        text, ctype = _scrape(srv.url)
        assert ctype.startswith("text/plain")
        assert "mxtpu_flight_ring_size" in text
        health, _ = _scrape(srv.url, "/healthz")
        assert json.loads(health)["status"] == "ok"
    finally:
        srv.close()


# --------------------------------------------- crash bundles + drain tails --

def test_watchdog_bundle_embeds_flight_tail(tmp_path):
    trainer, x, y = small_trainer(seed=7)
    trainer.step(x, y)
    trainer.step(x, y)
    hang = 1.2
    watchdog.configure({"trainer.step": 0.4},
                       crash_dir=str(tmp_path / "crash"), interval=0.1)
    faults.configure(f"trainer.step:hang@1:{hang}", seed=0)
    try:
        with pytest.raises(watchdog.StallError) as ei:
            trainer.step(x, y)
    finally:
        faults.reset()
        watchdog.configure_from_env()
    bundle = ei.value.bundle
    assert bundle and os.path.isdir(bundle)
    with open(os.path.join(bundle, "flight.json")) as f:
        tail = json.load(f)
    assert tail, "flight tail must never be empty after trainer steps"
    # the tail names the wedged point and carries the preceding steps
    assert any(e["kind"] == "watchdog.stall"
               and e["point"] == "trainer.step" for e in tail)
    assert any(e["kind"] == "step.begin" for e in tail)
    assert any(e["kind"] == "step.end" for e in tail)
    # OOM-forensics memory section rides in the report
    with open(os.path.join(bundle, "report.json")) as f:
        rep = json.load(f)
    assert "memory" in rep and "devices" in rep["memory"]
    time.sleep(hang + 0.3)  # let the abandoned waiter drain out


def test_drain_event_embeds_flight_tail(tmp_path):
    from mxnet_tpu import preempt

    flight.rec("t.drain", "p", "before-drain")
    preempt.request("telemetry-test")
    try:
        ev = preempt.drain(save=False, exit=False,
                           directory=str(tmp_path))
    finally:
        preempt.clear()
    assert ev["flight_tail"], "drain event must embed the flight tail"
    kinds = {e["kind"] for e in ev["flight_tail"]}
    assert "preempt.request" in kinds
    # and the on-disk record carries it too
    rec = preempt.last_drain(directory=str(tmp_path))
    assert rec and rec["flight_tail"]


# --------------------------------------------------------- trace integrity --

def test_trace_integrity_bulk_compile_serving(tmp_path):
    """Load a full profiler.dump() of a bulked + compile + serving run:
    valid Chrome-trace envelope, every counter track monotone-timestamped."""
    fname = str(tmp_path / "trace.json")
    mx.profiler.reset()
    mx.profiler.set_config(filename=fname, aggregate_stats=True)
    mx.profiler.set_state("run")
    try:
        # bulked eager segment
        with mx.engine.bulk(4):
            v = mx.nd.ones((8, 8))
            for _ in range(6):
                v = v * 1.01 + 0.1
            v.wait_to_read()
        # compile-service traffic
        import jax.numpy as jnp

        fn = C.jit(lambda a: a * 3, site="svc-tele-prof",
                   token=("tele-prof", 1))
        fn(jnp.ones((4,))).block_until_ready()  # noqa: unbounded-sync — test code
        fn(jnp.ones((4,))).block_until_ready()  # noqa: unbounded-sync — test code
        # serving traffic
        mx.random.seed(31)
        net = gluon.nn.Dense(4, in_units=6)
        net.initialize(mx.init.Xavier())
        net(mx.nd.zeros((2, 6)))
        cont = serving.ModelContainer()
        cont.add_block("tel_trace", net, example_shape=(6,),
                       buckets=(2,))
        srv = serving.ModelServer(cont, max_wait_ms=1.0).start()
        try:
            srv.warmup()
            rows = np.zeros((1, 6), np.float32)
            for _ in range(3):
                srv.predict("tel_trace", rows, timeout=10.0)
        finally:
            srv.drain(timeout=10.0)
            srv.stop()
    finally:
        mx.profiler.set_state("stop")
    mx.profiler.dump()
    with open(fname) as f:
        payload = json.load(f)
    events = payload["traceEvents"]
    assert events and payload["displayTimeUnit"] == "ms"
    counters = {}
    for ev in events:
        # the universal envelope: every event carries these fields
        for key in ("name", "cat", "ph", "ts", "pid", "tid"):
            assert key in ev, (key, ev)
        assert ev["ph"] in ("X", "i", "C")
        assert "dur" in ev
        if ev["ph"] == "C":
            counters.setdefault(ev["name"], []).append(ev["ts"])
    # every counter track is monotone-timestamped
    assert counters, "expected counter tracks in the trace"
    for name, stamps in counters.items():
        assert stamps == sorted(stamps), f"counter {name} not monotone"
    names = {e["name"] for e in events}
    assert any(n.startswith("BulkSegment[") for n in names)
    assert "serving[tel_trace]" in names
    assert any(n.startswith("compile_cache.service.") for n in names)
    mx.profiler.reset()


# ------------------------------------------------------------- satellites ---

def test_telemetry_describe_and_snapshot():
    d = telemetry.describe()
    assert d["enabled"] in (True, False)
    assert d["flight_ring"] == flight.size()
    snap = telemetry.metrics_snapshot()
    assert "mxtpu_flight_ring_size" in snap


# ------------------------------------------------------------ perf guard ---

@pytest.mark.perf
def test_telemetry_dispatch_overhead_within_noise():
    """CI guard: telemetry-on must not tax the eager per-op hot path —
    opperf --dispatch ns/op with push instrumentation enabled stays
    within noise of disabled (the PR 7-style A/B gate)."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import opperf

    kw = dict(chain_len=8, bulk=8, size=256, iters=60, warmup=10, trials=3)
    on = opperf.bench_dispatch(**kw)
    prev = telemetry.set_enabled(False)
    try:
        off = opperf.bench_dispatch(**kw)
    finally:
        telemetry.set_enabled(prev)
    # generous envelope: CPU CI timing is noisy; the real per-sync cost
    # is one ring-slot write (~1us per CHAIN, not per op) — the guard
    # catches order-of-magnitude regressions (per-op recording, locks,
    # allocation storms)
    for k in ("unbulked_ns_per_op", "bulked_ns_per_op"):
        assert on[k] <= off[k] * 1.6 + 2000.0, (k, on, off)
