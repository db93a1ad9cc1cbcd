"""Serving fleet: router policies, autoscaler, serving-mode supervision,
zero-downtime rollout (mxnet_tpu/serving/fleet.py + worker.py,
docs/SERVING.md "Fleet").

Headline guarantees under test:

* routing — least-loaded picks the shallow queue (falling back to
  round-robin without depth data), the consistent-hash ring keeps
  placements stable under worker-set change;
* autoscaling — the decision core scales up after K sustained pressure
  samples, down on sustained idle, respects min/max bounds and the
  cooldown (table-tested on synthetic gauge series), and the LIVE loop
  demonstrably grows 1→2 under injected load and shrinks back on idle
  with the decisions visible in the gauges and the diagnose report;
* serving-mode supervision — a crashed slot restarts individually with
  backoff, a deliberately drained slot (exit 75) is retired, a restart
  budget parks a flapping slot as failed;
* rollout — the health gate refuses an unwarmed worker (pending
  compiles) leaving the old generation serving; the acceptance drill
  rolls a live fleet mid-load with ZERO dropped admitted requests and
  ZERO recompiles in the new generation (warm from the disk cache);
* hedging — hedged_call fires only past the threshold, first answer
  wins, a fast failure takes ordinary failover (never re-issued), and
  the HedgeGovernor's threshold/plan/straggler-flag/canary-probe and
  remote-penalty arithmetic table-test;
* multi-host — the hosts= grammar normalizes (and rejects) placement
  specs, locality-aware ordering spills to remote only past the
  measured penalty, and a live 2-pseudo-host fleet places slots
  round-robin with per-host run dirs merged at scrape;
* QoS — a provably-unmeetable deadline drops BEFORE consuming a batch
  slot; the prediction cache serves copies, stays bounded, and a live
  weight swap (model-bus version flip) can never serve stale data;
* loadgen — the keep-alive HTTP client reuses one connection per worker
  thread (connect time reported separately from request time).
"""
import json
import os
import signal
import sys
import threading
import time

import numpy as np
import pytest

from mxnet_tpu import elastic
from mxnet_tpu.serving import fleet as fleet_mod
from mxnet_tpu.serving import worker as worker_mod
from mxnet_tpu.serving.fleet import (Autoscaler, HashRing, ServingFleet,
                                     gate_ready, order_candidates,
                                     worker_metrics)

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")
if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)


def _py(body):
    return [sys.executable, "-c", body]


# --------------------------------------------------------------- config ----

def test_fleet_config_grammar():
    cfg = fleet_mod._parse("min:2,max:6;up_queue:8,up_p99_ms:50.5,"
                           "k:2,idle_rps:0.5,idle_k:4,cooldown:3,"
                           "policy:hash,beat:0.1")
    assert cfg["min"] == 2 and cfg["max"] == 6
    assert cfg["up_queue"] == 8 and cfg["up_p99_ms"] == 50.5
    assert cfg["k"] == 2 and cfg["idle_k"] == 4
    assert cfg["policy"] == "hash" and cfg["beat"] == 0.1
    # untouched keys keep their defaults
    assert cfg["interval"] == fleet_mod.DEFAULTS["interval"]


def test_fleet_config_bad_specs():
    with pytest.raises(ValueError, match="unknown fleet option"):
        fleet_mod._parse("mni:2")
    with pytest.raises(ValueError, match="unknown fleet policy"):
        fleet_mod._parse("policy:fastest")
    with pytest.raises(ValueError, match="expected <option>:<value>"):
        fleet_mod._parse("min")
    with pytest.raises(ValueError, match="max .* < min"):
        fleet_mod._parse("min:4,max:2")
    with pytest.raises(ValueError, match=">= 1"):
        fleet_mod._parse("min:0")


# -------------------------------------------------------------- routing ----

def test_hash_ring_stable_under_worker_set_change():
    ring = HashRing([0, 1, 2, 3])
    keys = [f"model{i}" for i in range(200)]
    before = {k: ring.lookup(k) for k in keys}
    assert set(before.values()) == {0, 1, 2, 3}  # all slots own keys
    ring.rebuild([0, 1, 3])  # slot 2 dies
    after = {k: ring.lookup(k) for k in keys}
    moved = [k for k in keys if before[k] != after[k]]
    # ONLY the dead slot's keys may move — the consistent-hash property
    assert all(before[k] == 2 for k in moved)
    assert all(after[k] != 2 for k in keys)
    # allowed= restricts without rebuilding (the router's live filter)
    ring2 = HashRing([0, 1, 2, 3])
    assert ring2.lookup("modelX", allowed={1}) == 1


def test_least_loaded_picks_the_shallow_queue():
    depths = {0: 7.0, 1: 0.0, 2: 12.0}
    order = order_candidates("least_loaded", "m", [0, 1, 2],
                             depths=depths, rr=0)
    assert order[0] == 1 and order[-1] == 2
    # unknown depth counts as an empty queue (a fresh worker)
    order = order_candidates("least_loaded", "m", [0, 1, 2],
                             depths={0: 5.0}, rr=0)
    assert order[-1] == 0
    # no depth data at all -> pure round-robin rotation
    a = order_candidates("least_loaded", "m", [0, 1, 2], depths={}, rr=1)
    b = order_candidates("least_loaded", "m", [0, 1, 2], depths={}, rr=2)
    assert a == [1, 2, 0] and b == [2, 0, 1]


def test_hash_policy_orders_owner_first():
    ring = HashRing([0, 1, 2])
    owner = ring.lookup("modelA")
    order = order_candidates("hash", "modelA", [0, 1, 2], rr=5, ring=ring)
    assert order[0] == owner and sorted(order) == [0, 1, 2]
    assert order_candidates("round_robin", "m", [], rr=3) == []


# ------------------------------------------------------------ autoscaler ----

def _scaler(**over):
    cfg = dict(fleet_mod.DEFAULTS)
    cfg.update({"min": 1, "max": 4, "up_queue": 10, "up_p99_ms": 100.0,
                "up_fill": 0.99, "k": 3, "idle_rps": 1.0, "idle_k": 2,
                "cooldown": 5.0})
    cfg.update(over)
    return Autoscaler(cfg)


def test_autoscaler_scales_up_after_k_sustained_samples():
    sc = _scaler()
    hot = {"queue_depth": 50, "p99_ms": 5.0, "fill": 0.5, "rps": 100.0}
    assert sc.decide(hot, workers=1, now=0.0)[0] is None
    assert sc.decide(hot, workers=1, now=1.0)[0] is None
    direction, rec = sc.decide(hot, workers=1, now=2.0)
    assert direction == "up" and "queue" in rec["reason"]
    # a non-pressure sample resets the streak
    sc2 = _scaler()
    sc2.decide(hot, 1, now=0.0)
    sc2.decide({"queue_depth": 0, "rps": 100.0}, 1, now=1.0)
    sc2.decide(hot, 1, now=2.0)
    assert sc2.decide(hot, 1, now=3.0)[0] is None  # streak restarted


def test_autoscaler_cooldown_and_bounds():
    sc = _scaler(k=1, cooldown=10.0)
    hot = {"queue_depth": 99, "rps": 50.0}
    assert sc.decide(hot, 1, now=0.0)[0] == "up"
    # cooling down: pressure persists but nothing fires
    direction, rec = sc.decide(hot, 2, now=1.0)
    assert direction is None and rec["reason"] == "cooling down"
    # past the cooldown it fires again
    assert sc.decide(hot, 2, now=11.0)[0] == "up"
    # at max: held, named
    sc3 = _scaler(k=1)
    d, rec = sc3.decide(hot, 4, now=0.0)
    assert d is None and "at max" in rec["reason"]


def test_autoscaler_scales_down_on_sustained_idle():
    sc = _scaler(idle_k=3, cooldown=0.0)
    idle = {"queue_depth": 0, "p99_ms": 2.0, "fill": 0.2, "rps": 0.0}
    assert sc.decide(idle, 3, now=0.0)[0] is None
    assert sc.decide(idle, 3, now=1.0)[0] is None
    d, rec = sc.decide(idle, 3, now=2.0)
    assert d == "down" and "idle" in rec["reason"]
    # at min: held
    sc2 = _scaler(idle_k=1)
    d, rec = sc2.decide(idle, 1, now=0.0)
    assert d is None and "at min" in rec["reason"]
    # busy samples are not idle (rps above the floor)
    sc3 = _scaler(idle_k=1)
    assert sc3.decide({"queue_depth": 0, "rps": 500.0}, 3,
                      now=0.0)[0] is None
    assert sc.describe()["decisions"]["down"] == 1


# ----------------------------------------------------- gate + shard files ---

def test_health_gate_refuses_unwarmed_announce():
    ready = {"state": "serving", "ready": True, "pending_compiles": 0}
    assert gate_ready(ready)
    assert not gate_ready(None)
    assert not gate_ready({})
    assert not gate_ready(dict(ready, pending_compiles=5, ready=False))
    assert not gate_ready(dict(ready, pending_compiles=3))
    assert not gate_ready(dict(ready, state="drained"))


def test_worker_metrics_reads_serving_gauges_from_shards(tmp_path):
    shard = {
        "version": 1, "rank": 7, "generation": 2, "pid": 1, "seq": 1,
        "t_wall": time.time(), "t_mono": 0.0,
        "metrics": {
            "mxtpu_serving_queue_depth": {
                "kind": "gauge", "labels": ["model"],
                "series": [{"labels": {"model": "a"}, "value": 3.0},
                           {"labels": {"model": "b"}, "value": 2.0}]},
            "mxtpu_serving_latency_ms": {
                "kind": "gauge", "labels": ["model", "quantile"],
                "series": [{"labels": {"model": "a", "quantile": "p99"},
                            "value": 12.5},
                           {"labels": {"model": "a", "quantile": "p50"},
                            "value": 4.0}]},
            "mxtpu_serving_requests_total": {
                "kind": "counter", "labels": ["model", "outcome"],
                "series": [{"labels": {"model": "a",
                                       "outcome": "completed"},
                            "value": 41.0}]},
        }}
    path = tmp_path / "telemetry-rank-7.json"
    path.write_text(json.dumps(shard))
    m = worker_metrics(tmp_path)
    assert m[7]["queue_depth"] == 5.0       # summed over models
    assert m[7]["p99_ms"] == 12.5           # p99 only, p50 ignored
    assert m[7]["completed"] == 41.0
    assert m[7]["generation"] == 2
    # slots filter
    assert worker_metrics(tmp_path, slots={3}) == {}
    # torn shard skipped
    (tmp_path / "telemetry-rank-8.json").write_text("{\"rank\": 8")
    assert 8 not in worker_metrics(tmp_path)


def test_read_workers_skips_torn_announces(tmp_path):
    worker_mod._write_announce(tmp_path, 3, {"slot": 3, "state": "x"})
    (tmp_path / "worker-4.json").write_text("{nope")
    out = worker_mod.read_workers(tmp_path)
    assert list(out) == [3]


def test_spec_roundtrip_demo_and_checkpoint(tmp_path):
    import mxnet_tpu as mx

    spec = worker_mod.demo_spec(models=2, dim=8, seed=3, buckets=(2, 4))
    # a checkpoint entry next to the demo pair
    x = mx.sym.var("data")
    sym = mx.sym.FullyConnected(x, num_hidden=4, name="fl_fc")
    rng = np.random.RandomState(0)
    args = {"fl_fc_weight": mx.nd.array(rng.randn(4, 8).astype("float32")),
            "fl_fc_bias": mx.nd.zeros((4,))}
    mx.model.save_checkpoint(str(tmp_path / "ck"), 2, sym, args, {})
    spec.append({"kind": "checkpoint", "name": "ckm", "prefix": "ck",
                 "epoch": 2, "example_shape": [8], "buckets": [2, 4]})
    worker_mod.write_spec(tmp_path, spec)
    container, loaded = worker_mod.load_container(tmp_path)
    assert container.names() == ["model0", "model1", "ckm"]
    assert container["model0"].buckets == (2, 4)
    # demo models are seed-deterministic: a second build bit-matches
    container2, _ = worker_mod.load_container(tmp_path)
    xq = rng.randn(2, 8).astype("float32")
    a = container["model0"].run(xq)[0]
    b = container2["model0"].run(xq)[0]
    np.testing.assert_array_equal(a, b)
    # malformed specs fail loudly, naming the entry
    worker_mod.write_spec(tmp_path, [{"kind": "zeppelin", "name": "z"}])
    with pytest.raises(ValueError, match="unknown kind 'zeppelin'"):
        worker_mod.load_container(tmp_path)
    with pytest.raises(ValueError, match="no serving spec"):
        worker_mod.load_container(tmp_path / "nope")


# ------------------------------------------------- serving supervision -----

def _sup(run_dir, body, **kw):
    kw.setdefault("backoff", 0.05)
    kw.setdefault("grace", 5.0)
    kw.setdefault("dead_after", 0)
    return elastic.ServingSupervisor(
        lambda slot, gen: _py(body), run_dir, **kw)


def _poll_until(sup, pred, timeout=20.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        census = sup.poll()
        if pred(census):
            return census
        time.sleep(0.05)
    raise AssertionError(f"condition not reached; census={sup.census()} "
                         f"events={sup.events}")


def test_serving_supervisor_restarts_crashed_slot(tmp_path):
    """An unrequested death restarts the SLOT individually (not a gang):
    first spawn crashes with a real error code, the restart stays up."""
    marker = tmp_path / "flag"
    body = ("import os, sys, time\n"
            f"m = {str(marker)!r}\n"
            "if os.path.exists(m):\n"
            "    time.sleep(60)\n"
            "open(m, 'w').close()\n"
            "sys.exit(7)\n")
    sup = _sup(tmp_path / "run", body)
    sup.spawn(0, 1)
    census = _poll_until(
        sup, lambda c: c.get(0, {}).get("alive")
        and c[0].get("restarts") == 1)
    assert census[0]["generation"] == 1
    kinds = [e["kind"] for e in sup.events]
    assert "restart" in kinds
    restart = next(e for e in sup.events if e["kind"] == "restart")
    assert restart["exit_code"] == 7
    assert sup.restarts_total == 1
    assert sup.stop_all(graceful=False)


def test_serving_supervisor_deliberate_drain_retires_slot(tmp_path):
    """drain_slot -> SIGTERM -> exit 75 removes the slot (rollout /
    scale-down semantics) instead of restarting it."""
    armed = tmp_path / "armed"
    body = ("import signal, sys, time\n"
            "signal.signal(signal.SIGTERM, lambda *a: sys.exit(75))\n"
            f"open({str(armed)!r}, 'w').close()\n"
            "while True:\n"
            "    time.sleep(0.05)\n")
    sup = _sup(tmp_path / "run", body)
    sup.spawn(4, 2)
    # wait for the handler to be armed — a SIGTERM into interpreter
    # startup would take the default disposition (exit 143) instead
    _poll_until(sup, lambda c: armed.exists())
    sup.drain_slot(4, reason="test-retire")
    _poll_until(sup, lambda c: 4 not in c)
    ev = next(e for e in sup.events if e["kind"] == "drained")
    assert ev["slot"] == 4 and ev["exit_code"] == 75
    assert ev["generation"] == 2
    assert sup.drained_total == 1 and sup.restarts_total == 0


def test_serving_supervisor_restart_budget_parks_slot(tmp_path):
    sup = _sup(tmp_path / "run", "import sys; sys.exit(5)",
               max_restarts=2, backoff=0.01)
    sup.spawn(0, 1)
    census = _poll_until(
        sup, lambda c: c.get(0, {}).get("state") == "failed")
    assert census[0]["restarts"] == 2
    assert any(e["kind"] == "slot_failed" for e in sup.events)
    desc = sup.describe()
    assert desc["restarts_total"] == 2
    sup.stop_all(graceful=False)


# --------------------------------------------------------- hedging -------

def _gov(**over):
    cfg = dict(fleet_mod.DEFAULTS)
    cfg.update(over)
    return fleet_mod.HedgeGovernor(cfg)


def test_hedged_call_fires_only_past_threshold():
    """A primary that answers inside the threshold is returned as-is —
    the hedge closure is never invoked."""
    hedged = []
    rec = fleet_mod.hedged_call(lambda: "fast",
                                lambda: hedged.append(1) or "h",
                                hedge_after=0.5)
    assert rec["winner"] == "primary" and rec["value"] == "fast"
    assert rec["hedged"] is False and not hedged


def test_hedged_call_first_answer_wins():
    """Past the threshold the hedge is issued and the FIRST successful
    answer wins; the slow loser is abandoned, not awaited."""
    t0 = time.monotonic()
    rec = fleet_mod.hedged_call(lambda: time.sleep(2.0) or "slow",
                                lambda: "rescue",
                                hedge_after=0.02)
    assert rec["winner"] == "hedge" and rec["value"] == "rescue"
    assert rec["hedged"] is True
    assert time.monotonic() - t0 < 1.5  # did not wait for the loser


def test_hedged_call_fast_failure_is_not_hedged():
    """A primary that FAILS before the threshold takes the ordinary
    failover path — hedging never re-issues after a failure."""
    hedged = []

    def boom():
        raise ConnectionRefusedError("dead worker")

    rec = fleet_mod.hedged_call(boom, lambda: hedged.append(1) or "h",
                                hedge_after=0.5)
    assert rec["winner"] is None and rec["hedged"] is False
    assert isinstance(rec["primary_error"], ConnectionRefusedError)
    assert not hedged


def test_hedged_call_late_primary_error_waits_for_inflight_hedge():
    """Once the hedge is in flight, a primary failure (e.g. a timeout)
    legally waits for the ALREADY-ISSUED hedge — nothing new is issued
    after a failure, and both failing surfaces the primary's error."""
    def slow_fail():
        time.sleep(0.05)
        raise TimeoutError("upstream timeout")

    rec = fleet_mod.hedged_call(slow_fail,
                                lambda: time.sleep(0.1) or 42,
                                hedge_after=0.01)
    assert rec["winner"] == "hedge" and rec["value"] == 42

    def fail_too():
        time.sleep(0.05)
        raise ConnectionResetError("hedge died too")

    rec = fleet_mod.hedged_call(slow_fail, fail_too, hedge_after=0.01)
    assert rec["winner"] is None and rec["hedged"] is True
    assert isinstance(rec["primary_error"], TimeoutError)
    assert isinstance(rec["hedge_error"], ConnectionResetError)


def test_hedge_governor_threshold_table():
    g = _gov(hedge_min_ms=20.0, hedge_factor=2.0, timeout_ms=30000.0)
    assert g.threshold(0) is None          # <16 samples: signal too thin
    for _ in range(32):
        g.note(0, 10.0)
    assert g.threshold(0) == 20.0          # p99*factor floored at min_ms
    for _ in range(32):
        g.note(0, 100.0)
    assert g.threshold(0) == 200.0         # p99 100 x factor 2
    # capped at half the upstream timeout
    assert _gov(timeout_ms=300.0).threshold(0) is None
    g2 = _gov(hedge_min_ms=20.0, hedge_factor=2.0, timeout_ms=300.0)
    for _ in range(32):
        g2.note(0, 100.0)
    assert g2.threshold(0) == 150.0
    # a flagged straggler gets the floor immediately, no ring needed
    g3 = _gov(hedge_min_ms=25.0)
    g3.stragglers = frozenset({3})
    assert g3.threshold(3) == 25.0


def test_hedge_governor_plan_table():
    ep = {0: "http://a", 1: "http://b"}.get
    g = _gov(hedge=0)
    for _ in range(32):
        g.note(0, 10.0)
    assert g.plan(0, [0, 1], ep) == (None, None)      # hedging off
    g = _gov(hedge=1, hedge_min_ms=20.0)
    assert g.plan(0, [0, 1], ep) == (None, None)      # thin signal
    for _ in range(32):
        g.note(0, 10.0)
    assert g.plan(0, [0], ep) == (None, None)         # no second cand
    assert g.plan(0, [0, 2], ep) == (None, None)      # no live endpoint
    cand, thr = g.plan(0, [0, 1], ep)
    assert cand == 1 and thr == 20.0


def test_hedge_governor_straggler_flag_reorder_and_probe():
    g = _gov()
    for _ in range(8):
        g.note(0, 10.0)
        g.note(1, 150.0)
    # the flag needs `persist` consecutive verdicts, not one
    assert g.update_stragglers([0, 1]) == frozenset()
    assert g.update_stragglers([0, 1]) == frozenset()
    assert g.update_stragglers([0, 1]) == frozenset({1})
    # flagged slots stable-move to the tail of every candidate order...
    assert g.reorder([1, 0], rr=1) == [0, 1]
    assert g.reorder([1, 0, 2], rr=7) == [0, 2, 1]
    # ...EXCEPT the canary probe, which keeps its natural placement
    assert g.reorder([1, 0], rr=0) == [1, 0]
    assert g.reorder([1, 0], rr=g.PROBE_EVERY) == [1, 0]
    # recovery: the probes' fast answers decay the EWMA and the flag
    # clears on the next interval
    for _ in range(40):
        g.note(1, 10.0)
    assert g.update_stragglers([0, 1]) == frozenset()
    assert g.reorder([1, 0], rr=1) == [1, 0]


def test_hedge_governor_remote_penalty():
    g = _gov(hedge=1)
    g._locality_of = lambda slot: "remote" if slot >= 2 else "local"
    assert g.remote_penalty() == 0.0       # no signal yet
    g.note(0, 10.0)
    assert g.remote_penalty() == 0.0       # one locality only
    g.note(2, 30.0)
    assert g.remote_penalty() == pytest.approx(2.0)  # (30-10)/10


# ------------------------------------------------------- multi-host -------

def test_normalize_hosts_grammar():
    hosts = fleet_mod.normalize_hosts(
        ["local", "gpu@farm-3", {"name": "b", "locality": "local"},
         {"ssh": "edge-1", "advertise": "10.0.0.7",
          "env": {"X": "1"}, "cwd": "/srv/repo"}])
    local, farm, b, edge = hosts
    assert local == {"name": "local", "ssh": None, "cwd": None,
                     "env": {}, "advertise": "127.0.0.1",
                     "locality": "local"}
    assert farm["ssh"] == "gpu@farm-3" and farm["name"] == "gpu_farm-3"
    assert farm["locality"] == "remote" and farm["advertise"] == "farm-3"
    assert b["ssh"] is None and b["locality"] == "local"
    assert edge["advertise"] == "10.0.0.7" and edge["env"] == {"X": "1"}
    assert edge["cwd"] == "/srv/repo" and edge["locality"] == "remote"


def test_normalize_hosts_rejects_bad_specs():
    with pytest.raises(ValueError, match="expected a name/ssh string"):
        fleet_mod.normalize_hosts([42])
    with pytest.raises(ValueError, match="bad fleet host spec keys"):
        fleet_mod.normalize_hosts([{"hostname": "a"}])
    with pytest.raises(ValueError, match="duplicate fleet host name"):
        fleet_mod.normalize_hosts(["local", {"name": "local"}])
    with pytest.raises(ValueError, match="bad fleet host locality"):
        fleet_mod.normalize_hosts([{"name": "a", "locality": "ici"}])


def test_order_candidates_locality_and_penalty():
    loc = {0: "local", 1: "remote"}
    # an idle remote worker beats a queued local one while the measured
    # penalty is small...
    order = order_candidates("least_loaded", "m", [0, 1],
                             depths={0: 2.0, 1: 0.0}, rr=0,
                             localities=loc, remote_penalty=0.0)
    assert order[0] == 1
    # ...and loses once the remote hop costs more than the queue saves
    order = order_candidates("least_loaded", "m", [0, 1],
                             depths={0: 2.0, 1: 0.0}, rr=0,
                             localities=loc, remote_penalty=3.0)
    assert order[0] == 0
    # round_robin / hash stable-partition local-first
    order = order_candidates("round_robin", "m", [0, 1, 2], rr=0,
                             localities={0: "remote", 1: "local",
                                         2: "local"})
    assert order == [1, 2, 0]


# ------------------------------------------------- deadline / cache -------

def _tiny_server(**kw):
    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.gluon import nn

    mx.random.seed(7)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((2, 8)))
    container = serving.ModelContainer()
    container.add_block("m", net, example_shape=(8,), buckets=(2, 4))
    return serving.ModelServer(container, max_wait_ms=1.0, **kw).start()


def test_deadline_drop_before_batch_slot():
    """A provably-unmeetable deadline is dropped with DeadlineExceeded
    BEFORE consuming a batch slot: the batches counter does not move
    for the doomed request and the drop is counted by `where`."""
    from mxnet_tpu import serving

    server = _tiny_server()
    try:
        server.warmup()
        x = np.random.RandomState(0).randn(1, 8).astype(np.float32)
        # seed the batch-execution estimate with real measured batches
        for _ in range(4):
            server.submit("m", x).result(timeout=30.0)
        before = server.stats()["models"]["m"]
        assert before.get("deadline_dropped", {}) == {}
        with pytest.raises(serving.DeadlineExceeded) as ei:
            server.submit("m", x, deadline_ms=1e-4)
        assert ei.value.where == "submit"
        after = server.stats()["models"]["m"]
        assert after["deadline_dropped"] == {"submit": 1}
        assert after["batches"] == before["batches"]  # no slot consumed
        # a meetable deadline sails through and is counted as met
        server.submit("m", x, deadline_ms=30000.0).result(timeout=30.0)
        final = server.stats()["models"]["m"]
        assert final["deadline_met"] == 1
    finally:
        server.drain(timeout=10.0)


def test_prediction_cache_correct_across_version_flip():
    """Cache hits serve the pinned version's answer; a live weight swap
    (the model-bus path) flips the content keys so the next request
    recomputes against the NEW weights — never stale data."""
    server = _tiny_server(cache=True)
    try:
        server.warmup()
        x = np.random.RandomState(1).randn(1, 8).astype(np.float32)
        f1 = server.submit("m", x)
        r1 = np.asarray(f1.result(timeout=30.0)[0])
        assert f1.cache_hit is False
        f2 = server.submit("m", x)
        r2 = np.asarray(f2.result(timeout=30.0)[0])
        assert f2.cache_hit is True and np.allclose(r1, r2)
        # the model-bus version flip: same shapes, new weights
        model = server.container.get("m")
        praws, araws, _v = model.pinned()
        model.swap_params([np.asarray(p) * 1.5 for p in praws],
                          version=7, aux_raws=araws)
        f3 = server.submit("m", x)
        r3 = np.asarray(f3.result(timeout=30.0)[0])
        assert f3.cache_hit is False           # old keys died with v0
        assert not np.allclose(r1, r3)         # computed on new weights
        f4 = server.submit("m", x)
        assert f4.cache_hit is True
        assert np.allclose(r3, np.asarray(f4.result(timeout=30.0)[0]))
    finally:
        server.drain(timeout=10.0)


def test_prediction_cache_unit_lru_and_invalidation():
    from mxnet_tpu.serving import cache as cache_mod

    pc = cache_mod.PredictionCache(capacity=2)
    a = np.zeros((1, 4), np.float32)
    k1 = cache_mod.content_key("m", 1, a)
    assert cache_mod.content_key("m", 2, a) != k1  # version in the key
    assert pc.get(k1) is None
    pc.put(k1, a, version=1)
    hit = pc.get(k1)
    assert hit is not None
    hit[:] = 99.0                                  # copies never alias
    assert float(pc.get(k1)[0, 0]) == 0.0
    # bounded: eldest falls off past capacity
    pc.put("k2", a, version=1)
    pc.put("k3", a, version=1)
    assert len(pc) == 2 and pc.get(k1) is None
    # observe_version on a flip drops the dead generation
    pc.observe_version(1)
    assert len(pc) == 2
    pc.observe_version(2)
    assert len(pc) == 0 and pc.stats()["invalidations"] == 2


# ------------------------------------------------------- live fleet -------

def _predict(client, model, x):
    body = json.dumps({"data": x.tolist()}).encode()
    status, payload, _ = client.request(
        "POST", f"/v1/models/{model}:predict", body=body,
        headers={"Content-Type": "application/json"})
    return status, payload


@pytest.fixture()
def fleet_cleanup():
    fleets = []
    yield fleets
    for fl in fleets:
        try:
            fl.stop(drain=False)
        except Exception:
            pass


def test_fleet_rollout_mid_load_zero_drops_zero_recompiles(
        tmp_path, fleet_cleanup):
    """The acceptance drill: a live 1-worker fleet rolls out a new model
    dir mid-load. Zero dropped admitted requests (no client-visible
    errors; the drained worker answered everything it admitted), the
    old generation exits 75, the new generation serves DIFFERENT
    outputs and compiled NOTHING (its whole ladder loaded from the
    disk cache the first generation wrote)."""
    import loadgen

    v1 = tmp_path / "v1"
    v2 = tmp_path / "v2"
    worker_mod.write_spec(v1, worker_mod.demo_spec(models=1, seed=910,
                                                   buckets=(2, 4)))
    worker_mod.write_spec(v2, worker_mod.demo_spec(models=1, seed=911,
                                                   buckets=(2, 4)))
    fl = ServingFleet(v1, workers=1, run_dir=str(tmp_path / "run"),
                      config={"min": 1, "max": 1, "beat": 0.2,
                              "grace": 20}, name="t-rollout",
                      env={"MXNET_TPU_CACHE_DIR": str(tmp_path / "cache")})
    fleet_cleanup.append(fl)
    fl.start(timeout=90)

    stop = threading.Event()
    lock = threading.Lock()
    outs, errors = [], []
    x = np.random.RandomState(1).randn(1, 16).astype(np.float32)

    def load():
        cl = loadgen.KeepAliveClient(fl.url)
        while not stop.is_set():
            try:
                status, payload = _predict(cl, "model0", x)
            except Exception as e:
                with lock:
                    errors.append(f"{type(e).__name__}: {e}")
                continue
            if status == 200:
                with lock:
                    outs.append(json.loads(payload)["outputs"][0][0][0])
            elif status not in (429, 503):
                with lock:
                    errors.append(f"HTTP {status}")
            time.sleep(0.005)

    threads = [threading.Thread(target=load, daemon=True)
               for _ in range(2)]
    for t in threads:
        t.start()
    time.sleep(0.5)
    first = outs[0]

    rec = fl.rollout(v2, timeout=90)
    time.sleep(0.4)
    stop.set()
    for t in threads:
        t.join(timeout=10.0)

    assert not errors, errors[:3]
    assert rec["state"] == "done"
    assert list(rec["drained"].values()) == [75]
    # the drained generation answered every admitted request
    (final,) = rec["old_final"].values()
    assert final["state"] == "drained" and final["failed"] == 0
    assert final["answered"] == final["admitted"] > 0
    # the new generation is serving a DIFFERENT model now
    assert outs and outs[-1] != first
    # zero recompiles: generation 2 warmed entirely from the disk cache
    anns = worker_mod.read_workers(fl.run_dir)
    gen2 = [a for a in anns.values() if a["generation"] == 2]
    assert len(gen2) == 1
    assert gen2[0]["compile_serving"]["compiles"] == 0
    assert gen2[0]["compile_serving"]["disk_hits"] == 2  # both buckets
    # rollout generation is visible in the stats + summary file
    assert fl.generation == 2
    summary = json.loads(
        (tmp_path / "run" / "fleet.json").read_text())
    assert summary["generation"] == 2
    assert summary["rollouts"][-1]["state"] == "done"


def test_fleet_rollout_health_gate_refuses_unwarmed_worker(
        tmp_path, fleet_cleanup):
    """A generation whose workers announce pending compiles (unwarmed
    ladder) must NOT take traffic: the rollout aborts on the gate
    deadline and the old generation keeps serving."""
    import loadgen

    v1 = tmp_path / "v1"
    v2 = tmp_path / "v2"
    worker_mod.write_spec(v1, worker_mod.demo_spec(models=1, seed=920,
                                                   buckets=(2,)))
    worker_mod.write_spec(v2, worker_mod.demo_spec(models=1, seed=921,
                                                   buckets=(2,)))
    fl = ServingFleet(v1, workers=1, run_dir=str(tmp_path / "run"),
                      config={"min": 1, "max": 1, "beat": 0.2},
                      name="t-gate")
    fleet_cleanup.append(fl)
    fl.start(timeout=90)
    # future generations skip warmup -> announce pending compiles
    fl._warmup = False
    with pytest.raises(fleet_mod.FleetError, match="health gate"):
        fl.rollout(v2, timeout=6.0)
    assert fl.generation == 1 and fl.state == "serving"
    assert fl.rollouts[-1]["state"] == "aborted"
    gate = fl.rollouts[-1]["gate_failures"]
    assert any(v.get("pending_compiles") for v in gate.values())
    # the old generation still answers
    cl = loadgen.KeepAliveClient(fl.url)
    x = np.random.RandomState(1).randn(1, 16).astype(np.float32)
    status, _ = _predict(cl, "model0", x)
    assert status == 200


def test_fleet_autoscaler_scales_up_under_load_and_down_on_idle(
        tmp_path, fleet_cleanup, monkeypatch):
    """The live acceptance: injected load grows the fleet 1 -> 2 (the
    decision visible in the autoscale counters / fleet gauges), idling
    shrinks it back to 1 through a deliberate drain — and the diagnose
    'Serving Fleet' report carries the census + last decision."""
    import urllib.request

    import loadgen

    md = tmp_path / "m"
    worker_mod.write_spec(md, worker_mod.demo_spec(models=1, seed=930,
                                                   buckets=(2, 4)))
    fl = ServingFleet(
        md, workers=1, run_dir=str(tmp_path / "run"),
        config={"min": 1, "max": 2, "beat": 0.2, "interval": 0.3,
                "k": 2, "up_p99_ms": 0.05,  # any real traffic = pressure
                "idle_rps": 2.0, "idle_k": 3, "cooldown": 0.5,
                "grace": 20},
        name="t-scale")
    fleet_cleanup.append(fl)
    fl.start(timeout=90)
    assert fl.stats(light=True)["desired"] == 1

    stop = threading.Event()

    def load():
        cl = loadgen.KeepAliveClient(fl.url)
        x = np.random.RandomState(2).randn(1, 16).astype(np.float32)
        while not stop.is_set():
            try:
                _predict(cl, "model0", x)
            except Exception:
                time.sleep(0.01)

    threads = [threading.Thread(target=load, daemon=True)
               for _ in range(4)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:
        if fl.stats(light=True)["desired"] == 2:
            break
        time.sleep(0.2)
    else:
        raise AssertionError(
            f"never scaled up: {fl.stats()['autoscaler']}")
    assert fl._scaler.decisions["up"] >= 1
    up = fl._scaler.last_action
    assert up["direction"] == "up" and "p99" in up["reason"]

    # the decision is visible on the router's /metrics scrape
    text = urllib.request.urlopen(fl.url + "/metrics",
                                  timeout=10).read().decode()
    assert 'mxtpu_fleet_autoscale_total{direction="up"} 1' in text
    assert "mxtpu_fleet_workers_desired 2" in text

    # idle: load off -> completion rate collapses -> scale back down
    stop.set()
    for t in threads:
        t.join(timeout=10.0)
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:
        if fl.stats(light=True)["desired"] == 1:
            break
        time.sleep(0.2)
    else:
        raise AssertionError(
            f"never scaled down: {fl.stats()['autoscaler']}")
    assert fl._scaler.decisions["down"] >= 1
    # the drained slot retired through the deliberate-drain path
    deadline = time.monotonic() + 30.0
    while fl._sup.drained_total < 1 and time.monotonic() < deadline:
        time.sleep(0.1)
    assert fl._sup.drained_total >= 1

    # diagnose: the Serving Fleet section reports census + decisions
    import diagnose

    monkeypatch.setenv("MXTPU_FLEET_DIR", str(tmp_path / "run"))
    out = diagnose.check_fleet()
    assert out["summary"]["autoscaler"]["decisions"]["up"] >= 1
    assert out["summary"]["autoscaler"]["decisions"]["down"] >= 1
    assert out["summary"]["generation"] == 1
    assert out["summary"]["workers"]


def test_fleet_two_host_placement_and_merged_scrape(
        tmp_path, fleet_cleanup):
    """Multi-host live: two localhost pseudo-hosts under one fleet —
    slots place round-robin across them, each host gets its own run dir
    (host-<name>/) for announces + telemetry shards, and read_workers /
    worker_metrics merge the per-host shards into one fleet view the
    router serves traffic from."""
    import loadgen

    v1 = tmp_path / "v1"
    worker_mod.write_spec(v1, worker_mod.demo_spec(models=1, seed=920,
                                                   buckets=(2, 4)))
    fl = ServingFleet(
        str(v1), workers=2, run_dir=str(tmp_path / "run"),
        hosts=["local", {"name": "b", "locality": "local"}],
        config={"min": 2, "max": 2, "beat": 0.2, "grace": 20},
        name="twohost")
    fleet_cleanup.append(fl)
    fl.start(timeout=120)
    # placement: hosts[slot % 2] — slot 0 on "local", slot 1 on "b"
    st = fl.stats()
    assert {s: w["host"] for s, w in st["workers"].items()} == \
        {"0": "local", "1": "b"}
    assert all(w["locality"] == "local" for w in st["workers"].values())
    assert {h["name"]: h["slots"] for h in st["hosts"]} == \
        {"local": [0], "b": [1]}
    # per-host run dirs own the announces; the scrape merges them
    assert (tmp_path / "run" / "host-local" / "worker-0.json").exists()
    assert (tmp_path / "run" / "host-b" / "worker-1.json").exists()
    assert sorted(worker_mod.read_workers(fl.run_dir)) == [0, 1]
    # traffic flows through both placements
    cl = loadgen.KeepAliveClient(fl.url)
    x = np.random.RandomState(0).randn(1, 16).astype(np.float32)
    for _ in range(30):
        status, _ = _predict(cl, "model0", x)
        assert status == 200
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        m = worker_metrics(fl.run_dir)
        if sorted(m) == [0, 1] and all(
                (m[s].get("rps") or 0) >= 0 for s in m):
            break
        time.sleep(0.2)
    assert sorted(worker_metrics(fl.run_dir)) == [0, 1]


# ----------------------------------------------------------- loadgen ------

def test_loadgen_keepalive_reuses_connections():
    """--via-http now drives persistent connections: one connect per
    worker thread (not per request), connect time reported separately."""
    import loadgen

    rep = loadgen.run_inproc(duration=1.5, mode="closed", concurrency=4,
                             models=1, via_http=True)
    assert rep["errors"] == 0, rep["first_errors"]
    assert rep["completed"] > rep["connects"]
    # keep-alive: connects == threads (reconnects only on failure)
    assert rep["connects"] <= 4 + rep["reconnects"]
    assert rep["connect_ms_mean"] is not None
    assert rep["connect_ms_total"] < 1000.0


def test_loadgen_fleet_mode_short(tmp_path):
    """--workers N end to end: an N-worker fleet driven through the
    router, report carrying router counters + per-worker census."""
    import loadgen

    rep = loadgen.run_fleet(workers=1, duration=1.5, concurrency=4,
                            models=1, run_dir=str(tmp_path))
    assert rep["harness"] == "loadgen-fleet" and rep["workers"] == 1
    assert rep["errors"] == 0, rep["first_errors"]
    assert rep["completed"] > 0 and rep["rps"] > 0
    assert rep["router"]["completed"] >= rep["completed"]
    assert rep["per_worker"] and rep["connects"] >= 4
