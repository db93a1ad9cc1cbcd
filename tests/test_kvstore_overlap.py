"""Bucketed async gradient collectives (docs/PERFORMANCE.md):
deterministic bucket assembly, bit-identity with the legacy per-key
path, fingerprint stability, overlap telemetry, and the 2-process A/B
acceptance drill."""
import os
import socket
import subprocess
import sys
import textwrap

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.kvstore import buckets

from test_sparse_dist import _needs_multiprocess_cpu


# --------------------------------------------------------- plan assembly ---

def test_bucket_plan_greedy_cap_and_order():
    plan = buckets.BucketPlan(64)  # cap: 16 f32 elements
    plan.register("a", (2, 2), "float32")   # 16B -> bucket 0
    plan.register("b", (8,), "float32")     # 32B -> bucket 0 (48B)
    plan.register("c", (4,), "float32")     # 16B -> bucket 0 (64B, fits)
    plan.register("d", (1,), "float32")     # bucket 0 full -> bucket 1
    assert [b["keys"] for b in plan.buckets] == [["a", "b", "c"], ["d"]]
    assert plan.buckets[0]["nbytes"] == 64
    # assignment is stable under append and a pure function of the
    # registration sequence
    plan2 = buckets.BucketPlan(64)
    for k, s in (("a", (2, 2)), ("b", (8,)), ("c", (4,)), ("d", (1,))):
        plan2.register(k, s, "float32")
    assert [b["keys"] for b in plan2.buckets] == \
        [b["keys"] for b in plan.buckets]


def test_bucket_plan_oversized_single_grad_own_bucket():
    plan = buckets.BucketPlan(64)
    plan.register("small", (4,), "float32")
    plan.register("huge", (1024,), "float32")  # 4KB >> cap
    plan.register("tail", (4,), "float32")
    assert [b["keys"] for b in plan.buckets] == \
        [["small"], ["huge"], ["tail"]]


def test_bucket_plan_dtype_split_and_idempotent_register():
    plan = buckets.BucketPlan(1 << 20)
    plan.register("f", (4,), "float32")
    plan.register("i", (4,), "int32")     # dtype change -> new bucket
    plan.register("g", (4,), "float32")   # and again
    assert len(plan.buckets) == 3
    bid = plan.register("f", (4,), "float32")  # idempotent
    assert bid == 0 and len(plan.order) == 3


def test_bucket_plan_empty_and_single_key():
    plan = buckets.BucketPlan(buckets.DEFAULT_BUCKET_BYTES)
    assert plan.buckets == [] and plan.describe()["keys"] == 0
    plan.register("only", (3, 3), "float32")
    assert [b["keys"] for b in plan.buckets] == [["only"]]


def test_bucket_bytes_env(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_BUCKET_BYTES", raising=False)
    assert buckets.bucket_bytes() == buckets.DEFAULT_BUCKET_BYTES
    monkeypatch.setenv("MXNET_TPU_BUCKET_BYTES", "1234")
    assert buckets.bucket_bytes() == 1234
    monkeypatch.setenv("MXNET_TPU_BUCKET_BYTES", "0")
    assert buckets.bucket_bytes() == 0
    monkeypatch.setenv("MXNET_TPU_BUCKET_BYTES", "junk")
    assert buckets.bucket_bytes() == buckets.DEFAULT_BUCKET_BYTES


# --------------------------------------- forced pipeline vs legacy (1 proc) --

SHAPES = [(4, 4), (8,), (2, 3), (16,), (1,)]


def _drive(kv, steps=3, order="backward"):
    for i, s in enumerate(SHAPES):
        kv.init(i, mx.nd.zeros(s))
    outs = None
    for step in range(steps):
        idxs = range(len(SHAPES))
        if order == "backward":
            idxs = reversed(list(idxs))
        for i in idxs:
            g = mx.nd.array(onp.full(SHAPES[i], 0.25 * (i + 1) + 0.1 * step,
                                     onp.float32))
            kv.push(i, g, priority=-i)
        outs = [mx.nd.zeros(s) for s in SHAPES]
        for i in range(len(SHAPES)):
            kv.pull(i, outs[i])
    kv.barrier()
    return [o.asnumpy() for o in outs]


@pytest.mark.parametrize("cap", ["1", "48", "4096", None])
def test_forced_pipeline_bit_identical_to_legacy(monkeypatch, cap):
    """Every bucket size — per-key (1B cap), mixed partial-fit, one big
    bucket, and the default — produces bit-identical pulls vs the
    legacy path (MXNET_TPU_BUCKET_BYTES=0)."""
    monkeypatch.setenv("MXNET_TPU_BUCKET_FORCE", "1")
    if cap is None:
        monkeypatch.delenv("MXNET_TPU_BUCKET_BYTES", raising=False)
    else:
        monkeypatch.setenv("MXNET_TPU_BUCKET_BYTES", cap)
    bucketed = _drive(mx.kv.create("dist_sync"))
    monkeypatch.setenv("MXNET_TPU_BUCKET_BYTES", "0")
    monkeypatch.delenv("MXNET_TPU_BUCKET_FORCE", raising=False)
    legacy = _drive(mx.kv.create("dist_sync"))
    for a, b in zip(bucketed, legacy):
        assert onp.array_equal(a, b), (cap, a, b)


def test_forced_pipeline_update_on_store_bit_identical(monkeypatch):
    def run(force):
        monkeypatch.setenv("MXNET_TPU_BUCKET_FORCE", "1" if force else "0")
        monkeypatch.setenv("MXNET_TPU_BUCKET_BYTES", "" if force else "0")
        kv = mx.kv.create("dist_sync")
        kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.5,
                                          momentum=0.9))
        return _drive(kv)

    for a, b in zip(run(True), run(False)):
        assert onp.array_equal(a, b)


def test_forced_pipeline_dist_async_gather_bit_identical(monkeypatch):
    def run(force):
        monkeypatch.setenv("MXNET_TPU_BUCKET_FORCE", "1" if force else "0")
        monkeypatch.setenv("MXNET_TPU_BUCKET_BYTES", "" if force else "0")
        kv = mx.kv.create("dist_async")
        kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.5))
        return _drive(kv)

    for a, b in zip(run(True), run(False)):
        assert onp.array_equal(a, b)


def test_bucket_bytes_zero_restores_legacy_exactly(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_BUCKET_BYTES", "0")
    kv = mx.kv.create("dist_sync")
    assert kv._pipeline is None  # the legacy path, not an idle pipeline


def test_pipeline_fuses_fewer_collectives_than_keys(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_BUCKET_FORCE", "1")
    monkeypatch.delenv("MXNET_TPU_BUCKET_BYTES", raising=False)
    kv = mx.kv.create("dist_sync")
    _drive(kv)
    st = kv._pipeline.stats
    assert st["keys"] == 3 * len(SHAPES)
    assert 0 < st["fused"] < st["keys"]  # the fusion win
    assert st["resolved"] == st["fused"]
    assert kv._pipeline.pending() == {"staged": {}, "inflight": 0}
    desc = kv._pipeline.describe()
    assert desc["overlap_ratio"] is not None
    assert buckets.comm_stats()["fused"] >= st["fused"]


def test_repeat_push_before_pull_drains_bucket(monkeypatch):
    """Legacy semantics: two pushes of one key without a pull are two
    reduction rounds whose aggregates both land in pending."""
    monkeypatch.setenv("MXNET_TPU_BUCKET_FORCE", "1")

    def run(force):
        monkeypatch.setenv("MXNET_TPU_BUCKET_BYTES", "" if force else "0")
        kv = mx.kv.create("dist_sync")
        kv.init(0, mx.nd.zeros((4,)))
        kv.push(0, mx.nd.array([1.0, 2.0, 3.0, 4.0]))
        kv.push(0, mx.nd.array([10.0, 20.0, 30.0, 40.0]))
        out = mx.nd.zeros((4,))
        kv.pull(0, out)
        return out.asnumpy()

    a, b = run(True), run(False)
    assert onp.array_equal(a, b)


def test_partial_bucket_dispatches_at_pull(monkeypatch):
    """Keys never pushed this round must not block resolution — the
    partially-filled bucket dispatches (counted as partial) at the
    flush point."""
    monkeypatch.setenv("MXNET_TPU_BUCKET_FORCE", "1")
    monkeypatch.delenv("MXNET_TPU_BUCKET_BYTES", raising=False)
    kv = mx.kv.create("dist_sync")
    for i, s in enumerate(SHAPES):
        kv.init(i, mx.nd.zeros(s))
    kv.push(1, mx.nd.array(onp.ones(SHAPES[1], onp.float32)))
    out = mx.nd.zeros(SHAPES[1])
    kv.pull(1, out)
    assert onp.array_equal(out.asnumpy(), onp.ones(SHAPES[1]))
    assert kv._pipeline.stats["partial"] == 1


def test_fingerprint_deterministic_across_identical_programs(monkeypatch):
    """The pass-2 collective fingerprint is a pure function of the
    (registration, push) sequence at every bucket size — what makes the
    cross-rank check valid under bucketing."""
    for cap in ("1", "48", "4096", str(1 << 22)):
        monkeypatch.setenv("MXNET_TPU_BUCKET_FORCE", "1")
        monkeypatch.setenv("MXNET_TPU_BUCKET_BYTES", cap)

        def run():
            kv = mx.kv.create("dist_sync")
            if kv._sched is None:
                pytest.skip("distcheck disabled in this environment")
            _drive(kv)
            return kv._sched.fingerprint()

        assert run() == run(), cap


def test_sync_phase_and_overlap_land_in_step_report(monkeypatch):
    """The pipeline's blocked resolve tail is 'sync' time in the PR 9
    step timeline, and the scrape exports the overlap gauge."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import steps as tsteps

    monkeypatch.setenv("MXNET_TPU_BUCKET_FORCE", "1")
    monkeypatch.delenv("MXNET_TPU_BUCKET_BYTES", raising=False)
    kv = mx.kv.create("dist_sync")
    for i, s in enumerate(SHAPES):
        kv.init(i, mx.nd.zeros(s))
    tsteps.begin_step(1)
    for i in reversed(range(len(SHAPES))):
        kv.push(i, mx.nd.array(onp.ones(SHAPES[i], onp.float32)))
    for i in range(len(SHAPES)):
        kv.pull(i, mx.nd.zeros(SHAPES[i]))
    rec = tsteps.end_step()
    assert rec is not None and rec["phases"]["sync"] >= 0.0
    flat = str(telemetry.metrics_snapshot())
    assert "mxtpu_kvstore_fused_collectives_total" in flat
    assert "mxtpu_kvstore_overlap_ratio" in flat


def test_bucket_lifecycle_spans_committed(monkeypatch):
    from mxnet_tpu.telemetry import trace

    if not trace.enabled():
        pytest.skip("tracing disabled")
    monkeypatch.setenv("MXNET_TPU_BUCKET_FORCE", "1")
    monkeypatch.delenv("MXNET_TPU_BUCKET_BYTES", raising=False)
    before = trace.counts().get("bucket", 0)
    _drive(mx.kv.create("dist_sync"), steps=1)
    assert trace.counts().get("bucket", 0) > before
    spans = [s for s in trace.tail() if s["kind"] == "bucket"]
    assert spans
    tid = spans[-1]["trace"]
    phases = {s["name"] for s in trace.tail()
              if s["trace"] == tid and s["kind"] == "phase"}
    assert {"enqueue", "fuse", "dispatch", "resolve"} <= phases


def test_peer_lost_mid_bucket_carries_census(monkeypatch, tmp_path):
    """An injected kvstore.sync hang while a fused bucket resolves must
    surface PeerLostError with the bucket census attached (the chaos
    phase-11 contract, in-process)."""
    import time

    from mxnet_tpu import faults, watchdog
    from mxnet_tpu.kvstore import PeerLostError

    monkeypatch.setenv("MXNET_TPU_BUCKET_FORCE", "1")
    monkeypatch.delenv("MXNET_TPU_BUCKET_BYTES", raising=False)
    kv = mx.kv.create("dist_sync")
    for i, s in enumerate(SHAPES):
        kv.init(i, mx.nd.zeros(s))
    watchdog.configure({"kvstore.sync": 0.5}, crash_dir=str(tmp_path),
                       interval=0.1)
    faults.configure("kvstore.sync:hang@1:1.5")
    try:
        for i in reversed(range(len(SHAPES))):
            kv.push(i, mx.nd.array(onp.ones(SHAPES[i], onp.float32)))
        with pytest.raises(PeerLostError) as ei:
            kv.pull(0, mx.nd.zeros(SHAPES[0]))
        err = ei.value
        assert err.op == "bucket_reduce"
        assert err.census and err.census["plan"]["buckets"]
        assert "bucket census" in str(err)
    finally:
        faults.reset()
        watchdog.configure(None)
        time.sleep(1.6)  # let the abandoned waiter drain


# ------------------------------------------------------------ trainer side --

def test_trainer_grad_scatter_lever_and_token(monkeypatch):
    from mxnet_tpu.gluon import loss as gloss, nn
    from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer

    def build():
        mx.random.seed(0)
        net = nn.Dense(4, in_units=8)
        net.initialize(mx.init.Xavier())
        net(mx.nd.zeros((2, 8)))
        return ShardedTrainer(net, gloss.L2Loss(), "sgd",
                              {"learning_rate": 0.1},
                              mesh=DeviceMesh({"dp": 1}))

    tr = build()
    assert tr._grad_scatter is False  # single host: nothing to scatter
    # the lever is part of the compiled step's identity
    tok_on = tr._service_token("step")
    tr._grad_scatter = True
    assert tr._service_token("step") != tok_on
    tr._grad_scatter = False
    monkeypatch.setenv("MXNET_TPU_GRAD_SCATTER", "0")
    assert build()._grad_scatter is False
    # the dp-sharding helper picks the first divisible unsharded dim
    assert tr._dp_sharded_full((), (4, 4)) == (None, None)  # dp=1: no-op


def test_trainer_aot_lower_compile_clean():
    """aot_lower lowers the full step under GSPMD without executing it
    or consuming the RNG stream; the compiled HLO feeds the distcheck
    collective census (the multichip-dryrun ROADMAP 3a stage)."""
    from mxnet_tpu import random as mxrand
    from mxnet_tpu.analysis import distcheck
    from mxnet_tpu.gluon import loss as gloss, nn
    from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer

    mx.random.seed(0)
    net = nn.Dense(4, in_units=8)
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((2, 8)))
    tr = ShardedTrainer(net, gloss.L2Loss(), "sgd",
                        {"learning_rate": 0.1},
                        mesh=DeviceMesh({"dp": 1}), zero=True)
    mxrand._ensure()
    key_before = onp.asarray(mxrand._state.key)
    lowered = tr.aot_lower(mx.nd.ones((4, 8)), mx.nd.ones((4, 4)))
    compiled = lowered.compile()
    assert tr._t == 0  # nothing executed
    assert onp.array_equal(onp.asarray(mxrand._state.key), key_before)
    sched = distcheck.schedule_from_hlo(compiled.as_text())
    assert isinstance(sched, list)  # dp=1: typically empty, never raises
    # the lowered step still runs afterwards
    loss = tr.step(mx.nd.ones((4, 8)), mx.nd.ones((4, 4)))
    assert onp.isfinite(float(loss.asscalar()))


def test_diagnose_grad_comms_section(monkeypatch):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "tools"))
    import diagnose

    out = diagnose.check_gradcomms()
    assert out["cap_bytes"] == buckets.bucket_bytes()
    assert "stats" in out and "overlap_ratio" in out["stats"]


# ---------------------------------------------------------- perf guard -----

@pytest.mark.perf
def test_single_host_pipeline_overhead_within_noise(monkeypatch):
    """The forced bucket pipeline must not tax a single-host
    push/pull loop beyond noise vs the legacy path (the ISSUE guard
    that single-host step time is unaffected)."""
    import time

    def loop(force):
        monkeypatch.setenv("MXNET_TPU_BUCKET_FORCE", "1" if force else "0")
        monkeypatch.setenv("MXNET_TPU_BUCKET_BYTES", "" if force else "0")
        kv = mx.kv.create("dist_sync")
        for i, s in enumerate(SHAPES):
            kv.init(i, mx.nd.zeros(s))
        grads = [mx.nd.array(onp.ones(s, onp.float32)) for s in SHAPES]
        outs = [mx.nd.zeros(s) for s in SHAPES]
        _ = [kv.push(i, grads[i]) for i in range(len(SHAPES))]  # warm
        _ = [kv.pull(i, outs[i]) for i in range(len(SHAPES))]
        t0 = time.perf_counter()
        for _ in range(30):
            for i in reversed(range(len(SHAPES))):
                kv.push(i, grads[i])
            for i in range(len(SHAPES)):
                kv.pull(i, outs[i])
        return time.perf_counter() - t0

    bucketed, legacy = loop(True), loop(False)
    # generous envelope: CI timing is noisy; catches order-of-magnitude
    # regressions (a sync sneaking into enqueue, per-push concat, ...)
    assert bucketed <= legacy * 2.5 + 0.25, (bucketed, legacy)


# ------------------------------------------------- 2-process acceptance ----

def _run_two(tmp_path, child_src, ok_token, timeout=240):
    """The test_sparse_dist 2-process harness, returning both ranks'
    stdout for parent-side cross-rank assertions."""
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = str(s.getsockname()[1])
    script = tmp_path / "overlap_child.py"
    script.write_text(child_src)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("MXNET_TPU_BUCKET_BYTES", None)
    env.pop("MXNET_TPU_BUCKET_FORCE", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.getcwd() + os.pathsep + env.get("PYTHONPATH", "")
    procs = [subprocess.Popen(
        [sys.executable, str(script), port, str(pid)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=os.getcwd()) for pid in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.skip("distributed runtime hung in this environment")
    if any(p.returncode != 0 for p in procs):
        joined = "\n".join(outs)
        if "DISTRIBUTED" in joined.upper() or "initialize" in joined:
            pytest.skip(f"jax.distributed unavailable: {joined[-300:]}")
        raise AssertionError(joined[-2000:])
    assert all(ok_token in o for o in outs), outs
    return outs


_OVERLAP_CHILD = textwrap.dedent("""
    import os, sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    port, pid = sys.argv[1], int(sys.argv[2])
    jax.distributed.initialize(coordinator_address="localhost:" + port,
                               num_processes=2, process_id=pid)
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.kvstore import buckets
    from mxnet_tpu.telemetry import steps

    SHAPES = [(64, 64)] * 24   # 16KB each; one ~128KB bucket holds 8
    STEPS = 4

    def run(bucket_bytes):
        os.environ["MXNET_TPU_BUCKET_BYTES"] = str(bucket_bytes)
        kv = mx.kv.create("dist_sync")
        assert kv.num_workers == 2
        for i, s in enumerate(SHAPES):
            kv.init(i, mx.nd.zeros(s))
        sync_ms, outs = [], None
        for step in range(STEPS + 1):   # round 0 warms compile caches
            steps.begin_step(step + 1)
            for i in reversed(range(len(SHAPES))):
                g = mx.nd.array(np.full(
                    SHAPES[i], (kv.rank + 1) * 0.01 * (i + 1 + step),
                    np.float32))
                kv.push(i, g, priority=-i)
            outs = [mx.nd.zeros(s) for s in SHAPES]
            for i in range(len(SHAPES)):
                kv.pull(i, outs[i], priority=-i)
            rec = steps.end_step()
            if step > 0:
                sync_ms.append(rec["phases"]["sync"])
        kv.barrier()   # includes the cross-rank fingerprint check
        fp = kv._sched.fingerprint() if kv._sched is not None else "off"
        vals = np.concatenate([o.asnumpy().ravel() for o in outs])
        return vals, sum(sync_ms) / len(sync_ms), fp

    legacy_vals, legacy_sync, legacy_fp = run(0)
    bucket_vals, bucket_sync, bucket_fp = run(128 * 1024)
    cs = buckets.comm_stats()
    assert np.array_equal(legacy_vals, bucket_vals), "numerics diverged"
    assert 0 < cs["fused"] < cs["keys"], cs
    assert cs["overlap_ratio"] is not None and cs["overlap_ratio"] > 0.0, cs
    assert bucket_sync < legacy_sync, (bucket_sync, legacy_sync)
    print("OVERLAP_OK", pid, "FP=" + bucket_fp, "LFP=" + legacy_fp,
          "legacy_sync=%.3f" % legacy_sync,
          "bucket_sync=%.3f" % bucket_sync,
          "overlap=" + str(cs["overlap_ratio"]),
          "fused=%d/%d" % (cs["fused"], cs["keys"]))
""")


@pytest.mark.slow
@pytest.mark.skipif(os.environ.get("SKIP_DIST_TESTS") == "1",
                    reason="distributed tests disabled")
@_needs_multiprocess_cpu
def test_two_process_bucketed_overlap_ab_drill(tmp_path):
    """The acceptance drill: 2-process CPU A/B — bucketed vs
    MXNET_TPU_BUCKET_BYTES=0 legacy. Bit-identical pulls, fused
    collective count < per-key count, step_report sync mean strictly
    lower with overlap_ratio > 0, and rank-identical collective
    fingerprints."""
    outs = _run_two(tmp_path, _OVERLAP_CHILD, "OVERLAP_OK")
    fps = set()
    for out in outs:
        line = [ln for ln in out.splitlines() if "OVERLAP_OK" in ln][-1]
        fps.add([t for t in line.split() if t.startswith("FP=")][0])
    assert len(fps) == 1, f"fingerprints diverged across ranks: {outs}"


_COMPRESSED_CHILD = textwrap.dedent("""
    import os, sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    port, pid = sys.argv[1], int(sys.argv[2])
    jax.distributed.initialize(coordinator_address="localhost:" + port,
                               num_processes=2, process_id=pid)
    import numpy as np
    import mxnet_tpu as mx

    SHAPES = [(8, 8), (32,), (4, 4)]

    def run(bucket_bytes):
        os.environ["MXNET_TPU_BUCKET_BYTES"] = str(bucket_bytes)
        kv = mx.kv.create("dist_sync")
        kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
        for i, s in enumerate(SHAPES):
            kv.init(i, mx.nd.zeros(s))
        rs = np.random.RandomState(7)
        for step in range(3):
            for i in reversed(range(len(SHAPES))):
                g = mx.nd.array((rs.rand(*SHAPES[i]) - 0.4).astype(
                    np.float32) * (kv.rank + 1))
                kv.push(i, g)
            outs = [mx.nd.zeros(s) for s in SHAPES]
            for i in range(len(SHAPES)):
                kv.pull(i, outs[i])
        kv.barrier()
        res = {k: np.asarray(v) for k, v in kv._residuals.items()}
        return np.concatenate([o.asnumpy().ravel() for o in outs]), res

    lv, lres = run(0)
    bv, bres = run(1 << 20)
    assert np.array_equal(lv, bv), "compressed numerics diverged"
    for k in lres:
        assert np.array_equal(lres[k], bres[k]), "residuals diverged"
    print("COMPRESS_OK", pid)
""")


@pytest.mark.slow
@pytest.mark.skipif(os.environ.get("SKIP_DIST_TESTS") == "1",
                    reason="distributed tests disabled")
@_needs_multiprocess_cpu
def test_two_process_compressed_bucket_fusion(tmp_path):
    """2-bit payloads fused through buckets stay bit-identical to the
    legacy per-key compressed path, error-feedback residuals included."""
    _run_two(tmp_path, _COMPRESSED_CHILD, "COMPRESS_OK")
