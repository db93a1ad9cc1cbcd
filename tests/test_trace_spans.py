"""The one span primitive (telemetry/trace.span) and the spans of a
trainer step: one pair of clock reads, three views (the ring, the
caller's ``dur_ms`` and thence the ``steps`` phases, and the host plane of
a profiler session)."""
import glob
import os
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import compile as mxcompile
from mxnet_tpu import faults, gluon, profiler, telemetry, watchdog
from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer
from mxnet_tpu.telemetry import steps, trace

#: the children of ``trainer.step``, in the order they run: up to
#: ``trainer.dispatch`` what the launch needs, after ``trainer.guard_sync``
#: what needs the flag, the rest between the two
STEP_CHILDREN = ["trainer.put_batch", "trainer.scalars", "trainer.gather",
                 "trainer.dispatch", "trainer.rng_key", "trainer.commit",
                 "trainer.release", "trainer.guard_sync",
                 "trainer.bookkeeping"]
#: which spans each ``steps`` phase is the sum of
PHASE_SPANS = {"h2d": ["trainer.put_batch"],
               "host": ["trainer.scalars", "trainer.gather",
                        "trainer.commit", "trainer.release"],
               "compute": ["trainer.dispatch", "trainer.rng_key"],
               "sync": ["trainer.guard_sync"]}


def small_trainer(seed=0):
    mx.random.seed(seed)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
    net.initialize(mx.init.Xavier())
    rs = np.random.RandomState(seed)
    x = mx.nd.array(rs.randn(8, 8).astype(np.float32))
    y = mx.nd.array(rs.randn(8, 4).astype(np.float32))
    net(x)
    trainer = ShardedTrainer(net, gluon.loss.L2Loss(), "sgd",
                             {"learning_rate": 0.01},
                             mesh=DeviceMesh({"dp": 1}))
    return trainer, x, y


@pytest.fixture
def warm():
    """A trainer past its compiling first step, over an empty ring."""
    prev = trace.configure(2048)
    steps.reset()
    trainer, x, y = small_trainer()
    trainer.step(x, y)
    trace.clear()
    yield trainer, x, y
    trace.configure(prev)
    steps.reset()


def _end(rec):
    return rec["t0"] + rec["dur_ms"] / 1e3


def _one_step(tail):
    parents = [r for r in tail if r["name"] == "trainer.step"]
    assert len(parents) == 1, [r["name"] for r in tail]
    parent = parents[0]
    children = sorted((r for r in tail if r["parent"] == parent["seq"]),
                      key=lambda r: r["t0"])
    return parent, children


# ------------------------------------------------------------ the ring ---

def test_step_children_are_real_nested_and_disjoint(warm):
    trainer, x, y = warm
    trainer.step(x, y)
    parent, children = _one_step(trace.tail())
    assert [c["name"] for c in children] == STEP_CHILDREN
    assert parent["kind"] == "step"
    assert parent["trace"] == f"step-g0-r0-{parent['attrs']['step']}"
    slack = 2e-6   # t0 is rounded to the microsecond
    for c in children:
        assert c["trace"] == parent["trace"]
        assert c["lane"] == parent["lane"]
        assert c["t0"] >= parent["t0"] - slack
        assert _end(c) <= _end(parent) + slack
    for a, b in zip(children, children[1:]):
        assert _end(a) <= b["t0"] + slack, (a["name"], b["name"])
    # the compile service's two halves sit under the dispatch span
    dispatch = children[STEP_CHILDREN.index("trainer.dispatch")]
    inner = sorted((r for r in trace.tail()
                    if r["parent"] == dispatch["seq"]),
                   key=lambda r: r["t0"])
    assert [r["name"] for r in inner] == ["compile.signature",
                                          "compile.execute"]
    assert sum(r["dur_ms"] for r in inner) <= dispatch["dur_ms"] + 1e-3
    # nothing was laid out from durations: no 'phase' child any more
    assert not [r for r in trace.tail() if r["kind"] == "phase"]


def test_phases_are_the_sums_of_the_same_spans(warm):
    trainer, x, y = warm
    trainer.step(x, y)
    parent, children = _one_step(trace.tail())
    dur = {c["name"]: c["dur_ms"] for c in children}
    phases = steps.last()["phases"]
    for phase, names in PHASE_SPANS.items():
        # the ring rounds a span to 1e-4 ms, the record a phase to 1e-3
        assert phases[phase] == pytest.approx(
            sum(dur[n] for n in names), abs=1e-3 + 1e-4 * len(names))
    assert phases["data_wait"] == phases["optimizer"] == 0.0
    # the record rides its span, and what no span covers is small
    assert parent["attrs"]["phases"] == phases
    rec = steps.last()
    assert phases["other"] == pytest.approx(
        rec["duration_ms"] - sum(v for k, v in phases.items()
                                 if k != "other"), abs=0.01)
    assert rec["duration_ms"] <= parent["dur_ms"]
    assert trainer.step_report() == rec


@pytest.mark.parametrize("off", ["ring", "telemetry"])
def test_a_step_commits_nothing_when_off_and_still_runs(warm, off):
    trainer, x, y = warm
    if off == "ring":
        trace.configure(0)
    else:
        telemetry.set_enabled(False)
    try:
        before = len(steps.history())
        loss = float(trainer.step(x, y).asscalar())
        assert np.isfinite(loss)
        assert trace.tail() == [] and trace.counts() == {}
        if off == "ring":
            # the spans still time: the phases come from them
            rec = steps.last()
            assert len(steps.history()) == before + 1
            assert rec["phases"]["compute"] > 0
            assert rec["phases"]["sync"] > 0
        else:
            assert len(steps.history()) == before
    finally:
        telemetry.set_enabled(True)
        trace.configure(2048)


def test_span_hands_back_the_duration_it_commits():
    prev = trace.configure(64)
    try:
        with trace.context("outer-id"):
            with trace.span("a", kind="t", n=3) as a:
                with trace.span("b", trace_id="own-id") as b:
                    assert trace.get_context() == "own-id"
                assert trace.get_context() == "outer-id"
        recs = {r["name"]: r for r in trace.tail()}
        assert recs["a"]["dur_ms"] == round(a.dur_ms, 4) > 0
        assert recs["b"]["dur_ms"] == round(b.dur_ms, 4)
        assert recs["b"]["parent"] == recs["a"]["seq"] == a.span_id
        assert recs["a"]["parent"] is None
        assert recs["a"]["trace"] == "outer-id"
        assert recs["b"]["trace"] == "own-id"
        assert recs["a"]["attrs"] == {"n": 3}
        assert trace.counts() == {"t": 1, "span": 1}
        assert trace.carry() == (None, ())
    finally:
        trace.configure(prev)


def test_carry_and_adopt_nest_a_helper_threads_spans():
    prev = trace.configure(64)
    try:
        with trace.span("caller", trace_id="req-1") as outer:
            carried = trace.carry()

            def work():
                trace.adopt(carried)
                with trace.span("helper"):
                    pass

            t = threading.Thread(target=work)
            t.start()
            t.join()
        helper = next(r for r in trace.tail() if r["name"] == "helper")
        assert helper["parent"] == outer.span_id
        assert helper["trace"] == "req-1"
    finally:
        trace.configure(prev)


def test_step_record_closed_outside_a_step_span_commits_its_own():
    """A caller that drives ``begin_step``/``end_step`` itself still gets
    one ``trainer.step`` record with the phase split, and no children."""
    prev = trace.configure(64)
    steps.reset()
    try:
        steps.begin_step(7)
        steps.phase("sync", 1.5)
        rec = steps.end_step()
        (only,) = trace.tail()
        assert only["name"] == "trainer.step" and only["kind"] == "step"
        assert only["trace"] == "step-g0-r0-7"
        assert only["attrs"]["phases"] == rec["phases"]
        assert only["attrs"]["phases"]["sync"] == 1.5
    finally:
        trace.configure(prev)
        steps.reset()


# ------------------------------------------------------- a raising step ---

def test_raising_step_leaves_no_open_span_and_no_record(warm):
    trainer, x, y = warm
    before = len(steps.history())
    faults.configure("trainer.step:raise@1", seed=0)
    try:
        with pytest.raises(faults.InjectedFault):
            trainer.step(x, y)
    finally:
        faults.reset()
    assert trace.carry() == (None, ())     # stack and context both clean
    assert len(steps.history()) == before
    # the failed step's span closed without a phase split or a child
    parent, children = _one_step(trace.tail())
    assert "phases" not in parent["attrs"] and children == []
    trace.clear()
    trainer.step(x, y)
    parent, children = _one_step(trace.tail())
    assert [c["name"] for c in children] == STEP_CHILDREN
    assert parent["parent"] is None
    assert len(steps.history()) == before + 1


def test_children_keep_the_step_id_on_the_watchdogs_waiter_thread(
        warm, tmp_path):
    """With a ``raise``-mode deadline the body of the step runs on a
    waiter thread: its spans still nest under the caller's
    ``trainer.step`` and carry its trace id."""
    trainer, x, y = warm
    watchdog.configure({"trainer.step": 60}, action="raise",
                       crash_dir=str(tmp_path))
    try:
        trainer.step(x, y)
    finally:
        watchdog.configure_from_env()
    parent, children = _one_step(trace.tail())
    assert [c["name"] for c in children] == STEP_CHILDREN
    assert {c["trace"] for c in children} == {parent["trace"]}
    assert parent["attrs"]["phases"] == steps.last()["phases"]
    assert trace.carry() == (None, ())


# -------------------------------------------------- the profiler's clock ---

def _host_events(log_dir):
    """``{line name: [(name, start_ns, end_ns, stats)]}`` of the host
    plane of the one trace under ``log_dir``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.setdefault(line.name, []).extend(
                (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                 dict(ev.stats)) for ev in line.events)
    return out


def _nested_step_spans(log_dir, want_steps):
    lines = _host_events(log_dir)
    (events,) = [evs for evs in lines.values()
                 if any(e[0] == "trainer.step" for e in evs)]
    parents = [e for e in events if e[0] == "trainer.step"]
    assert len(parents) == want_steps
    for _, lo, hi, stats in parents:
        assert "step" in stats
        inside = [e for e in events if e[0].startswith(
            ("trainer.", "compile.")) and lo < e[1] and e[2] < hi]
        assert [e[0] for e in sorted(inside, key=lambda e: e[1])] == \
            STEP_CHILDREN[:4] + ["compile.signature", "compile.execute"] \
            + STEP_CHILDREN[4:]


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_spans_reach_the_host_plane_of_any_profiler_session(warm, tmp_path):
    import jax

    trainer, x, y = warm
    # ring off: the profiler's view does not depend on it
    trace.configure(0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        trainer.step(x, y)
        trainer.step(x, y)
    finally:
        jax.profiler.stop_trace()
    _nested_step_spans(str(tmp_path), want_steps=2)


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_profiler_device_session_starts_stops_and_holds_the_spans(
        warm, tmp_path):
    trainer, x, y = warm
    fname = str(tmp_path / "prof.json")
    profiler.set_config(filename=fname, profile_device=True)
    try:
        profiler.set_state("run")
        trainer.step(x, y)
        profiler.set_state("stop")
    finally:
        profiler.set_config(filename="profile.json", profile_device=False)
    _nested_step_spans(fname + ".device", want_steps=1)
    # no Python tracer: the host plane holds no line of per-call events
    names = {e[0] for evs in _host_events(fname + ".device").values()
             for e in evs}
    assert not [n for n in names if n.startswith("$")]


# ------------------------------------- what runs on which side of the line ---

@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_guarded_step_launches_nothing_ahead_of_its_program(warm, tmp_path):
    """With the guard's read the device has nothing queued between two
    steps: no jax program of the trainer's is launched from the start of
    ``trainer.step`` to the step's own (jit's ``PjitFunction`` events in
    the profiler's host plane, not the service's counters), and the
    stream's advance and the donated inputs' release end before the read
    begins."""
    import jax

    trainer, x, y = warm
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            trainer.step(x, y)
    finally:
        jax.profiler.stop_trace()
    (events,) = [evs for evs in _host_events(str(tmp_path)).values()
                 if any(e[0] == "trainer.step" for e in evs)]
    launches = [e for e in events if e[0].startswith("PjitFunction(")]
    assert {e[0] for e in launches} >= {"PjitFunction(step_fn)"}
    parents = [e for e in events if e[0] == "trainer.step"]
    assert len(parents) == 3
    for _, lo, hi, _ in parents:
        inside = {e[0]: e for e in events
                  if e[0].startswith(("trainer.", "compile."))
                  and lo < e[1] and e[2] < hi}
        execute = inside["compile.execute"]
        early = [e[0] for e in launches if lo <= e[1] < execute[1]]
        assert early == [], early
        in_step = [e[0] for e in launches if lo <= e[1] < hi]
        assert in_step[0] == "PjitFunction(step_fn)"
        # the stream still moves by next_key()'s programs, behind the step
        assert len(set(in_step)) > 1
        sync = inside["trainer.guard_sync"]
        for name in ("trainer.rng_key", "trainer.commit", "trainer.release"):
            assert execute[2] <= inside[name][1], name
            assert inside[name][2] <= sync[1], name


@pytest.mark.parametrize("options, mesh", [
    ({"nan_guard": False}, {"dp": 1}), ({"donate": False}, {"dp": 1}),
    ({}, {"dp": 2}), ({"zero": True}, {"dp": 2})],
    ids=["noguard", "nodonate", "dp2", "zero"])
def test_every_trainer_runs_the_one_order(options, mesh):
    prev = trace.configure(2048)
    steps.reset()
    try:
        mx.random.seed(0)
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
        net.initialize(mx.init.Xavier())
        rs = np.random.RandomState(0)
        x = mx.nd.array(rs.randn(8, 8).astype(np.float32))
        y = mx.nd.array(rs.randn(8, 4).astype(np.float32))
        net(x)
        trainer = ShardedTrainer(net, gluon.loss.L2Loss(), "adam",
                                 {"learning_rate": 0.01},
                                 mesh=DeviceMesh(mesh), **options)
        before = mxcompile.stats().get("trainer", {}).get("sig_hits", 0)
        trainer.step(x, y)
        trace.clear()
        trainer.step(x, y)
        _, children = _one_step(trace.tail())
        want = [c for c in STEP_CHILDREN
                if c != "trainer.guard_sync" or options.get("nan_guard", True)]
        assert [c["name"] for c in children] == want
        assert mxcompile.stats()["trainer"]["sig_hits"] == before + 1
        phases = steps.last()["phases"]
        assert (phases["sync"] > 0) == options.get("nan_guard", True)
    finally:
        trace.configure(prev)
        steps.reset()


# ------------------------------------------------------ compile service ---

def test_call_spanned_on_a_plain_jit_is_one_execute_span():
    import jax
    import jax.numpy as jnp

    prev = trace.configure(64)
    try:
        out = mxcompile.call_spanned(jax.jit(lambda a: a + 1),
                                     jnp.float32(1.0))
        assert float(out) == 2.0
        assert [r["name"] for r in trace.tail()] == ["compile.execute"]
    finally:
        trace.configure(prev)
