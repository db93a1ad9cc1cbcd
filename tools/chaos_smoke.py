#!/usr/bin/env python
"""Chaos smoke: a 2-epoch toy fit under a canned fault schedule.

Proves the fault-tolerance stack end to end on one machine, fast:

  * per-step fault injection (delay + NaN-poisoned batches) with the
    ShardedTrainer nan_guard absorbing the bad steps,
  * checkpoint-every-epoch through CheckpointManager (atomic writes,
    CRC manifest) with an injected write failure retried,
  * an injected mid-epoch crash, then resume from the manifest,
  * an injected HANG in the train step, detected by the watchdog within
    its deadline, surfaced as a catchable StallError with a crash bundle
    written — then training continues unimpeded,
  * an injected SIGTERM preemption mid-epoch: the run DRAINS (in-flight
    step finishes, final CRC-verified checkpoint written, drain event
    recorded, exit code 75 reserved), then a fresh trainer on a
    DIFFERENT simulated device count reshards the checkpoint on load
    and finishes cleanly,
  * a MISCONFIGURED mesh (sharding rule naming an axis the mesh does not
    have) refused by the distcheck analyzer BEFORE anything compiles,
    with a param-named did-you-mean diagnostic,
  * the SERVING drill (phase 6): a model server's in-flight batch is
    wedged by an injected ``serving.batch`` hang — the watchdog writes a
    crash bundle, the batch's requests fail typed, and the server KEEPS
    SERVING; then, in a subprocess, SIGTERM lands mid-load — admission
    stops, every admitted request is answered, and the process exits 75
    for the gang scheduler (``--serve-drill`` is that child's entry),
  * the TELEMETRY pass (phase 7): a ``/metrics`` scrape on the serving
    front end under ``loadgen`` traffic carries serving / compile /
    watchdog / device-memory series consistent with the server's own
    stats and loadgen's report, and the crash bundles written by the
    injected hangs embed non-empty flight-recorder tails naming the
    wedged points (``trainer.step`` with step events, ``serving.batch``),
  * the GANG drill (phase 8): a 2-worker trainer-gang role under
    ``tools/launch.py --cluster`` (the reconciling cluster control
    plane, ``shrink_on_kill`` armed) loses rank 1 to a seeded SIGKILL
    (the ``peerloss`` fault) mid-epoch — the reconciler charges the 137
    exit to the restart ledger, shrinks the census 2 -> 1, restarts at
    generation 2 on a fresh coordinator epoch, and the resharded resume
    matches the uninterrupted run's loss trajectory within 1e-4, zero
    human intervention — all recorded in the crash-safe world record
    (``--skip-gang-drill`` for harnesses that cannot spawn),
  * the DATA-PLANE drill (phase 9): a non-JPEG record inside the
    AUGMENTED native decode loop falls back to PIL per-record with the
    SAME augmentation draws (bit-identical to an all-PIL run), an
    injected ``io.decode`` fault surfaces typed and the iterator's
    ``state_dict`` recovers at the exact position, and — in a
    subprocess — a mid-epoch SIGKILL inside the streaming loop resumes
    from the CheckpointManager-persisted iterator state with the
    identical remaining batch stream (``--skip-dataplane-drill`` skips
    the subprocess half),
  * the STRAGGLER drill (phase 10): a supervised 2-worker gang with a
    seeded ``delay`` fault on rank 1's ``trainer.step`` — the
    supervisor's single fleet ``/metrics`` scrape must flag rank 1 as a
    persistent straggler (``mxtpu_gang_straggler_*``) and record the
    ``gang.straggler`` flight event, while the gang still completes
    (``--skip-straggler-drill`` for spawn-constrained harnesses),
  * the GRADIENT-COMMS drill (phase 11): with the bucketed async
    reduction pipeline engaged (``MXNET_TPU_BUCKET_FORCE``), an
    injected ``kvstore.sync`` hang lands MID-BUCKET — while a fused
    reduction future resolves — and must surface a structured
    ``PeerLostError`` carrying the bucket census, with the same census
    embedded in the crash bundle's ``report.json`` (no silent wedge of
    the async path),
  * the INT8-SERVING drill (phase 12): an entropy-calibrated quantized
    model (``contrib.quantization``) served through its own bucket
    ladder takes an injected ``serving.batch`` fault — the request
    fails typed, the server keeps serving int8, and the ladder census
    stays intact with ``weight_dtype: int8`` still reported,
  * the SERVING-FLEET drill (phase 13): a 2-worker serving-fleet role
    under an in-process cluster supervisor takes a worker SIGKILL
    mid-load (router retries to the live worker — zero client errors —
    and the reconciler charges the restart and respawns the slot in
    place), then a ``ServingFleet`` runs a mid-load ``fleet.rollout()``
    (generation 2 health-gated warm from the disk compile cache with
    zero compiles, traffic shifted, generation 1 drained through exit
    75 with zero dropped admitted requests),
  * the MODEL-BUS drill (phase 14): a training gang streams live weight
    updates through ``mxnet_tpu.modelbus`` into a server under
    closed-loop load — versions apply between batches with ZERO
    recompiles and zero dropped admitted requests, an injected
    ``modelbus.publish`` NaN (in-transit poison, past the publisher's
    finite gate) is auto-rejected + quarantined by the subscriber, and
    the next publish rolls the bus back by re-publishing the last good
    version — with the bus running as a ``model-bus`` role whose
    reconciler observation carries the lineage and the quarantine
    (``--skip-modelbus-drill`` skips it),
  * the LOCK-WITNESS drill (phase 15): the fit/serve/bus composite
    re-run with every module-level lock wrapped by ``analysis.concur``'s
    runtime witness — the recorded per-thread acquisition orders must
    show zero inversions against each other and the static lock graph
    (``--skip-witness-drill`` skips it),
  * the CLUSTER drill (phase 16): a full ``cluster.json`` topology
    (trainer-gang streaming into a model-bus, a serving-fleet
    subscribed to it) under ``launch.py --cluster``; the SUPERVISOR is
    SIGKILLed mid-load — every worker sails on through the outage — and
    its restart re-adopts all of them from the crash-safe world record
    by pid + start-ticks: zero healthy-worker restarts, zero dropped
    admitted requests, then a SIGTERM drains the whole topology through
    the exit ladder (``--skip-cluster-drill`` skips it),
  * the HEDGING drill (phase 17): planet-scale serving resilience — a
    2-host fleet (two localhost pseudo-hosts, distinct per-host run
    dirs) with one persistently-straggling host: hedged requests must
    cut the client p99 >=3x vs hedging-off; the same topology under one
    ``cluster.json`` then loses a FULL host under load with zero
    client-visible errors; and an in-process saturating burst proves
    batch starves before interactive degrades + unmeetable deadlines
    drop before a batch slot (``--skip-hedging-drill`` skips it),
  * a final integrity pass (all params finite, manifest verifies).

Run it on a dev box or in CI::

    JAX_PLATFORMS=cpu python tools/chaos_smoke.py
    python tools/chaos_smoke.py --epochs 4 --steps 8 --seed 3
    python tools/chaos_smoke.py --phases 13,16   # a slice of the ladder

``--phases`` runs a subset (comma list / ranges); prerequisite phases
whose in-process state a selected phase consumes are added
automatically, and a per-phase wall-clock budget report prints at the
end of every run.

Exit code 0 = every recovery path worked; anything else is a real bug.
A custom schedule can be injected via MXNET_TPU_FAULTS (see
docs/MIGRATION.md "Fault tolerance & checkpointing"), replacing the
canned one.
"""
import argparse
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# phase -> phases whose in-process state (imports, trainers, crash
# bundles) it consumes. --phases expands the transitive closure, so a
# selection always runs with its prerequisites in place.
PHASE_DEPS = {1: (), 2: (1,), 3: (2,), 4: (2,), 5: (4,), 6: (5,),
              7: (3, 6), 8: (), 9: (5,), 10: (), 11: (3,), 12: (6,),
              13: (), 14: (), 15: (), 16: (), 17: ()}


def parse_phases(spec):
    """``"13,16"`` / ``"1-7"`` -> the selected phase set plus the
    transitive :data:`PHASE_DEPS` closure."""
    want = set()
    for tok in spec.split(","):
        tok = tok.strip()
        if not tok:
            continue
        if "-" in tok:
            lo, hi = tok.split("-", 1)
            want.update(range(int(lo), int(hi) + 1))
        else:
            want.add(int(tok))
    unknown = want - set(PHASE_DEPS)
    if unknown:
        raise SystemExit(f"chaos_smoke: unknown phase(s) "
                         f"{sorted(unknown)} (have 1-{len(PHASE_DEPS)})")
    frontier = list(want)
    while frontier:
        for dep in PHASE_DEPS[frontier.pop()]:
            if dep not in want:
                want.add(dep)
                frontier.append(dep)
    return want


class _PhaseClock:
    """Phase selection + per-phase wall-clock accounting.

    ``enter(n)`` closes the previous phase's span and answers whether
    phase ``n`` is selected; ``report()`` prints one budget line per
    phase that ran plus the total — the receipt CI reads to keep all
    17 phases under the tier-1 timeout and to spot the phase that eats
    the budget when they drift."""

    def __init__(self, selected):
        self.selected = frozenset(selected)
        self.t0 = time.monotonic()
        self.spans = []              # (phase, seconds) in run order
        self._current = None

    def _close(self):
        if self._current is not None:
            phase, t = self._current
            self.spans.append((phase, time.monotonic() - t))
            self._current = None

    def enter(self, phase):
        self._close()
        if phase not in self.selected:
            return False
        self._current = (phase, time.monotonic())
        return True

    def ran(self, phase):
        return phase in self.selected

    def report(self):
        self._close()
        total = time.monotonic() - self.t0
        print(f"chaos_smoke: phase budget ({len(self.spans)} phase(s) "
              f"ran, total {total:.1f}s):")
        for phase, secs in self.spans:
            print(f"  phase {phase:>2}: {secs:7.1f}s")
        return total


def batch_for(epoch, step, seed):
    import numpy as np

    import mxnet_tpu as mx

    rs = np.random.RandomState(seed * 100000 + 1000 * epoch + step)
    x = rs.randn(16, 8).astype(np.float32)
    y = (x @ rs.randn(8, 4) * 0.5).astype(np.float32)
    return mx.nd.array(x), mx.nd.array(y)


def build(seed, mesh=None):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer

    mx.random.seed(seed)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"))
    net.add(gluon.nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net(batch_for(1, 0, seed)[0])
    trainer = ShardedTrainer(net, gluon.loss.L2Loss(), "adam",
                             {"learning_rate": 0.02},
                             mesh=mesh or DeviceMesh(),
                             max_consecutive_skips=4)
    return net, trainer


def serve_drill(seed=0):
    """The phase-6 child: a 1-model server under closed-loop load takes
    a SIGTERM mid-run; the drain must answer every admitted request and
    the process must exit preempt.exit_code() (75). Prints one
    ``SERVE_DRILL {...}`` JSON line for the parent to verify."""
    import json
    import signal
    import threading

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import preempt, serving
    from mxnet_tpu.gluon import nn

    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((2, 8)))
    container = serving.ModelContainer()
    container.add_block("drill", net, example_shape=(8,), buckets=(2, 4, 8))
    server = serving.ModelServer(container, max_wait_ms=1.0).start()
    server.warmup()
    if not preempt.install():
        print("SERVE_DRILL " + json.dumps({"error": "no signal handlers"}))
        return 1

    pool = [np.random.RandomState(i).randn(1, 8).astype(np.float32)
            for i in range(8)]
    futures, flock = [], threading.Lock()
    stop = threading.Event()

    def load_worker(tid):
        i = 0
        while not stop.is_set():
            try:
                fut = server.submit("drill", pool[(tid + i) % len(pool)])
            except serving.ServerDrainingError:
                return  # admission stopped: the drain is under way
            with flock:
                futures.append(fut)
            i += 1
            time.sleep(0.002)

    workers = [threading.Thread(target=load_worker, args=(t,), daemon=True)
               for t in range(4)]
    for w in workers:
        w.start()
    time.sleep(0.4)  # get a steady stream of admitted requests going
    os.kill(os.getpid(), signal.SIGTERM)  # the platform preempts us
    while not preempt.requested():
        time.sleep(0.01)
    drained = server.drain(timeout=30.0)
    stop.set()
    for w in workers:
        w.join(timeout=5.0)
    with flock:
        admitted = len(futures)
        answered = sum(1 for f in futures if f.done()
                       and f._error is None)
    report = {"admitted": admitted, "answered": answered,
              "drained": bool(drained),
              "exit_code": preempt.exit_code()}
    print("SERVE_DRILL " + json.dumps(report), flush=True)
    if not (drained and admitted and answered == admitted):
        return 1
    # records the drain event and raises SystemExit(75) for the wrapper
    preempt.drain(save=False)
    return 1  # unreachable: drain() exits


def gang_drill(root=None):
    """Phase 8: the elastic gang acceptance drill, as subprocesses —
    rewritten against the unified cluster control plane.

    An uninterrupted 4-device reference run first, then a 2-worker
    trainer-gang under ``launch.py --cluster`` (one reconciling
    supervisor, ``shrink_on_kill`` armed) whose rank 0 SIGKILLs rank 1
    at step 6 through the seeded ``peerloss`` fault. Success = the
    reconciler recovered without help: world record shows the 137 exit,
    one charged gang restart, the shrink to the survivor, generation 2
    — and the resharded resume's post-kill loss trajectory lands within
    1e-4 of the reference. Both runs are wall-clock bounded."""
    import json as _json
    import subprocess

    import numpy as np

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    child = os.path.join(repo, "tests", "_gang_child.py")
    launch = os.path.join(repo, "tools", "launch.py")
    root = root or tempfile.mkdtemp(prefix="chaos_gang_")
    os.makedirs(root, exist_ok=True)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": repo + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    # a clean slate: the drill seeds its own faults/gang/rendezvous env
    for k in ("MXNET_TPU_FAULTS", "XLA_FLAGS", "MXTPU_GANG_DIR",
              "MXTPU_COORDINATOR", "MXTPU_NUM_WORKERS",
              "MXTPU_WORKER_ID", "MXTPU_GANG_GENERATION"):
        env.pop(k, None)

    ref_out = os.path.join(root, "ref.npz")
    proc = subprocess.run(
        [sys.executable, child],
        env={**env, "GC_DEVICES": "4", "GC_TOTAL": "12", "GC_EPOCH": "4",
             "GC_CKPT_DIR": os.path.join(root, "refck"),
             "GC_OUT": ref_out},
        capture_output=True, text=True, timeout=240)
    if proc.returncode != 0:
        print(f"FAIL: gang reference run exited {proc.returncode}:\n"
              f"{proc.stderr[-2000:]}")
        return 1

    run_dir = os.path.join(root, "run")
    out = os.path.join(root, "out.npz")
    spec_path = os.path.join(root, "cluster.json")
    with open(spec_path, "w") as f:
        _json.dump({"cluster": "chaos-gang", "roles": {"train": {
            "kind": "trainer-gang",
            "command": [sys.executable, child],
            "workers": 2, "max_restarts": 3, "backoff": 0.1,
            "grace": 60, "dead_after": 15, "coordinator_port": 9457,
            "shrink_on_kill": True}}}, f)
    proc = subprocess.run(
        [sys.executable, launch, "--cluster", spec_path,
         "--run-dir", run_dir, "--poll", "0.05"],
        env={**env, "GC_BASE_DEVICES": "2", "GC_TOTAL": "12",
             "GC_EPOCH": "4", "GC_STEP_SLEEP": "0.25", "GC_OUT": out,
             "GC_FAULTS_GEN1": "trainer.step:peerloss@6:1"},
        capture_output=True, text=True, timeout=240)
    if proc.returncode != 0:
        print(f"FAIL: cluster gang exited {proc.returncode}:\n"
              f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}")
        return 1

    with open(os.path.join(run_dir, "world.json")) as f:
        world = _json.load(f)
    kinds = [a["kind"] for a in world["actions"]]
    if world["supervisor"]["state"] != "stopped" \
            or world["generation"].get("train") != 2 \
            or world["ledger"]["train"]["restarts_total"] != 1:
        print(f"FAIL: world record is not a 1-restart recovery: "
              f"supervisor={world['supervisor'].get('state')} "
              f"generation={world['generation']} "
              f"ledger={world['ledger']}")
        return 1
    if not any(a["kind"] == "exit" and a.get("slot") == 1
               and a.get("exit") == 137 for a in world["actions"]):
        print(f"FAIL: no recorded 137 exit for rank 1: {kinds}")
        return 1
    shrink = [a for a in world["actions"] if a["kind"] == "shrink"]
    if not shrink or "[1]" not in shrink[0]["reason"]:
        print(f"FAIL: the census never shrank off killed rank 1: "
              f"{shrink or kinds}")
        return 1
    slots = world["slots"]["train"]
    if sorted(slots) != ["0"] or slots["0"]["generation"] != 2:
        print(f"FAIL: final census is not the surviving rank at "
              f"generation 2: {slots}")
        return 1

    ref, got = dict(np.load(ref_out)), dict(np.load(out))
    start = int(got["__start__"])
    if not 0 < start < 12 or int(got["__generation__"]) != 2 \
            or int(got["__devices__"]) != 2:
        print(f"FAIL: resume was not a mid-run generation-2 reshard: "
              f"start={start} gen={int(got['__generation__'])} "
              f"devices={int(got['__devices__'])}")
        return 1
    worst = float(np.max(np.abs(ref["__losses__"][start:]
                                - got["__losses__"])))
    if worst > 1e-4:
        print(f"FAIL: resumed loss trajectory diverges: "
              f"max |delta| = {worst:g} > 1e-4")
        return 1
    print(f"  gang drill: rank 1 SIGKILLed at step 6 -> reconciler "
          f"charged 1 restart, shrank the census, generation 2 resumed "
          f"at step {start} on 2 devices, loss parity {worst:.2e} "
          f"(world record {os.path.join(run_dir, 'world.json')})")
    return 0


def straggler_drill(root=None):
    """Phase 10: gang-wide straggler detection, live.

    A supervised 2-worker gang (``launch.py --supervise --metrics-port
    0``) trains with a seeded ``delay`` fault on rank 1's
    ``trainer.step``. The drill scrapes the supervisor's ONE fleet
    endpoint while the gang runs and asserts that within the run the
    ``mxtpu_gang_straggler_*`` gauges name rank 1 (persistent), and
    that the ``gang.straggler`` flight event was recorded
    (``mxtpu_flight_events_total{kind="gang.straggler"}``)."""
    import re as _re
    import subprocess
    import threading
    import urllib.request

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    child = os.path.join(repo, "tests", "_gang_child.py")
    launch = os.path.join(repo, "tools", "launch.py")
    root = root or tempfile.mkdtemp(prefix="chaos_straggle_")
    run_dir = os.path.join(root, "run")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": repo + os.pathsep
           + os.environ.get("PYTHONPATH", ""),
           "GC_BASE_DEVICES": "1", "GC_TOTAL": "16", "GC_EPOCH": "16",
           "GC_STEP_SLEEP": "0.05", "GC_STRAGGLE_RANK": "1",
           "GC_STRAGGLE_MS": "300", "GC_METRICS": "1",
           "GC_CKPT_DIR": os.path.join(root, "ckpt"),
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
    lines = []

    def _pump(stream):
        for line in stream:
            lines.append(line)

    threading.Thread(target=_pump, args=(proc.stdout,),
                     daemon=True).start()
    stderr_tail = []
    threading.Thread(target=_pump, args=(proc.stderr,),
                     daemon=True).start()
    url = None
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline and url is None:
        for line in list(lines):
            m = _re.search(r"gang metrics: (http://\S+)/metrics", line)
            if m:
                url = m.group(1)
                break
        time.sleep(0.1)
    if url is None:
        proc.kill()
        print("FAIL: supervisor never announced its metrics endpoint")
        return 1

    def metric(text, name, **labels):
        pat = name + (r"\{" if labels else r"[ {]")
        for ln in text.splitlines():
            if not _re.match(pat, ln):
                continue
            if all(f'{k}="{v}"' in ln for k, v in labels.items()):
                return float(ln.rsplit(" ", 1)[1])
        return None

    seen = None
    deadline = time.monotonic() + 180.0
    while time.monotonic() < deadline and proc.poll() is None:
        try:
            text = urllib.request.urlopen(url + "/metrics",
                                          timeout=5).read().decode()
        except OSError:
            time.sleep(0.25)
            continue
        who = metric(text, "mxtpu_gang_straggler_rank")
        persistent = metric(text, "mxtpu_gang_straggler_persistent")
        if who == 1 and persistent == 1:
            seen = {
                "rank": 1,
                "skew_ms": metric(text, "mxtpu_gang_straggler_skew_ms"),
                "score": metric(text, "mxtpu_gang_straggler_score",
                                rank="1"),
                "flight": metric(text, "mxtpu_flight_events_total",
                                 kind="gang.straggler")}
            break
        time.sleep(0.25)
    try:
        proc.wait(timeout=120.0)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait(timeout=10.0)
    if seen is None:
        print("FAIL: the supervisor scrape never flagged rank 1 as a "
              "persistent straggler\nsupervisor stdout:\n"
              + "".join(lines[-30:]))
        return 1
    if not seen["flight"]:
        print(f"FAIL: straggler flagged but no gang.straggler flight "
              f"event on the scrape: {seen}")
        return 1
    if proc.returncode != 0:
        print(f"FAIL: straggler gang exited {proc.returncode}")
        return 1
    print(f"  straggler drill: fleet scrape named rank 1 "
          f"(score {seen['score']}, skew {seen['skew_ms']}ms) with a "
          f"gang.straggler flight event; gang still completed clean")
    return 0


def fleet_drill(root=None):
    """Phase 13: the serving fleet under fire — worker SIGKILL mid-load,
    then a mid-load zero-downtime rollout.

    Drill A runs a 2-worker serving-fleet role under an in-process
    :class:`~mxnet_tpu.cluster.ClusterSupervisor` — the unified control
    plane owns the lifecycle; routing/autoscaling stay on the fleet
    decision cores — while closed-loop keep-alive clients drive the
    reconciler's router. SIGKILLing one worker's process must cost ZERO
    client-visible errors (the router retries refused connections onto
    the live worker) and the reconciler must charge the slot's restart
    budget and respawn it in place, all visible in the world record.
    Drill B calls ``fleet.rollout(v2_dir)`` mid-load on a
    :class:`~mxnet_tpu.serving.fleet.ServingFleet` — the rollout
    decision core stays fleet-layer: the health gate
    admits only warm workers (zero pending compiles — generation 2
    loads its ladder from the shared disk cache, ``compiles == 0``),
    traffic shifts, the old generation drains through exit 75 with
    every admitted request answered, and the responses flip to the v2
    model — all with zero dropped admitted requests end to end."""
    import json as _json
    import signal
    import threading

    import numpy as np

    import loadgen
    from mxnet_tpu import cluster as cluster_mod
    from mxnet_tpu.serving import fleet as fleet_mod
    from mxnet_tpu.serving import worker as worker_mod

    root = root or tempfile.mkdtemp(prefix="chaos_fleet_")
    v1 = os.path.join(root, "v1")
    v2 = os.path.join(root, "v2")
    worker_mod.write_spec(v1, worker_mod.demo_spec(models=1, seed=130))
    worker_mod.write_spec(v2, worker_mod.demo_spec(models=1, seed=131))

    lock = threading.Lock()
    stop = threading.Event()
    completed, rejected, errors = [0], [0], []
    responses = []               # (t_mono, first output value)
    url_ref = [None]             # load target: cluster router, then fleet
    pool = [np.random.RandomState(i).randn(1, 16).astype(np.float32)
            for i in range(8)]

    def load_worker(tid):
        cl = loadgen.KeepAliveClient(url_ref[0])
        i = 0
        while not stop.is_set():
            body = _json.dumps(
                {"data": pool[(tid + i) % len(pool)].tolist()}).encode()
            try:
                status, payload, _ = cl.request(
                    "POST", "/v1/models/model0:predict", body=body,
                    headers={"Content-Type": "application/json"})
            except Exception as e:
                with lock:
                    errors.append(f"{type(e).__name__}: {e}")
                i += 1
                continue
            if status == 200:
                with lock:
                    completed[0] += 1
                    if (tid + i) % len(pool) == 0:
                        out = _json.loads(payload)["outputs"][0][0][0]
                        responses.append((time.monotonic(), out))
            elif status in (429, 503):
                with lock:
                    rejected[0] += 1
            else:
                with lock:
                    errors.append(f"HTTP {status}")
            i += 1
            time.sleep(0.002)

    # ---- drill A: SIGKILL one worker under load; the reconciling
    # cluster supervisor owns the slot and must restart it in place ------
    sup = cluster_mod.ClusterSupervisor(
        {"cluster": "chaos-fleet", "roles": {"serve": {
            "kind": "serving-fleet", "model_dir": v1, "workers": 2,
            "min": 2, "max": 2, "restarts": 3, "backoff": 0.05,
            "grace": 20, "dead_after": 10}}},
        run_dir=os.path.join(root, "cluster"), poll=0.05)
    serve = sup.roles["serve"]
    try:
        sup.wait_ready(timeout=120)
    except cluster_mod.ClusterError as e:
        sup.stop(graceful=False)
        print(f"FAIL: cluster fleet never became ready: {e}")
        return 1
    tick_stop = threading.Event()

    def ticker():
        while not tick_stop.is_set():
            sup.tick()
            tick_stop.wait(0.05)

    tick_thread = threading.Thread(target=ticker, daemon=True)
    tick_thread.start()
    url_ref[0] = serve._router.url
    threads = [threading.Thread(target=load_worker, args=(t,),
                                daemon=True) for t in range(4)]
    for t in threads:
        t.start()
    time.sleep(1.0)  # a steady admitted stream before any fault

    victim = 0
    pid = serve.slots[victim].pid
    os.kill(pid, signal.SIGKILL)
    deadline = time.monotonic() + 60.0
    recovered = False
    while time.monotonic() < deadline:
        s = serve.slots.get(victim)
        if s is not None and s.restarts >= 1 and s.pid != pid \
                and s.alive() and victim in serve._routable:
            recovered = True
            break
        time.sleep(0.1)
    retries_a = serve._counters["retries"]
    stop.set()
    for t in threads:
        t.join(timeout=10.0)
    tick_stop.set()
    tick_thread.join(timeout=10.0)
    restarted = [a for a in sup.world.actions
                 if "exit 137" in (a.get("reason") or "")]
    ledger_a = dict(sup.world.ledger.get("serve") or {})
    sup.stop()
    if not recovered:
        print(f"FAIL: slot {victim} not restarted after SIGKILL: "
              f"{(sup.world.slots.get('serve') or {}).get(str(victim))}")
        return 1
    if errors:
        print(f"FAIL: SIGKILL drill leaked {len(errors)} client "
              f"error(s): {errors[:3]}")
        return 1
    if not restarted or ledger_a.get("restarts_total", 0) < 1:
        print(f"FAIL: world record never charged the 137 restart: "
              f"actions={[a['kind'] for a in sup.world.actions]} "
              f"ledger={ledger_a}")
        return 1
    print(f"  fleet SIGKILL drill: slot {victim} (pid {pid}) killed "
          f"under load -> router retried ({retries_a} retries, 0 client "
          f"errors), reconciler charged "
          f"{ledger_a.get('restarts_total')} restart and respawned the "
          f"slot in place")

    # ---- drill B: zero-downtime rollout under load (the rollout
    # decision core stays on the fleet layer) ----------------------------
    fl = fleet_mod.ServingFleet(
        v1, workers=2, run_dir=os.path.join(root, "run"),
        config={"min": 2, "max": 2, "beat": 0.2, "grace": 20},
        name="chaos-fleet",
        # the rollout's zero-recompile claim needs a cache both
        # generations share; the fleet derives none from its run dir
        env={"MXNET_TPU_CACHE_DIR": os.path.join(root, "cache")})
    fl.start(timeout=90)
    stop.clear()
    del errors[:]
    del responses[:]
    url_ref[0] = fl.url
    threads = [threading.Thread(target=load_worker, args=(t,),
                                daemon=True) for t in range(4)]
    for t in threads:
        t.start()
    time.sleep(1.0)
    pre = completed[0]
    rec = fl.rollout(v2, timeout=90)
    time.sleep(0.5)
    stop.set()
    for t in threads:
        t.join(timeout=10.0)
    stats = fl.stats()
    anns = worker_mod.read_workers(fl.run_dir)
    fl.stop()
    if errors:
        print(f"FAIL: rollout dropped requests — {len(errors)} client "
              f"error(s): {errors[:3]}")
        return 1
    if rec["state"] != "done" or \
            any(code != 75 for code in rec["drained"].values()):
        print(f"FAIL: rollout did not retire generation 1 via exit 75: "
              f"{ {k: rec[k] for k in ('state', 'drained')} }")
        return 1
    for slot, final in rec["old_final"].items():
        if final.get("failed") or \
                final.get("answered") != final.get("admitted"):
            print(f"FAIL: drained worker {slot} dropped admitted "
                  f"requests: {final}")
            return 1
    gen2 = {s: a for s, a in anns.items() if a.get("generation") == 2}
    if len(gen2) != 2 or any(
            a["compile_serving"]["compiles"] != 0 for a in gen2.values()):
        print(f"FAIL: generation 2 recompiled instead of warming from "
              f"the disk cache: "
              f"{ {s: a['compile_serving'] for s, a in gen2.items()} }")
        return 1
    if completed[0] <= pre:
        print("FAIL: no traffic completed through generation 2")
        return 1
    # the traffic must actually be the NEW model now
    vals = sorted(set(round(v, 6) for _, v in responses))
    if len(vals) < 2:
        print(f"FAIL: responses never changed across the rollout: {vals}")
        return 1
    print(f"  fleet rollout drill: generation 2 warmed from the disk "
          f"cache (0 compiles, {next(iter(gen2.values()))['compile_serving']['disk_hits']} disk hits), "
          f"old generation exits {sorted(rec['drained'].values())}, "
          f"{completed[0]} requests completed / 0 dropped "
          f"({stats['router']['retries']} router retries total)")
    return 0


def hedging_drill(root=None):
    """Phase 17: planet-scale serving resilience — a 2-host fleet under
    a persistent straggler, a full host loss, and the QoS starvation
    order.

    Drill A places a 2-worker fleet on two localhost pseudo-hosts, one
    of which stalls every serving batch 250 ms via the ``serving.batch``
    fault point, and drives the router closed-loop twice with the same
    topology: hedging OFF then ON. The straggler detector must flag the
    slow host's slot, hedged requests must fire and win (the canary
    probes that keep supplying the flagged slot are rescued at the
    hedge floor), and the client-visible p99 must drop by >=3x — with
    zero errors either way.

    Drill B runs the same 2-host shape as a serving-fleet role under
    ONE ``cluster.json`` — per-host run dirs (``host-<name>/``) whose
    announce shards merge at scrape — then SIGKILLs every worker of one
    host under load: a full host loss. The router must retry onto the
    surviving host with ZERO client-visible errors (no admitted request
    dropped) while the reconciler charges the restart and respawns the
    slot in place.

    Drill C proves the QoS contract in-process: a saturating burst
    submitted batch-FIRST must still drain interactive first (batch
    starves before interactive degrades — median interactive latency
    strictly under median batch latency), and a provably-unmeetable
    deadline must be dropped with :class:`DeadlineExceeded` BEFORE
    consuming a batch slot while the backlog around it completes."""
    import json as _json
    import signal
    import threading

    import numpy as np

    import loadgen
    import mxnet_tpu as mx
    from mxnet_tpu import cluster as cluster_mod
    from mxnet_tpu import serving
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.serving import worker as worker_mod

    root = root or tempfile.mkdtemp(prefix="chaos_hedge_")

    # ---- drill A: injected straggler, hedging off vs on ----------------
    hosts = ["local",
             {"name": "slow", "locality": "local",
              "env": {"MXNET_TPU_FAULTS": "serving.batch:delay@*:0.25"}}]
    cfg = {"beat": 0.2, "grace": 20, "interval": 0.3,
           "hedge_min_ms": 20.0}
    reps = {}
    for label, hedge in (("off", 0), ("on", 1)):
        reps[label] = loadgen.run_fleet(
            workers=2, duration=6.0, concurrency=8, models=1,
            run_dir=os.path.join(root, f"hedge-{label}"),
            hosts=[h if isinstance(h, str) else dict(h) for h in hosts],
            config=dict(cfg, hedge=hedge))
    for label, rep in reps.items():
        if rep.get("errors"):
            print(f"FAIL: hedge-{label} run leaked {rep['errors']} "
                  f"client error(s): {rep.get('first_errors')}")
            return 1
        placed = sorted(set((w or {}).get("host")
                            for w in rep["per_worker"].values()))
        if placed != ["local", "slow"]:
            print(f"FAIL: hedge-{label} workers not placed across both "
                  f"hosts: {rep['per_worker']}")
            return 1
    p99_off = reps["off"].get("p99_ms") or 0.0
    p99_on = reps["on"].get("p99_ms") or 0.0
    hedges = reps["on"].get("hedges") or {}
    if not p99_on or p99_off / p99_on < 3.0:
        print(f"FAIL: hedging did not cut p99 >=3x under the injected "
              f"straggler: off {p99_off}ms -> on {p99_on}ms "
              f"(hedges {hedges}, "
              f"stragglers {reps['on'].get('stragglers')})")
        return 1
    if hedges.get("fired", 0) < 1 or hedges.get("won", 0) < 1:
        print(f"FAIL: no hedge ever fired/won under a persistent "
              f"straggler: {hedges}")
        return 1
    if 1 not in [int(s) for s in reps["on"].get("stragglers") or []]:
        print(f"FAIL: the slow host's slot was never flagged: "
              f"stragglers={reps['on'].get('stragglers')}")
        return 1
    print(f"  hedging drill: straggler host flagged "
          f"{reps['on']['stragglers']}, hedges {hedges['fired']} fired /"
          f" {hedges['won']} won -> p99 {p99_off:.1f}ms unhedged vs "
          f"{p99_on:.1f}ms hedged ({p99_off / p99_on:.1f}x cut, "
          f"0 errors)")

    # ---- drill B: full host loss under one cluster.json ----------------
    v1 = os.path.join(root, "v1")
    worker_mod.write_spec(v1, worker_mod.demo_spec(models=1, seed=170))
    sup = cluster_mod.ClusterSupervisor(
        {"cluster": "chaos-hedge", "roles": {"serve": {
            "kind": "serving-fleet", "model_dir": v1, "workers": 2,
            "min": 2, "max": 2, "restarts": 3, "backoff": 0.05,
            "grace": 20, "dead_after": 10,
            "hosts": ["local", {"name": "b", "locality": "local"}]}}},
        run_dir=os.path.join(root, "cluster"), poll=0.05)
    serve = sup.roles["serve"]
    try:
        sup.wait_ready(timeout=120)
    except cluster_mod.ClusterError as e:
        sup.stop(graceful=False)
        print(f"FAIL: 2-host cluster fleet never became ready: {e}")
        return 1
    hostdirs = sorted(d for d in os.listdir(serve.dir)
                      if d.startswith("host-"))
    anns = worker_mod.read_workers(serve.dir)
    if hostdirs != ["host-b", "host-local"] or len(anns) != 2:
        sup.stop(graceful=False)
        print(f"FAIL: per-host run dirs / merged announce scrape wrong: "
              f"dirs={hostdirs} announces={sorted(anns)}")
        return 1

    lock = threading.Lock()
    stop = threading.Event()
    errors = []
    completed = [0]
    pool = [np.random.RandomState(i).randn(1, 16).astype(np.float32)
            for i in range(8)]

    def load_worker(tid):
        cl = loadgen.KeepAliveClient(serve._router.url)
        i = 0
        while not stop.is_set():
            body = _json.dumps(
                {"data": pool[(tid + i) % len(pool)].tolist()}).encode()
            try:
                status, _, _ = cl.request(
                    "POST", "/v1/models/model0:predict", body=body,
                    headers={"Content-Type": "application/json"})
            except Exception as e:
                with lock:
                    errors.append(f"{type(e).__name__}: {e}")
            else:
                if status == 200:
                    with lock:
                        completed[0] += 1
                elif status not in (429, 503):
                    with lock:
                        errors.append(f"HTTP {status}")
            i += 1
            time.sleep(0.002)

    tick_stop = threading.Event()

    def ticker():
        while not tick_stop.is_set():
            sup.tick()
            tick_stop.wait(0.05)

    tick_thread = threading.Thread(target=ticker, daemon=True)
    tick_thread.start()
    threads = [threading.Thread(target=load_worker, args=(t,),
                                daemon=True) for t in range(4)]
    for t in threads:
        t.start()
    time.sleep(1.0)  # a steady admitted stream before the host loss

    # host "b" owns every odd slot (hosts[slot % len(hosts)]); killing
    # them all IS the full host loss
    victims = {s: serve.slots[s].pid for s in serve.slots
               if serve._host_of(s)["name"] == "b"}
    if not victims:
        stop.set()
        tick_stop.set()
        sup.stop(graceful=False)
        print("FAIL: no slot placed on host 'b'")
        return 1
    for pid in victims.values():
        os.kill(pid, signal.SIGKILL)
    deadline = time.monotonic() + 60.0
    recovered = False
    while time.monotonic() < deadline:
        live = all(
            (s := serve.slots.get(v)) is not None and s.restarts >= 1
            and s.pid != pid and s.alive() and v in serve._routable
            for v, pid in victims.items())
        if live:
            recovered = True
            break
        time.sleep(0.1)
    stop.set()
    for t in threads:
        t.join(timeout=10.0)
    tick_stop.set()
    tick_thread.join(timeout=10.0)
    retries = serve._counters["retries"]
    ledger = dict(sup.world.ledger.get("serve") or {})
    sup.stop()
    if not recovered:
        print(f"FAIL: host-b slots {sorted(victims)} never respawned "
              f"after the host loss")
        return 1
    if errors:
        print(f"FAIL: full host loss leaked {len(errors)} client "
              f"error(s): {errors[:3]}")
        return 1
    if ledger.get("restarts_total", 0) < len(victims):
        print(f"FAIL: world record never charged the host-loss "
              f"restart(s): {ledger}")
        return 1
    print(f"  host-loss drill: host b (slots {sorted(victims)}) killed "
          f"under load -> {completed[0]} requests completed, 0 client "
          f"errors ({retries} router retries), reconciler respawned "
          f"the host's slots in place")

    # ---- drill C: batch starves before interactive degrades ------------
    mx.random.seed(17)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((2, 8)))
    container = serving.ModelContainer()
    container.add_block("qos", net, example_shape=(8,), buckets=(2, 4, 8))
    server = serving.ModelServer(container, max_wait_ms=1.0).start()
    server.warmup()
    from mxnet_tpu import faults as faults_mod
    try:
        rng = np.random.RandomState(17)
        futs = {"batch": [], "interactive": []}
        # stall the FIRST batch execution 80 ms so the whole burst is
        # queued before the collector drains anything — the class
        # medians then reflect the starvation order, not seeding speed
        faults_mod.configure({"serving.batch": "delay@1:0.08"})
        # batch submitted FIRST — and twice as much of it, so the class
        # medians separate even if a few batch rows drain while the
        # burst is still being seeded
        for klass, count in (("batch", 64), ("interactive", 32)):
            for _ in range(count):
                futs[klass].append(server.submit(
                    "qos", rng.randn(1, 8).astype(np.float32),
                    priority=klass))
        for flist in futs.values():
            for f in flist:
                f.result(timeout=60.0)
        med = {}
        for klass, flist in futs.items():
            lats = sorted(f.latency_ms() for f in flist)
            med[klass] = lats[len(lats) // 2]
        if med["interactive"] >= med["batch"]:
            print(f"FAIL: batch did not starve before interactive: "
                  f"median interactive {med['interactive']:.2f}ms vs "
                  f"batch {med['batch']:.2f}ms")
            return 1
        # a provably-unmeetable deadline dies BEFORE a batch slot while
        # the backlog around it completes untouched
        backlog = [server.submit("qos",
                                 rng.randn(1, 8).astype(np.float32),
                                 priority="batch") for _ in range(32)]
        dropped = False
        try:
            doomed = server.submit(
                "qos", rng.randn(1, 8).astype(np.float32),
                priority="interactive", deadline_ms=0.01)
        except serving.DeadlineExceeded:
            dropped = True       # submit-time estimate said unmeetable
        else:
            try:
                doomed.result(timeout=30.0)
            except serving.DeadlineExceeded:
                dropped = True   # queue-time doom check caught it
        for f in backlog:
            f.result(timeout=60.0)
        stats = server.stats()["models"]["qos"]
        drops = stats.get("deadline_dropped") or {}
        if not dropped or not sum(drops.values()):
            print(f"FAIL: unmeetable deadline was not dropped before a "
                  f"batch slot: dropped={dropped} counters={drops}")
            return 1
    finally:
        faults_mod.reset()
        server.drain(timeout=10.0)
    print(f"  qos drill: interactive median {med['interactive']:.2f}ms "
          f"vs batch {med['batch']:.2f}ms under a saturating burst "
          f"(batch starved first), unmeetable deadline dropped before a "
          f"slot ({drops})")
    return 0


def modelbus_drill(root=None, seed=0):
    """Phase 14: live weight streaming under fire — a trainer publishes
    to a model bus every 2 steps while a server under closed-loop load
    applies the versions between batches.

    The bar: zero dropped admitted requests and ZERO serving recompiles
    across every weight flip; an injected ``modelbus.publish`` NaN
    (in-transit poison — it fires AFTER the publisher's finite gate) is
    rejected + quarantined by the subscriber while serving stays pinned
    on the last good version; the next publish auto-rolls the bus back
    (re-publishes the good version) and newer weights then flow again —
    all visible in ``mxtpu_modelbus_*_total`` and the flight tail.

    The bus rides the unified control plane: it runs as a ``model-bus``
    role under an in-process ClusterSupervisor, so the reconcile loop's
    observation carries the lineage (latest version / model / step) and
    the quarantine the whole way through the drill."""
    import threading

    import numpy as np

    from mxnet_tpu import cluster as cluster_mod
    from mxnet_tpu import compile as _compile
    from mxnet_tpu import faults, modelbus, serving
    from mxnet_tpu.telemetry import export as _texport
    from mxnet_tpu.telemetry import flight as _flight

    root = root or tempfile.mkdtemp(prefix="chaos_bus_")
    faults.reset()
    net, trainer = build(seed + 14)
    container = serving.ModelContainer()
    container.add_block("chaos_bus", net, example_shape=(8,),
                        buckets=(2, 4))
    server = serving.ModelServer(container, max_wait_ms=1.0).start()
    server.warmup()
    misses0 = _compile.stats().get("serving", {}).get("misses", 0)
    bus0 = modelbus.stats()

    sup = cluster_mod.ClusterSupervisor(
        {"cluster": "chaos-bus", "roles": {"bus": {
            "kind": "model-bus", "dir": "bus", "model": "chaos_bus"}}},
        run_dir=root, poll=0.1)
    bus = trainer.publish_to(sup.bus_dir("bus"), every=2,
                             model="chaos_bus")
    watcher = server.watch_bus(bus, poll=0.02)

    lock = threading.Lock()
    stop = threading.Event()
    completed, busy, errors = [0], [0], []
    versions_seen = set()
    pool = [np.random.RandomState(i).randn(1, 8).astype(np.float32)
            for i in range(4)]

    def load_worker(tid):
        i = 0
        while not stop.is_set():
            try:
                fut = server.submit("chaos_bus", pool[(tid + i) % 4])
                fut.result(timeout=10.0)
            except serving.ServerBusyError:
                with lock:
                    busy[0] += 1
            except Exception as e:
                with lock:
                    errors.append(f"{type(e).__name__}: {e}")
            else:
                with lock:
                    completed[0] += 1
                    versions_seen.add(fut.model_version)
            i += 1
            time.sleep(0.003)

    threads = [threading.Thread(target=load_worker, args=(t,),
                                daemon=True) for t in range(2)]
    for t in threads:
        t.start()

    def fail(msg):
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
        server.drain(timeout=10.0)
        sup.stop()
        faults.reset()
        print(f"FAIL: {msg}")
        return 1

    def wait_for(cond, what, timeout=30.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if cond():
                return True
            time.sleep(0.02)
        return False

    # steady state: 4 steps -> versions 1 (step 2) and 2 (step 4) flow
    # through the bus and flip the served weights under load
    for s in range(4):
        x, y = batch_for(14, s, seed)
        trainer.step(x, y)
    if not wait_for(lambda: watcher.applied_version >= 2,
                    "steady-state versions"):
        return fail(f"watcher never applied the steady-state versions: "
                    f"{watcher.stats()}")
    obs, _ = sup.tick()
    bus_obs = obs["roles"]["bus"]
    if (bus_obs.get("latest") or 0) < 2 \
            or bus_obs.get("model") != "chaos_bus" \
            or bus_obs.get("lineage_mismatch"):
        return fail(f"reconciler observation missed the bus lineage: "
                    f"{bus_obs}")

    # in-transit poison: nan on the NEXT publish (version 3, step 6) —
    # it passes the publisher's finite gate (the injection point is
    # after it) so the SUBSCRIBER must catch and quarantine it
    faults.configure("modelbus.publish:nan@1", seed=seed)
    for s in range(4, 6):
        x, y = batch_for(14, s, seed)
        trainer.step(x, y)
    faults.reset()
    if not wait_for(
            lambda: modelbus.stats()["rejected"] > bus0["rejected"],
            "poison reject"):
        return fail(f"the poisoned version was never rejected: "
                    f"{watcher.stats()}")
    poisoned = max(watcher.rejected)
    if watcher.rejected.get(poisoned) != "nonfinite" \
            or poisoned not in bus.quarantined():
        return fail(f"poisoned version not quarantined as nonfinite: "
                    f"{watcher.rejected} / {sorted(bus.quarantined())}")
    pinned_at = watcher.applied_version
    if pinned_at >= poisoned:
        return fail(f"serving moved onto the poisoned version "
                    f"{poisoned} (applied {pinned_at})")
    obs, _ = sup.tick()
    if poisoned not in (obs["roles"]["bus"].get("quarantined") or []):
        return fail(f"reconciler observation missed the quarantine: "
                    f"{obs['roles']['bus']}")

    # recovery: the next publish finds the quarantined head, re-publishes
    # the last good version (rollback = re-publish), then streams the
    # new weights; the watcher converges onto the newest good version
    for s in range(6, 8):
        x, y = batch_for(14, s, seed)
        trainer.step(x, y)
    if not wait_for(
            lambda: (modelbus.stats()["rollbacks"] > bus0["rollbacks"]
                     and watcher.applied_version > poisoned),
            "rollback + fresh weights"):
        return fail(f"no rollback re-publication after the quarantine: "
                    f"{modelbus.stats()} / {watcher.stats()}")

    stop.set()
    for t in threads:
        t.join(timeout=10.0)
    server.drain(timeout=10.0)
    obs, _ = sup.tick()
    final_obs = dict(obs["roles"]["bus"])
    sup.stop()

    if errors:
        return fail(f"model-bus drill dropped {len(errors)} admitted "
                    f"request(s): {errors[:3]}")
    misses1 = _compile.stats().get("serving", {}).get("misses", 0)
    if misses1 != misses0:
        return fail(f"weight flips recompiled the serving ladder "
                    f"(misses {misses0} -> {misses1})")
    if len([v for v in versions_seen if v is not None]) < 2:
        return fail(f"responses never flipped model_version under load: "
                    f"{sorted(versions_seen)}")
    kinds = {e["kind"] for e in _flight.tail()}
    if not {"modelbus.publish", "modelbus.apply", "modelbus.reject",
            "modelbus.rollback"} <= kinds:
        return fail(f"flight tail is missing modelbus events: "
                    f"{sorted(k for k in kinds if 'modelbus' in k)}")
    rej_line = [l for l in _texport.render_prometheus().splitlines()
                if l.startswith("mxtpu_modelbus_rejected_total")]
    if not rej_line or float(rej_line[0].split()[-1]) < 1:
        return fail(f"/metrics does not carry the reject: {rej_line}")
    d = modelbus.stats()
    print(f"  model-bus drill: {d['published'] - bus0['published']} "
          f"versions published, {d['applied'] - bus0['applied']} applied "
          f"under load (versions seen in responses: "
          f"{sorted(v for v in versions_seen if v is not None)}), "
          f"poisoned v{poisoned} rejected+quarantined (pinned at "
          f"v{pinned_at}), {d['rollbacks'] - bus0['rollbacks']} "
          f"rollback, {completed[0]} requests completed / 0 dropped, "
          f"0 recompiles; reconciler observed lineage "
          f"{final_obs.get('model')}@v{final_obs.get('latest')} "
          f"(quarantined {final_obs.get('quarantined')})")
    return 0


def witness_drill(root=None, seed=0):
    """Phase 15: the runtime lock witness — re-run a compact composite
    of the earlier drills (a fit with an injected fault, threaded
    serving load, live weight streaming over the bus) with every
    module-level lock in the package wrapped by ``analysis.concur``'s
    witness, then cross-check the recorded per-thread acquisition
    orders against themselves and the static lock graph: zero
    inversions."""
    import threading

    import numpy as np

    from mxnet_tpu import faults, serving
    from mxnet_tpu.analysis import concur

    faults.reset()
    wrapped = concur.trace_locks()
    if not wrapped:
        print("FAIL: witness drill armed zero locks "
              "(MXNET_TPU_CONCUR=0 or already armed?)")
        return 1
    try:
        net, trainer = build(seed + 15)
        # phase 1 in miniature: one NaN batch for the guard to absorb
        # while the engine/telemetry locks are witnessed
        faults.configure("trainer.step:nan@2", seed=seed)
        for s in range(4):
            x, y = batch_for(15, s, seed)
            trainer.step(x, y)
        faults.reset()

        # phases 6 + 14 in miniature: threaded serving load while the
        # trainer streams weight versions through the bus
        container = serving.ModelContainer()
        container.add_block("chaos_wit", net, example_shape=(8,),
                            buckets=(2, 4))
        server = serving.ModelServer(container, max_wait_ms=1.0).start()
        server.warmup()
        root = root or tempfile.mkdtemp(prefix="chaos_wit_")
        bus = trainer.publish_to(os.path.join(root, "bus"), every=2)
        watcher = server.watch_bus(bus, poll=0.02)

        stop = threading.Event()
        errors = []

        def load_worker(tid):
            rng = np.random.RandomState(tid)
            while not stop.is_set():
                try:
                    server.predict(
                        "chaos_wit",
                        rng.randn(1 + tid % 2, 8).astype(np.float32),
                        timeout=10.0)
                except serving.ServerBusyError:
                    pass
                except Exception as e:
                    errors.append(f"{type(e).__name__}: {e}")
                time.sleep(0.003)

        threads = [threading.Thread(target=load_worker, args=(t,),
                                    daemon=True) for t in range(2)]
        for t in threads:
            t.start()
        for s in range(4):
            x, y = batch_for(16, s, seed)
            trainer.step(x, y)
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and watcher.applied_version < 2:
            time.sleep(0.02)
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
        server.drain(timeout=10.0)
        if errors:
            print(f"FAIL: witness drill dropped {len(errors)} "
                  f"request(s): {errors[:3]}")
            return 1
        if watcher.applied_version < 2:
            print(f"FAIL: witness drill never streamed weights: "
                  f"{watcher.stats()}")
            return 1

        inversions = concur.check_witness(raise_=False)
        state = concur.witness_state()
        if inversions:
            print("FAIL: the lock witness saw order inversions:")
            for _pair, rec, _rev, other, why in inversions[:3]:
                print(f"  {rec['sites'][0]} -> {rec['sites'][1]} vs "
                      f"{other['sites'][0]} -> {other['sites'][1]} "
                      f"({why})")
            return 1
        if not state["ring"]:
            print("FAIL: the armed witness recorded zero acquisitions "
                  "over the whole composite (dead wrappers?)")
            return 1
        print(f"  lock witness clean: {wrapped} locks wrapped, "
              f"{state['ring']} acquisitions in the ring, "
              f"{state['pairs']} nested ordered pairs witnessed across "
              f"the fit/serve/bus composite, 0 inversions")
        return 0
    finally:
        faults.reset()
        concur.untrace_locks()
        concur.reset_witness()


def cluster_drill(root=None, seed=0):
    """Phase 16: supervisor crash-safety — SIGKILL the reconciling
    cluster supervisor mid-load and restart it against the same
    crash-safe world record.

    One ``cluster.json`` runs the whole topology under ``launch.py
    --cluster``: a 2-rank trainer-gang streaming live weights into a
    model-bus role, and a 1-worker serving-fleet subscribed to that bus,
    driven by closed-loop HTTP clients the whole time. The supervisor
    process is SIGKILLed mid-load; every worker keeps running (training
    steps, bus publishes, served requests) through the outage, and the
    relaunched supervisor must RE-ADOPT all of them from the world
    record by pid + /proc start-ticks: incarnation 2, identical worker
    pids, zero healthy-worker restarts, zero spawn actions — and zero
    dropped admitted requests across the outage (connection-level
    refusals while the router is down are client-retried, never
    errors). A final SIGTERM drains the topology: the launcher exits 0
    and the trainer ranks retire through exit 75."""
    import json as _json
    import signal
    import subprocess
    import threading

    import numpy as np

    import loadgen
    from mxnet_tpu.serving import worker as worker_mod

    root = root or tempfile.mkdtemp(prefix="chaos_cluster_")
    os.makedirs(root, exist_ok=True)
    run_dir = os.path.join(root, "run")
    models = os.path.join(root, "models")
    worker_mod.write_spec(
        models, worker_mod.demo_spec(models=1, seed=777, buckets=(2, 4)))
    here = os.path.dirname(os.path.abspath(__file__))
    child = os.path.join(os.path.dirname(here), "tests",
                         "_cluster_child.py")
    launch = os.path.join(here, "launch.py")
    spec_path = os.path.join(root, "cluster.json")
    with open(spec_path, "w") as f:
        _json.dump({"cluster": "chaos-cluster", "roles": {
            "train": {"kind": "trainer-gang",
                      "command": [sys.executable, child], "workers": 2,
                      "max_restarts": 2, "backoff": 0.1, "grace": 15,
                      "dead_after": 20, "coordinator_port": 9461,
                      "publish_to": "bus"},
            "bus": {"kind": "model-bus", "model": "model0"},
            "serve": {"kind": "serving-fleet", "model_dir": models,
                      "workers": 1, "min": 1, "max": 1, "restarts": 3,
                      "backoff": 0.1, "grace": 20, "dead_after": 20,
                      "subscribe_to": "bus"}}}, f)

    env = dict(os.environ)
    for key in ("MXNET_TPU_FAULTS", "MXTPU_GANG_DIR", "MXTPU_WORKER_ID",
                "MXTPU_GANG_GENERATION", "MXTPU_COORDINATOR",
                "MXTPU_FLEET_DIR", "MXTPU_MODELBUS_DIR",
                "MXTPU_CLUSTER_DIR", "MXNET_TPU_PREEMPT",
                "MXNET_TPU_PREEMPT_DIR", "MXNET_TPU_CRASH_DIR",
                "MXNET_TPU_GANG_BEAT"):
        env.pop(key, None)
    env.update({"JAX_PLATFORMS": "cpu", "CC_SEED": "777",
                "CC_STEP_SLEEP": "0.05", "CC_PUBLISH_EVERY": "10"})
    cmd = [sys.executable, launch, "--cluster", spec_path,
           "--run-dir", run_dir, "--poll", "0.1"]
    world_path = os.path.join(run_dir, "world.json")

    def read_world():
        try:
            with open(world_path) as f:
                return _json.load(f)
        except (OSError, ValueError):
            return None

    def world_pids(world):
        return {(role, slot): rec.get("pid")
                for role, slots in (world.get("slots") or {}).items()
                for slot, rec in slots.items()
                if rec.get("state") in ("running", "starting")}

    lock = threading.Lock()
    stop = threading.Event()
    completed, retries, errors = [0], [0], []
    versions = []                # model_version of each 200, in order
    url_ref = [None]
    pool = [np.random.RandomState(i).randn(1, 16).astype(np.float32)
            for i in range(4)]

    def load_worker(tid):
        cl, cl_url = None, None
        i = 0
        while not stop.is_set():
            url = url_ref[0]
            if url is None:
                time.sleep(0.05)
                continue
            if cl is None or cl_url != url:
                cl = loadgen.KeepAliveClient(url)
                cl_url = url
            body = _json.dumps(
                {"data": pool[(tid + i) % 4].tolist()}).encode()
            try:
                status, payload, _ = cl.request(
                    "POST", "/v1/models/model0:predict", body=body,
                    headers={"Content-Type": "application/json"})
            except Exception:
                # connection-level refusal/reset — the router process is
                # the supervisor; during the outage the client retries
                with lock:
                    retries[0] += 1
                cl = None
                time.sleep(0.05)
                i += 1
                continue
            if status == 200:
                with lock:
                    completed[0] += 1
                    versions.append(
                        _json.loads(payload).get("model_version"))
            elif status not in (429, 503):
                with lock:
                    errors.append(f"HTTP {status}")
            i += 1
            time.sleep(0.01)

    def fail(msg, proc=None):
        stop.set()
        if proc is not None and proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
        print(f"FAIL: {msg}")
        return 1

    with open(os.path.join(root, "sup1.log"), "w") as logf:
        proc = subprocess.Popen(cmd, env=env, stdout=logf,
                                stderr=subprocess.STDOUT)

    # readiness = the router answers a real predict with 200 (serve
    # worker warm + routable) AND the bus has flowed a version through
    # to the responses (train rank 0 -> bus -> serve applied)
    deadline = time.monotonic() + 150.0
    ready = False
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            return fail(f"supervisor exited early (rc {proc.returncode}"
                        f"): see {os.path.join(root, 'sup1.log')}")
        world = read_world()
        url = ((world or {}).get("router") or {}).get(
            "serve", {}).get("url")
        if url:
            url_ref[0] = url
            cl = loadgen.KeepAliveClient(url)
            try:
                status, payload, _ = cl.request(
                    "POST", "/v1/models/model0:predict",
                    body=_json.dumps({"data": pool[0].tolist()}).encode(),
                    headers={"Content-Type": "application/json"})
            except Exception:
                status = None
            if status == 200 and (_json.loads(payload).get(
                    "model_version") or 0) >= 1:
                ready = True
                break
        time.sleep(0.25)
    if not ready:
        return fail("cluster never served a bus-streamed version "
                    "end to end (train -> bus -> serve)", proc)

    threads = [threading.Thread(target=load_worker, args=(t,),
                                daemon=True) for t in range(2)]
    for t in threads:
        t.start()
    time.sleep(1.5)  # a steady admitted stream before the crash

    world1 = read_world()
    if world1 is None or world1.get("supervisor", {}).get("pid") \
            != proc.pid:
        return fail(f"world record does not name the launcher as the "
                    f"supervisor: {world1 and world1.get('supervisor')}",
                    proc)
    pids1 = world_pids(world1)
    restarts1 = {(role, slot): rec.get("restarts", 0)
                 for role, slots in world1["slots"].items()
                 for slot, rec in slots.items()}
    if len(pids1) != 3:
        return fail(f"expected 3 live workers before the crash: {pids1}",
                    proc)
    actions_before = len(world1.get("actions") or [])
    pre_outage = completed[0]

    # ---- the crash: SIGKILL the supervisor (and with it the router);
    # every worker must sail on unsupervised --------------------------
    os.kill(proc.pid, signal.SIGKILL)
    proc.wait(timeout=30)
    time.sleep(1.0)  # a real outage window under load
    for (role, slot), pid in pids1.items():
        try:
            os.kill(pid, 0)
        except OSError:
            return fail(f"worker {role}/{slot} (pid {pid}) died during "
                        f"the supervisor outage")

    with open(os.path.join(root, "sup2.log"), "w") as logf:
        proc2 = subprocess.Popen(cmd, env=env, stdout=logf,
                                 stderr=subprocess.STDOUT)
    deadline = time.monotonic() + 60.0
    world2 = None
    while time.monotonic() < deadline:
        if proc2.poll() is not None:
            return fail(f"restarted supervisor exited early (rc "
                        f"{proc2.returncode}): see "
                        f"{os.path.join(root, 'sup2.log')}")
        world = read_world()
        if world and world.get("incarnation") == 2 \
                and ((world.get("router") or {}).get("serve") or {}).get(
                    "url") \
                and len(world_pids(world)) == 3:
            world2 = world
            break
        time.sleep(0.25)
    if world2 is None:
        return fail("restarted supervisor never published incarnation 2 "
                    "with a router and 3 live slots", proc2)
    url_ref[0] = world2["router"]["serve"]["url"]  # port may have moved

    # re-adoption: identical pids, zero healthy-worker restarts, adopt
    # (not spawn) actions for every slot
    pids2 = world_pids(world2)
    if pids2 != pids1:
        return fail(f"re-adoption changed worker pids: {pids1} -> "
                    f"{pids2}", proc2)
    restarts2 = {(role, slot): rec.get("restarts", 0)
                 for role, slots in world2["slots"].items()
                 for slot, rec in slots.items()}
    if restarts2 != restarts1:
        return fail(f"re-adoption charged restarts on healthy workers: "
                    f"{restarts1} -> {restarts2}", proc2)
    new_actions = (world2.get("actions") or [])[actions_before:]
    adopts = [a for a in new_actions if a.get("kind") == "adopt"]
    spawns = [a for a in new_actions if a.get("kind") == "spawn"]
    if len(adopts) < 3 or spawns:
        return fail(f"expected 3 adopt / 0 spawn actions after the "
                    f"restart, got {len(adopts)} adopt / {len(spawns)} "
                    f"spawn: {[a.get('kind') for a in new_actions]}",
                    proc2)

    # the data plane survived: traffic flows again through the new
    # router AND the served model_version keeps advancing (train rank 0
    # -> bus -> the UN-restarted serve worker)
    v_mark = None
    with lock:
        post_outage = completed[0]
        if versions:
            v_mark = versions[-1]
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        with lock:
            moved = completed[0] > post_outage + 20 and versions \
                and versions[-1] is not None \
                and versions[-1] > (v_mark or 0)
        if moved:
            break
        time.sleep(0.25)
    else:
        return fail(f"data plane stalled after re-adoption: "
                    f"{completed[0] - post_outage} completions, "
                    f"version {versions[-1] if versions else None} "
                    f"(was {v_mark})", proc2)

    stop.set()
    for t in threads:
        t.join(timeout=10.0)
    if errors:
        return fail(f"dropped {len(errors)} admitted request(s) across "
                    f"the outage: {errors[:3]}", proc2)

    # clean drain: SIGTERM -> every rank retires through exit 75, rc 0
    proc2.send_signal(signal.SIGTERM)
    try:
        rc = proc2.wait(timeout=60)
    except subprocess.TimeoutExpired:
        return fail("supervisor never drained on SIGTERM", proc2)
    world3 = read_world()
    if rc != 0 or world3.get("supervisor", {}).get("state") != "stopped":
        return fail(f"drain exited rc {rc}, supervisor state "
                    f"{world3.get('supervisor', {}).get('state')}")
    train_exits = sorted(rec.get("last_exit")
                         for rec in world3["slots"]["train"].values())
    if train_exits != [75, 75]:
        return fail(f"trainer ranks did not retire through exit 75: "
                    f"{train_exits}")
    with lock:
        seen = sorted(set(v for v in versions if v is not None))
    print(f"  cluster drill: supervisor SIGKILLed mid-load -> all 3 "
          f"workers re-adopted by pid+start-ticks (incarnation 2, "
          f"{len(adopts)} adopt / 0 spawn / 0 restarts), "
          f"{completed[0]} requests completed / 0 dropped "
          f"({retries[0]} client retries during the outage, "
          f"{pre_outage} pre-crash), bus versions kept flowing "
          f"(served {seen[:3]}..{seen[-1] if seen else None}), "
          f"SIGTERM drain rc 0 with train exits {train_exits} "
          f"(world record {world_path})")
    return 0


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--epochs", type=int, default=2)
    parser.add_argument("--steps", type=int, default=6)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--dir", default=None,
                        help="checkpoint directory (default: a tempdir)")
    parser.add_argument("--serve-drill", action="store_true",
                        help="run only the phase-6 SIGTERM-under-load "
                             "child (exits 75 on success)")
    parser.add_argument("--skip-serve-drill", action="store_true",
                        help="skip the phase-6 subprocess half (in-process "
                             "CI harnesses that cannot spawn)")
    parser.add_argument("--skip-gang-drill", action="store_true",
                        help="skip the phase-8 supervised gang drill "
                             "(two subprocess runs; same spawn caveat)")
    parser.add_argument("--skip-dataplane-drill", action="store_true",
                        help="skip the phase-9 SIGKILL-resume subprocess "
                             "half (in-process checks still run)")
    parser.add_argument("--skip-straggler-drill", action="store_true",
                        help="skip the phase-10 supervised straggler-"
                             "detection drill (subprocess gang; same "
                             "spawn caveat)")
    parser.add_argument("--skip-fleet-drill", action="store_true",
                        help="skip the phase-13 serving-fleet drills "
                             "(worker SIGKILL + mid-load rollout; "
                             "spawns worker subprocesses)")
    parser.add_argument("--skip-modelbus-drill", action="store_true",
                        help="skip the phase-14 live-weight-streaming "
                             "drill (in-process trainer -> bus -> "
                             "server with poison + rollback)")
    parser.add_argument("--skip-witness-drill", action="store_true",
                        help="skip the phase-15 lock-witness drill "
                             "(in-process fit/serve/bus composite with "
                             "analysis.concur's runtime witness armed)")
    parser.add_argument("--skip-cluster-drill", action="store_true",
                        help="skip the phase-16 cluster control-plane "
                             "drill (supervisor SIGKILL mid-load + "
                             "re-adoption; spawns a worker topology)")
    parser.add_argument("--skip-hedging-drill", action="store_true",
                        help="skip the phase-17 planet-scale serving "
                             "drills (2-host straggler hedging + full "
                             "host loss + QoS starvation order; spawns "
                             "four short-lived fleets' worth of worker "
                             "subprocesses)")
    parser.add_argument("--phases", default=None, metavar="N,M",
                        help="run only these phases (comma list and/or "
                             "ranges, e.g. '13,16' or '1-7'); "
                             "prerequisite phases are added "
                             "automatically")
    args = parser.parse_args(argv)

    if args.serve_drill:
        return serve_drill(seed=args.seed)

    import numpy as np

    from mxnet_tpu import checkpoint, faults

    ckpt_dir = args.dir or tempfile.mkdtemp(prefix="chaos_smoke_")
    total_steps = args.epochs * args.steps
    crash_at = total_steps // 2 + 1

    selected = parse_phases(args.phases) if args.phases \
        else set(PHASE_DEPS)
    clock = _PhaseClock(selected)
    if args.phases:
        print(f"chaos_smoke: running phases {sorted(selected)} "
              f"(--phases {args.phases} plus prerequisites)")

    env_schedule = os.environ.get("MXNET_TPU_FAULTS")
    print(f"chaos_smoke: ckpt dir {ckpt_dir}, "
          f"{args.epochs} epochs x {args.steps} steps")

    manager = checkpoint.CheckpointManager(ckpt_dir, prefix="chaos", keep=2)

    # phase 1 (canned; MXNET_TPU_FAULTS overrides): one NaN batch for the
    # guard to absorb + one checkpoint-write failure for the retry to
    # absorb (a point holds one spec, so the crash runs as phase 2)
    if clock.enter(1):
        net, trainer = build(args.seed)
        faults.configure(env_schedule or
                         "trainer.step:nan@2;ckpt.write:raise@2",
                         seed=args.seed)
        save = faults.retry(trainer.save_checkpoint, retries=2, backoff=0.01,
                            retry_on=(faults.InjectedFault, OSError))
        step = 0
        for epoch in range(1, args.epochs + 1):
            for s in range(args.steps):
                x, y = batch_for(epoch, s, args.seed)
                trainer.step(x, y)
                step += 1
            save(manager, epoch)
            print(f"  epoch {epoch}: checkpointed at step {trainer._t} "
                  f"(skipped so far: {trainer.skipped_steps})")
        if env_schedule is None and trainer.skipped_steps < 1:
            print("FAIL: the NaN injection was not absorbed by the guard")
            return 1

    # phase 2: crash mid-epoch, resume from the manifest, finish
    if clock.enter(2):
        faults.configure(f"trainer.step:raise@{crash_at}", seed=args.seed)
        crashed = False
        try:
            for epoch in range(args.epochs + 1, 2 * args.epochs + 1):
                for s in range(args.steps):
                    x, y = batch_for(epoch, s, args.seed)
                    trainer.step(x, y)
                trainer.save_checkpoint(manager, epoch)
        except faults.InjectedFault as e:
            crashed = True
            print(f"  injected crash: {e}")
        faults.reset()
        if not crashed:
            print("FAIL: the injected crash never fired")
            return 1

        net2, trainer2 = build(args.seed + 1)  # "new process": fresh init
        entry = trainer2.resume(manager)
        print(f"  resumed from epoch {entry['epoch']} (step {entry['step']})")
        for epoch in range(entry["epoch"] + 1, 2 * args.epochs + 1):
            for s in range(args.steps):
                x, y = batch_for(epoch, s, args.seed)
                trainer2.step(x, y)
            trainer2.save_checkpoint(manager, epoch)

    # phase 3: wedge a step; the watchdog must convert the hang into a
    # StallError + crash bundle within the deadline, then training
    # continues cleanly once the fault schedule is cleared
    if clock.enter(3):
        from mxnet_tpu import watchdog

        hang_secs = 2.0
        watchdog.configure({"trainer.step": 0.8},
                           crash_dir=os.path.join(ckpt_dir, "crash"),
                           interval=0.1)
        faults.configure(f"trainer.step:hang@1:{hang_secs}", seed=args.seed)
        x, y = batch_for(1, 0, args.seed)
        try:
            trainer2.step(x, y)
            print("FAIL: the injected hang was not detected")
            return 1
        except watchdog.StallError as e:
            print(f"  watchdog caught the hang: {e}")
            if not (e.bundle and os.path.isdir(e.bundle)):
                print("FAIL: no crash bundle written for the stall")
                return 1
        faults.reset()
        watchdog.configure(None)
        # drain the abandoned waiter (daemon) before mutating the trainer again
        time.sleep(hang_secs + 0.5)
        trainer2.step(x, y)

    # phase 4: preempt mid-epoch with SIGTERM (the 'preempt' fault mode
    # delivers it to this process at the trainer.step injection point);
    # the drain flag lets the in-flight step finish, a final checkpoint
    # lands, a drain event is recorded — then a FRESH trainer on a
    # different simulated device count reshards the checkpoint on load
    # and finishes cleanly
    if clock.enter(4):
        import jax

        from mxnet_tpu import preempt
        from mxnet_tpu.parallel import DeviceMesh

        if not preempt.install():
            print("FAIL: could not install preemption handlers")
            return 1
        faults.configure("trainer.step:preempt@2", seed=args.seed)
        drained = None
        for s in range(args.steps):
            x, y = batch_for(1, s, args.seed)
            trainer2.step(x, y)
            if preempt.requested():
                # exit=False: this smoke keeps running where a real job would
                # now exit preempt.exit_code() (75) for its wrapper
                drained = preempt.drain(exit=False, directory=ckpt_dir)
                break
        faults.reset()
        if drained is None:
            print("FAIL: the injected SIGTERM never requested a drain")
            return 1
        if drained["final_checkpoint"] != "written":
            print(f"FAIL: drain checkpoint not written: {drained}")
            return 1
        print(f"  drained on {drained.get('signal')} (would exit "
              f"{drained['exit_code']}); event: {drained['recorded']}")
        entry, _ = manager.load()
        if not (entry["meta"].get("drain") and manager.verify(entry)):
            print("FAIL: drained checkpoint missing drain meta or CRC-bad")
            return 1
        preempt.uninstall()

        n = jax.device_count()
        resume_mesh = DeviceMesh({"dp": max(1, n // 2)})
        net3, trainer3 = build(args.seed + 2, mesh=resume_mesh)
        import warnings

        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # the reshard notice, if n > 1
            entry3 = trainer3.resume(manager)
        print(f"  resharded resume onto {resume_mesh!r} (from {n} devices) "
              f"at step {entry3['step']}")
        for s in range(args.steps):
            x, y = batch_for(2, s, args.seed)
            trainer3.step(x, y)
        trainer3.save_checkpoint(manager, entry3["epoch"] + 1)
        net2 = net3  # the integrity pass below checks the resumed net

    # phase 5: distributed-correctness pre-check — a sharding rule naming
    # a nonexistent mesh axis must be REFUSED before anything compiles
    # (analysis.distcheck pass 1), param-named with a did-you-mean hint
    if clock.enter(5):
        import mxnet_tpu as mx
        from mxnet_tpu.analysis import distcheck
        from mxnet_tpu import gluon

        bad_net = gluon.nn.Dense(16, in_units=8)
        bad_net.initialize(mx.init.Xavier())
        bad_net(batch_for(1, 0, args.seed)[0])
        pname = next(iter(bad_net.collect_params()))
        try:
            from mxnet_tpu.parallel import ShardedTrainer as _ST

            _ST(bad_net, gluon.loss.L2Loss(), "sgd", {},
                mesh=DeviceMesh({"dp": max(1, n // 2)}),
                rules={pname: ("dpp",)})
            print("FAIL: misconfigured mesh rule was not refused by distcheck")
            return 1
        except distcheck.DistCheckError as e:
            bad = [i for i in e.issues if i.code == "undefined-axis"]
            if not bad or pname not in bad[0].node or \
                    "did you mean" not in bad[0].message:
                print(f"FAIL: distcheck refusal lacks a named diagnostic: {e}")
                return 1
            print(f"  distcheck refused the bad mesh config: {bad[0]}")

    # phase 6: serving — (a) an injected serving.batch hang is caught by
    # the watchdog (crash bundle + typed request failure) and the server
    # KEEPS SERVING; (b) in a subprocess, SIGTERM mid-load drains
    # gracefully (all admitted requests answered) and exits 75
    if clock.enter(6):
        from mxnet_tpu import serving, watchdog as _wd

        mx.random.seed(args.seed + 7)
        serve_net = gluon.nn.HybridSequential()
        serve_net.add(gluon.nn.Dense(16, activation="relu"),
                      gluon.nn.Dense(4))
        serve_net.initialize(mx.init.Xavier())
        serve_net(mx.nd.zeros((2, 8)))
        scontainer = serving.ModelContainer()
        scontainer.add_block("chaos", serve_net, example_shape=(8,),
                             buckets=(2, 4))
        sserver = serving.ModelServer(scontainer, max_wait_ms=1.0).start()
        sserver.warmup()
        serve_hang = 2.0
        _wd.configure({"serving.batch": 0.6},
                      crash_dir=os.path.join(ckpt_dir, "crash"), interval=0.1)
        faults.configure(f"serving.batch:hang@1:{serve_hang}", seed=args.seed)
        xs = np.random.RandomState(args.seed).randn(1, 8).astype(np.float32)
        fut = sserver.submit("chaos", xs)
        try:
            fut.result(timeout=10.0)
            print("FAIL: the injected serving hang was not detected")
            return 1
        except serving.RequestError as e:
            if not isinstance(e.cause, _wd.StallError):
                print(f"FAIL: serving batch failed without a StallError: {e}")
                return 1
            if not (e.cause.bundle and os.path.isdir(e.cause.bundle)):
                print("FAIL: no crash bundle for the serving stall")
                return 1
            print(f"  serving watchdog caught the wedged batch: {e.cause}")
        faults.reset()
        _wd.configure(None)
        time.sleep(serve_hang + 0.5)  # let the abandoned waiter drain out
        y = sserver.predict("chaos", xs, timeout=10.0)  # server kept serving
        if y.shape != (1, 4):
            print(f"FAIL: post-stall predict shape {y.shape}")
            return 1
        print("  server kept serving after the stall "
              f"(stats: {sserver.stats()['models']['chaos']['stalled_batches']}"
              " stalled batch)")
        sserver.drain(timeout=10.0)

        if not args.skip_serve_drill:
            import json as _json
            import subprocess
            import sys as _sys

            env = dict(os.environ)
            env.setdefault("JAX_PLATFORMS", "cpu")
            # the drill must see pristine fault/watchdog state
            env.pop("MXNET_TPU_FAULTS", None)
            proc = subprocess.run(
                [_sys.executable, os.path.abspath(__file__), "--serve-drill",
                 "--seed", str(args.seed)],
                capture_output=True, text=True, timeout=300, env=env)
            lines = [l for l in proc.stdout.splitlines()
                     if l.startswith("SERVE_DRILL ")]
            if proc.returncode != 75 or not lines:
                print(f"FAIL: serve drill rc={proc.returncode} (want 75)\n"
                      f"stdout={proc.stdout}\nstderr={proc.stderr[-2000:]}")
                return 1
            drill = _json.loads(lines[-1].split(" ", 1)[1])
            if not drill["admitted"] or drill["answered"] != drill["admitted"]:
                print(f"FAIL: serve drill dropped requests: {drill}")
                return 1
            print(f"  SIGTERM-under-load drill: {drill['answered']}/"
                  f"{drill['admitted']} admitted requests answered, exit 75")

    # phase 7: telemetry — a /metrics scrape on the serving front end
    # under loadgen traffic must carry serving/compile/watchdog/memory
    # series CONSISTENT with the server's own stats and loadgen's
    # report; and the crash bundles written by the earlier injected
    # hangs must embed a non-empty flight-recorder tail NAMING the
    # wedged point (the post-mortem story with no profiler running)
    if clock.enter(7):
        import re as _re
        import urllib.request

        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import loadgen

        from mxnet_tpu import compile as _compile

        tcontainer = loadgen.build_demo_container(models=2, dim=8)
        tserver = serving.ModelServer(tcontainer).start()
        tserver.warmup()
        tfront = serving.HttpFrontEnd(tserver).start()
        lrep = loadgen.run_inproc(duration=1.0, mode="closed", concurrency=4,
                                  dim=8, server=tserver, warmup=False)
        if not lrep["completed"]:
            print(f"FAIL: loadgen completed nothing: {lrep}")
            return 1
        text = urllib.request.urlopen(tfront.url + "/metrics",
                                      timeout=10).read().decode()

        def metric(name, **labels):
            pat = name + r"\{" if labels else name + r"[ {]"
            for line in text.splitlines():
                if not _re.match(pat, line):
                    continue
                if all(f'{k}="{v}"' in line for k, v in labels.items()):
                    return float(line.rsplit(" ", 1)[1])
            return None

        sstats = tserver.stats()["models"]
        scraped = {m: metric("mxtpu_serving_requests_total", model=m,
                             outcome="completed") for m in sstats}
        if any(scraped[m] != sstats[m]["completed"] for m in sstats):
            print(f"FAIL: /metrics serving counters {scraped} disagree with "
                  f"server stats")
            return 1
        if int(sum(scraped.values())) != lrep["completed"]:
            print(f"FAIL: scraped completions {sum(scraped.values())} != "
                  f"loadgen report {lrep['completed']}")
            return 1
        chits = metric("mxtpu_compile_cache_hits_total", site="serving")
        if chits is None or \
                chits != _compile.stats()["serving"]["hits"]:
            print(f"FAIL: /metrics compile series {chits} disagree with "
                  f"compile.stats()")
            return 1
        stalls = metric("mxtpu_watchdog_stalls_total")
        if not stalls or stalls < 2:  # phase 3 (trainer) + phase 6 (serving)
            print(f"FAIL: watchdog stall series missing/low: {stalls}")
            return 1
        if metric("mxtpu_flight_ring_size") is None or \
                not [l for l in text.splitlines()
                     if l.startswith("mxtpu_device_memory_live_bytes")]:
            print("FAIL: flight/memory series missing from /metrics")
            return 1
        tfront.close()
        tserver.drain(timeout=10.0)
        print(f"  /metrics scrape consistent: {int(sum(scraped.values()))} "
              f"completions, {int(stalls)} stalls, compile hits {int(chits)}")

        import json as _json2

        crash_root = os.path.join(ckpt_dir, "crash")
        for marker, want_point, want_step_events in (
                ("trainer_step", "trainer.step", True),
                ("serving_batch", "serving.batch", False)):
            bundles = [os.path.join(crash_root, n)
                       for n in os.listdir(crash_root) if marker in n]
            if not bundles:
                print(f"FAIL: no {marker} crash bundle found")
                return 1
            with open(os.path.join(max(bundles, key=os.path.getmtime),
                                   "flight.json")) as f:
                ftail = _json2.load(f)
            if not ftail:
                print(f"FAIL: empty flight tail in the {marker} bundle")
                return 1
            if not any(e.get("point") == want_point for e in ftail):
                print(f"FAIL: {marker} flight tail never names {want_point}")
                return 1
            if want_step_events and not any(
                    str(e.get("kind", "")).startswith("step.")
                    for e in ftail):
                print(f"FAIL: {marker} flight tail carries no step events")
                return 1
        print("  flight-recorder tails in both crash bundles name the "
              "wedged points")

    # phase 8: elastic gang supervision — a supervised 2-worker gang
    # loses a rank to a seeded SIGKILL mid-epoch and must recover on
    # its own: census shrink, generation bump, resharded resume, loss
    # parity with the uninterrupted reference within 1e-4
    if clock.enter(8):
        if not args.skip_gang_drill:
            rc = gang_drill(root=os.path.join(ckpt_dir, "gang"))
            if rc:
                return rc

    # phase 9: the streaming data plane — (a) a non-JPEG record inside
    # the AUGMENTED native decode loop is retried through PIL with the
    # SAME per-image augmentation draws (bit-identical to an all-PIL
    # run); (b) an injected io.decode fault surfaces typed and a fresh
    # iterator restored from state_dict continues at the exact position;
    # (c) subprocess: SIGKILL mid-epoch inside the loop, resume from the
    # CheckpointManager-persisted state, identical remaining stream
    if clock.enter(9):
        import io as _pio
        import zlib as _zlib

        from PIL import Image as _Image

        import mxnet_tpu.recordio as _recordio
        from mxnet_tpu import native as _native

        dp_root = os.path.join(ckpt_dir, "dataplane")
        os.makedirs(dp_root, exist_ok=True)
        dp_rec_path = os.path.join(dp_root, "dp.rec")
        dp_rs = np.random.RandomState(args.seed)
        dp_rec = _recordio.MXIndexedRecordIO(os.path.join(dp_root, "dp.idx"),
                                             dp_rec_path, "w")
        for i in range(24):
            arr = dp_rs.randint(0, 255, (32, 32, 3), np.uint8)
            buf = _pio.BytesIO()
            # record 5: a PNG — valid image, but the native libjpeg loop
            # rejects it, forcing the per-record PIL retry path
            _Image.fromarray(arr).save(buf, "PNG" if i == 5 else "JPEG",
                                       **({} if i == 5 else {"quality": 95}))
            dp_rec.write_idx(i, _recordio.pack(
                _recordio.IRHeader(0, float(i), i, 0), buf.getvalue()))
        dp_rec.close()
        dp_kw = dict(path_imgrec=dp_rec_path, data_shape=(3, 24, 24),
                     batch_size=4, shuffle=True, rand_crop=True,
                     rand_mirror=True, color_jitter=0.2, seed=args.seed,
                     round_batch=False, prefetch_buffer=0,
                     num_parts=1, part_index=0)
        native_stream = [b.data[0].asnumpy()
                         for b in mx.io.ImageRecordIter(**dp_kw)]
        orig_aug = _native.decode_augment_batch
        _native.decode_augment_batch = lambda *a, **k: None
        try:
            pil_stream = [b.data[0].asnumpy()
                          for b in mx.io.ImageRecordIter(**dp_kw)]
        finally:
            _native.decode_augment_batch = orig_aug
        if len(native_stream) != len(pil_stream) or any(
                not np.array_equal(a, b)
                for a, b in zip(native_stream, pil_stream)):
            print("FAIL: augmented native loop (with PIL per-record retry) "
                  "diverges from the all-PIL fallback")
            return 1
        if _native.status()["augment"]:
            print("  augmented native loop == PIL fallback bit-exact "
                  "(PNG record retried in-loop)")

        faults.configure("io.decode:raise@2", seed=args.seed)
        dp_it = mx.io.ImageRecordIter(**dp_kw)
        dp_states, dp_seen, dp_fault = [dp_it.state_dict()], [], None
        try:
            for b in dp_it:
                dp_seen.append(b.data[0].asnumpy())
                dp_states.append(dp_it.state_dict())
        except faults.InjectedFault as e:
            dp_fault = e
        faults.reset()
        if dp_fault is None:
            print("FAIL: the injected io.decode fault never fired")
            return 1
        dp_resume = mx.io.ImageRecordIter(**dp_kw)
        dp_resume.load_state_dict(dp_states[len(dp_seen)])
        dp_rest = [b.data[0].asnumpy() for b in dp_resume]
        want = native_stream[len(dp_seen):]
        if len(dp_rest) != len(want) or any(
                not np.array_equal(a, b) for a, b in zip(dp_rest, want)):
            print("FAIL: post-fault state_dict resume is not at the exact "
                  "position")
            return 1
        print(f"  io.decode fault at batch {len(dp_seen) + 1} -> typed "
              f"InjectedFault; state_dict resume replayed the remaining "
              f"{len(dp_rest)} batches bit-exact")

        if not args.skip_dataplane_drill:
            import subprocess as _sp

            child = os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "tests", "_dataplane_child.py")
            denv = {**os.environ, "JAX_PLATFORMS": "cpu",
                    "DP_REC": dp_rec_path,
                    "DP_CKPT": os.path.join(dp_root, "ck"),
                    "DP_BATCH": "4"}
            denv.pop("MXNET_TPU_FAULTS", None)
            ref_out = os.path.join(dp_root, "ref.npz")
            proc = _sp.run([sys.executable, child],
                           env={**denv, "DP_OUT": ref_out,
                                "DP_CKPT": os.path.join(dp_root, "refck")},
                           capture_output=True, text=True, timeout=120)
            if proc.returncode != 0:
                print(f"FAIL: dataplane reference run exited "
                      f"{proc.returncode}:\n{proc.stderr[-1500:]}")
                return 1
            proc = _sp.run([sys.executable, child],
                           env={**denv, "DP_KILL_AFTER": "2"},
                           capture_output=True, text=True, timeout=120)
            if proc.returncode != -9:  # SIGKILL, no cleanup ran
                print(f"FAIL: kill child exited {proc.returncode}, "
                      f"want SIGKILL:\n{proc.stderr[-1500:]}")
                return 1
            res_out = os.path.join(dp_root, "res.npz")
            proc = _sp.run([sys.executable, child],
                           env={**denv, "DP_RESUME": "1", "DP_OUT": res_out},
                           capture_output=True, text=True, timeout=120)
            if proc.returncode != 0:
                print(f"FAIL: dataplane resume run exited "
                      f"{proc.returncode}:\n{proc.stderr[-1500:]}")
                return 1
            ref_np, res_np = dict(np.load(ref_out)), dict(np.load(res_out))
            start9 = int(res_np["__start__"])
            if start9 != 2:
                print(f"FAIL: resume started at batch {start9}, want 2")
                return 1
            if not np.array_equal(res_np["crcs"], ref_np["crcs"][start9:]):
                print("FAIL: resumed stream checksums diverge from the "
                      "uninterrupted run")
                return 1
            print(f"  SIGKILL at batch {start9} -> resume replayed batches "
                  f"{start9 + 1}..{len(ref_np['crcs'])} bit-exact "
                  "(augmentation stream included)")

    # phase 10: gang-wide straggler detection — a supervised 2-worker
    # run with a seeded delay fault on rank 1's trainer.step must show
    # mxtpu_gang_straggler_* naming rank 1 on the supervisor's ONE
    # fleet scrape endpoint, with the gang.straggler flight event
    # recorded (the PR 12 tracing-plane acceptance)
    if clock.enter(10):
        if not args.skip_straggler_drill:
            rc = straggler_drill(root=os.path.join(ckpt_dir, "straggle"))
            if rc:
                return rc

    # phase 11: bucketed gradient collectives — an injected kvstore.sync
    # hang MID-BUCKET (while a fused reduction future resolves) must
    # surface a structured PeerLostError carrying the bucket census,
    # with the same census embedded in the crash bundle's report.json —
    # never a silent wedge of the async path
    if clock.enter(11):
        import json as _json

        from mxnet_tpu import kvstore as kv_mod
        from mxnet_tpu.kvstore import PeerLostError

        os.environ["MXNET_TPU_BUCKET_FORCE"] = "1"  # full pipeline, 1 proc
        try:
            import mxnet_tpu as mx_

            kv = kv_mod.create("dist_sync")
            if kv._pipeline is None:
                print("FAIL: bucket pipeline not constructed")
                return 1
            for i in range(4):
                kv.init(i, mx_.nd.zeros((8, 8)))
            watchdog.configure({"kvstore.sync": 0.8},
                               crash_dir=os.path.join(ckpt_dir, "crash"),
                               interval=0.1)
            faults.configure("kvstore.sync:hang@1:2.0", seed=args.seed)
            for i in reversed(range(4)):  # backward order, one fused bucket
                kv.push(i, mx_.nd.ones((8, 8)))
            try:
                kv.pull(0, mx_.nd.zeros((8, 8)))
                print("FAIL: the mid-bucket hang was not detected")
                return 1
            except PeerLostError as e:
                if not e.census or not e.census["plan"]["buckets"]:
                    print(f"FAIL: PeerLostError carries no bucket census: "
                          f"{e.census}")
                    return 1
                if not (e.bundle and os.path.isdir(e.bundle)):
                    print("FAIL: no crash bundle for the bucket stall")
                    return 1
                with open(os.path.join(e.bundle, "report.json")) as f:
                    rep = _json.load(f)
                if not rep.get("kvstore_buckets"):
                    print("FAIL: bucket census missing from the crash "
                          "bundle report")
                    return 1
                print(f"  mid-bucket hang -> PeerLostError rank "
                      f"{e.rank}/{e.num_workers} with census "
                      f"({len(e.census['plan']['buckets'])} buckets, "
                      f"{e.census['pending']['inflight']} in flight); "
                      f"bundle {e.bundle}")
            faults.reset()
            watchdog.configure(None)
            time.sleep(2.5)  # drain the abandoned waiter before moving on
        finally:
            os.environ.pop("MXNET_TPU_BUCKET_FORCE", None)

    # phase 12: int8 serving — an entropy-calibrated quantized model
    # served through its own bucket ladder takes an injected
    # serving.batch fault: the request fails TYPED (RequestError), the
    # server keeps serving int8, and the ladder census stays intact
    # (every warmed bucket still servable — the quantized executables
    # survived the fault)
    if clock.enter(12):
        from mxnet_tpu.contrib import quantization as _quant

        mx.random.seed(args.seed + 12)
        qdata = mx.sym.var("data")
        qnet = mx.sym.FullyConnected(qdata, num_hidden=16, name="chaosq_fc1")
        qnet = mx.sym.Activation(qnet, act_type="relu")
        qnet = mx.sym.FullyConnected(qnet, num_hidden=4, name="chaosq_fc2")
        qrng = np.random.RandomState(args.seed + 12)
        qfargs = {"chaosq_fc1_weight": mx.nd.array(
                      (qrng.randn(16, 8) * 0.2).astype(np.float32)),
                  "chaosq_fc1_bias": mx.nd.array(np.zeros(16, np.float32)),
                  "chaosq_fc2_weight": mx.nd.array(
                      (qrng.randn(4, 16) * 0.2).astype(np.float32)),
                  "chaosq_fc2_bias": mx.nd.array(np.zeros(4, np.float32))}
        qcalib = mx.io.NDArrayIter(
            qrng.randn(64, 8).astype(np.float32), batch_size=16,
            label_name=None)
        qsym12, qargs12, _ = _quant.quantize_model(
            qnet, qfargs, {}, data_names=("data",), calib_data=qcalib,
            calib_mode="entropy")
        qcont = serving.ModelContainer()
        qcont.add_symbol("chaos_int8", qsym12, qargs12, example_shape=(8,),
                         buckets=(2, 4))
        qserver = serving.ModelServer(qcont, max_wait_ms=1.0).start()
        qserver.warmup()
        qstats0 = qserver.stats()["models"]["chaos_int8"]
        if qstats0.get("weight_dtype") != "int8":
            print(f"FAIL: served quantized model not reported int8: {qstats0}")
            return 1
        faults.configure("serving.batch:raise@1", seed=args.seed)
        qx = np.random.RandomState(args.seed).randn(1, 8).astype(np.float32)
        try:
            qserver.predict("chaos_int8", qx, timeout=10.0)
            print("FAIL: the injected int8 serving fault was not raised")
            return 1
        except serving.RequestError as e:
            print(f"  int8 serving fault surfaced typed: {type(e).__name__}")
        faults.reset()
        # the whole ladder must still be servable: drive one batch into
        # every bucket and require each to land in the census
        y12 = qserver.predict("chaos_int8", qx, timeout=10.0)
        if y12.shape != (1, 4):
            print(f"FAIL: post-fault int8 predict shape {y12.shape}")
            return 1
        qserver.predict("chaos_int8",
                        np.repeat(qx, 3, axis=0), timeout=10.0)
        qstats1 = qserver.stats()["models"]["chaos_int8"]
        census12 = qstats1["bucket_census"]
        if not {2, 4} <= {int(b) for b in census12} \
                or qstats1.get("weight_dtype") != "int8":
            print(f"FAIL: int8 ladder census damaged after the fault: "
                  f"{qstats1}")
            return 1
        print(f"  int8 server kept serving after the fault "
              f"(ladder census {census12}, calib mode "
              f"{_quant.last_calibration()['mode']})")
        qserver.drain(timeout=10.0)

    # phase 13: the serving fleet — a worker SIGKILLed under load is
    # retried by the router (zero client errors) and restarted by the
    # serving-mode supervisor; a mid-load rollout health-gates a warm
    # generation 2 (zero compiles — disk-cache loads only), shifts
    # traffic, drains generation 1 through exit 75 with every admitted
    # request answered
    if clock.enter(13):
        if not args.skip_fleet_drill:
            rc = fleet_drill(root=os.path.join(ckpt_dir, "fleet"))
            if rc:
                return rc

    # phase 14: the model bus — a trainer streams weight versions into a
    # loaded server (zero recompiles, zero dropped requests); an
    # injected in-transit NaN is rejected + quarantined by the
    # subscriber and the next publish rolls the bus back to known-good
    if clock.enter(14):
        if not args.skip_modelbus_drill:
            rc = modelbus_drill(root=os.path.join(ckpt_dir, "bus"),
                                seed=args.seed)
            if rc:
                return rc

    # phase 15: the lock witness — the fit/serve/bus composite again,
    # this time with every module-level lock wrapped by the concurrency
    # analyzer's runtime witness; the recorded acquisition orders must
    # show zero inversions against each other and the static lock graph
    if clock.enter(15):
        if not args.skip_witness_drill:
            rc = witness_drill(root=os.path.join(ckpt_dir, "witness"),
                               seed=args.seed)
            if rc:
                return rc

    # phase 16: the cluster control plane under fire — a full
    # cluster.json topology (trainer-gang -> model-bus -> serving-fleet)
    # under launch.py --cluster; the SUPERVISOR is SIGKILLed mid-load
    # and its restart re-adopts every running worker from the crash-safe
    # world record (zero healthy-worker restarts, zero dropped admitted
    # requests), then a SIGTERM drains the whole topology through the
    # exit ladder
    if clock.enter(16):
        if not args.skip_cluster_drill:
            rc = cluster_drill(root=os.path.join(ckpt_dir, "cluster"),
                               seed=args.seed)
            if rc:
                return rc

    # phase 17: planet-scale serving resilience — a 2-host fleet with a
    # persistently-straggling host (hedging must cut p99 >=3x, zero
    # errors), a full host loss under one cluster.json (zero client
    # errors, reconciler respawns the host's slots), and the QoS
    # starvation order (batch starves before interactive; unmeetable
    # deadlines drop before a batch slot)
    if clock.enter(17):
        if not args.skip_hedging_drill:
            rc = hedging_drill(root=os.path.join(ckpt_dir, "hedge"))
            if rc:
                return rc

    # integrity: finite params, manifest verifies end to end (needs the
    # phase 1-4 trainer lineage, so a selection without phase 2 skips it)
    final = ""
    if clock.ran(2):
        for name, p in net2.collect_params().items():
            if not np.isfinite(p.data().asnumpy()).all():
                print(f"FAIL: non-finite parameter {name} after recovery")
                return 1
        entry, _ = manager.load()
        if not manager.verify(entry):
            print("FAIL: final checkpoint does not verify")
            return 1
        final = f" — final epoch {entry['epoch']}"
    clock.report()
    print(f"chaos_smoke: OK{final}, "
          f"fault stats {faults.stats() or '(env schedule consumed)'}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
