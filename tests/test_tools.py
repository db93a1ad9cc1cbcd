"""Tools layer (parity model: tools/ in the reference — launch.py,
parse_log.py, diagnose.py, bandwidth/measure.py, rec2idx.py)."""
import os
import subprocess
import sys

import numpy as onp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


def test_parse_log_roundtrip(tmp_path):
    import parse_log

    log = tmp_path / "train.log"
    log.write_text(
        "INFO Epoch[0] Train-accuracy=0.812345\n"
        "INFO Epoch[0] Time cost=12.345\n"
        "INFO Epoch[0] Validation-accuracy=0.798000\n"
        "INFO Epoch[1] Train-accuracy=0.901000\n"
        "INFO Epoch[1] Time cost=11.000\n"
        "INFO Epoch[1] Validation-accuracy=0.888000\n")
    rows = parse_log.main([str(log), "--format", "none"])
    assert rows[0]["train"]["accuracy"] == pytest.approx(0.812345)
    assert rows[1]["val"]["accuracy"] == pytest.approx(0.888)
    assert rows[1]["time"] == pytest.approx(11.0)


def test_launch_local_sets_worker_env(tmp_path):
    import launch

    out = tmp_path / "env"
    script = (
        "import os, pathlib\n"
        "p = pathlib.Path(%r) / os.environ['MXTPU_WORKER_ID']\n"
        "p.write_text(os.environ['MXTPU_COORDINATOR'] + ' ' +\n"
        "             os.environ['MXTPU_NUM_WORKERS'])\n" % str(out))
    out.mkdir()
    rc = launch.launch_local(3, [sys.executable, "-c", script])
    assert rc == 0
    files = sorted(os.listdir(out))
    assert files == ["0", "1", "2"]
    for f in files:
        coord, n = (out / f).read_text().split()
        assert coord.startswith("127.0.0.1:") and n == "3"


def test_bandwidth_measure_cpu_mesh():
    sys.path.insert(0, os.path.join(REPO, "tools", "bandwidth"))
    import measure

    rows = measure.measure([0.25], iters=2, warmup=1)
    assert rows and rows[0]["algo_gbps"] > 0
    assert rows[0]["devices"] >= 1


def test_diagnose_runs(capsys):
    import diagnose

    diagnose.main()
    out = capsys.readouterr().out
    assert "Framework Info" in out and "Version" in out
    assert "jax" in out
    # watchdog knobs + most-recent-crash-bundle report (docs/ROBUSTNESS.md)
    assert "Watchdog Knobs" in out and "MXNET_TPU_WATCHDOG" in out
    # gang supervision knobs (docs/ROBUSTNESS.md "Gang supervision")
    assert "Gang" in out and "MXNET_TPU_GANG_MAX_RESTARTS" in out
    # telemetry section (docs/OBSERVABILITY.md)
    assert "Telemetry" in out and "MXNET_TPU_TELEMETRY" in out


def test_diagnose_json_machine_readable(capsys):
    """--json: one JSON document with every report section, for CI
    scraping; the human text stays the default (covered above)."""
    import json

    import diagnose

    diagnose.main(["--json"])
    out = capsys.readouterr().out
    report = json.loads(out)  # exactly one parseable document, no prose
    for section in ("python", "framework", "dependencies", "hardware",
                    "environment", "analysis", "compile_cache",
                    "serving", "watchdog", "preempt", "gang",
                    "telemetry"):
        assert section in report, section
    assert report["python"]["version"]
    assert "jax" in report["dependencies"]
    tele = report["telemetry"]
    assert "metrics" in tele and "flight_tail" in tele
    assert "device_memory" in tele


def test_diagnose_gang_report_reads_run_dir(tmp_path, capsys,
                                            monkeypatch):
    """The Gang section reports the run dir's gang.json (generation,
    per-incarnation restart reasons), per-rank last heartbeats, and any
    post-mortem bundle."""
    import json
    import time

    import diagnose

    summary = {"state": "failed", "generation": 3, "restarts_used": 2,
               "max_restarts": 2,
               "history": [{"generation": 1, "exits": {"0": 137},
                            "reason": "rank 0 exited 137 (killed)"},
                           {"generation": 2, "exits": {"0": 86},
                            "reason": "rank 0 exited 86 "
                                      "(watchdog-abort)"},
                           {"generation": 3, "exits": {"0": 86},
                            "reason": "rank 0 exited 86 "
                                      "(watchdog-abort)"}]}
    (tmp_path / "gang.json").write_text(json.dumps(summary))
    (tmp_path / "rank-0.json").write_text(json.dumps(
        {"rank": 0, "generation": 3, "state": "running", "steps": 7,
         "pid": 12345, "t_wall": time.time() - 4.0}))
    (tmp_path / "postmortem-x-p1.json").write_text("{}")
    monkeypatch.setenv("MXNET_TPU_GANG_DIR", str(tmp_path))

    out = diagnose.check_gang()
    text = capsys.readouterr().out
    assert out["summary"]["generation"] == 3
    assert out["heartbeats"][0]["steps"] == 7
    assert out["postmortems"] == ["postmortem-x-p1.json"]
    assert "restarts 2/2" in text and "watchdog-abort" in text
    assert "rank 0 beat" in text and "postmortem-x-p1.json" in text


def test_rec2idx_matches_writer(tmp_path):
    import rec2idx

    from mxnet_tpu import recordio

    rec_path = str(tmp_path / "d.rec")
    idx_path = str(tmp_path / "d.idx")
    w = recordio.MXIndexedRecordIO(idx_path, rec_path, "w")
    payloads = [bytes([i]) * (10 + i) for i in range(12)]
    for i, p in enumerate(payloads):
        w.write_idx(i, p)
    w.close()
    written = open(idx_path).read()

    rebuilt = str(tmp_path / "rebuilt.idx")
    rec2idx.main([rec_path, rebuilt])
    assert open(rebuilt).read().split() == written.split()

    # the rebuilt index actually seeks correctly
    r = recordio.MXIndexedRecordIO(rebuilt, rec_path, "r")
    assert r.read_idx(7) == payloads[7]


@pytest.mark.lint
def test_mxlint_self_run_clean():
    """CI gate: the repo must lint clean against the committed baseline —
    new violations of the framework rules (docs/ANALYSIS.md) fail here.
    Addressable alone via `pytest -m lint`."""
    import mxlint

    rc = mxlint.main(["mxnet_tpu"])
    assert rc == 0, "new mxlint violations vs tools/mxlint_baseline.txt"


@pytest.mark.lint
def test_mxlint_catches_planted_violations(tmp_path):
    """The linter actually fires on each rule it claims to enforce."""
    import mxlint

    bad = tmp_path / "bad.py"
    bad.write_text(
        "import os\n"                                    # unused-import
        "import numpy as np\n"
        "import jax\n"
        "from mxnet_tpu.ops.registry import register\n"
        "def f(x, y=[]):\n"                              # mutable-default
        "    try:\n"
        "        v = x.asnumpy()\n"                      # host-sync
        "    except:\n"                                  # bare-except
        "        v = np.random.uniform()\n"              # unseeded-random
        "    return v\n"
        "@register('badop')\n"
        "def badop(data):\n"                             # no-schema-doc
        "    return data\n"
        "g = jax.jit(badop)\n"                           # raw-jit
        "from jax.sharding import PartitionSpec as P\n"
        "spec = P('dpp', None)\n")                       # partition-spec-literal
    findings = mxlint.run([str(bad)], root=str(tmp_path))
    rules = {f.rule for f in findings}
    assert rules == {"unused-import", "raw-jit",
                     "mutable-default", "host-sync", "bare-except",
                     "unseeded-random", "no-schema-doc",
                     "partition-spec-literal"}
    psl = [f for f in findings if f.rule == "partition-spec-literal"]
    assert "did you mean" in psl[0].message  # difflib near-miss hint
    # the canonical vocabulary, and parallel/ itself, stay clean
    good_spec = tmp_path / "good_spec.py"
    good_spec.write_text("from jax.sharding import PartitionSpec as P\n"
                         "spec = P('dp', ('tp', 'sp'))\n")
    assert mxlint.run([str(good_spec)], root=str(tmp_path)) == []
    par = tmp_path / "mxnet_tpu" / "parallel"
    par.mkdir(parents=True)
    exempt = par / "exempt.py"
    exempt.write_text("from jax.sharding import PartitionSpec as P\n"
                      "spec = P('stage')\n")
    assert mxlint.run([str(exempt)], root=str(tmp_path)) == []
    # noqa suppression works, per-rule
    ok = tmp_path / "ok.py"
    ok.write_text("v = x.asnumpy()  # noqa: host-sync\n")
    assert mxlint.run([str(ok)], root=str(tmp_path)) == []


@pytest.mark.lint
def test_mxlint_raw_jit_rule_scoping(tmp_path):
    """raw-jit fires on direct jax.jit calls and 'from jax import jit',
    but compile.py (the service home) is exempt."""
    import mxlint

    direct = tmp_path / "site.py"
    direct.write_text("import jax\nf = jax.jit(lambda x: x)\n")
    assert {f.rule for f in mxlint.run([str(direct)],
                                       root=str(tmp_path))} == {"raw-jit"}
    imported = tmp_path / "site2.py"
    imported.write_text("from jax import jit\nf = jit(lambda x: x)\n")
    assert "raw-jit" in {f.rule for f in mxlint.run([str(imported)],
                                                    root=str(tmp_path))}
    exempt = tmp_path / "compile.py"
    exempt.write_text("import jax\nf = jax.jit(lambda x: x)\n")
    assert mxlint.run([str(exempt)], root=str(tmp_path)) == []
    # the service call spelling stays clean
    good = tmp_path / "site3.py"
    good.write_text("from mxnet_tpu import compile as _compile\n"
                    "f = _compile.jit(lambda x: x, site='s', token=('t',))\n")
    assert mxlint.run([str(good)], root=str(tmp_path)) == []


@pytest.mark.lint
def test_mxlint_raw_pallas_call_rule(tmp_path):
    """raw-pallas-call fires on pl.pallas_call outside mxnet_tpu/kernels/
    (with a did-you-mean pointing at the registry) and is exempt inside
    kernels/ — the one blessed home of raw Pallas call sites."""
    import mxlint

    src = ("from jax.experimental import pallas as pl\n"
           "def f(x):\n"
           "    return pl.pallas_call(lambda i, o: None)(x)\n")
    ops = tmp_path / "mxnet_tpu" / "ops"
    ops.mkdir(parents=True)
    bad = ops / "planted.py"
    bad.write_text(src)
    findings = [f for f in mxlint.run([str(bad)], root=str(tmp_path))
                if f.rule == "raw-pallas-call"]
    assert len(findings) == 1
    assert "register_kernel" in findings[0].message
    assert "kernels.dispatch" in findings[0].message

    kern = tmp_path / "mxnet_tpu" / "kernels"
    kern.mkdir(parents=True)
    ok = kern / "mykernel.py"
    ok.write_text(src)
    assert [f for f in mxlint.run([str(ok)], root=str(tmp_path))
            if f.rule == "raw-pallas-call"] == []

    # the real tree carries zero raw-pallas-call debt: flash moved into
    # the registry, so the baseline must not need a single entry
    findings = [f for f in mxlint.run(["mxnet_tpu"])
                if f.rule == "raw-pallas-call"]
    assert findings == []
    with open(mxlint.DEFAULT_BASELINE) as fh:
        assert "raw-pallas-call" not in fh.read()


@pytest.mark.lint
def test_mxlint_serving_blocking_call_rule(tmp_path):
    """serving-blocking-call: serving/ code may not block outside a
    watchdog.sync span — device syncs and zero-arg waits fire; callables
    passed to *.sync(...) (lambda or by name) are exempt, as is the same
    code outside serving/."""
    import mxlint

    serving_dir = tmp_path / "mxnet_tpu" / "serving"
    serving_dir.mkdir(parents=True)
    bad = serving_dir / "bad.py"
    bad.write_text(
        "def f(x, t, q):\n"
        "    x.wait_to_read()\n"        # device sync
        "    jax.block_until_ready(x)\n"  # device sync
        "    t.join()\n"                # zero-arg unbounded wait
        "    q.get()\n"                 # zero-arg unbounded wait
        "    t.join(timeout=1.0)\n"     # bounded: clean
        "    q.get(timeout=0.5)\n"      # bounded: clean
    )
    findings = [f for f in mxlint.run([str(bad)], root=str(tmp_path))
                if f.rule == "serving-blocking-call"]
    assert len(findings) == 4
    assert "bounded-tail-latency" in findings[0].message
    # the watchdog.sync exemption: inline lambda AND a local fn by name
    ok = serving_dir / "ok.py"
    ok.write_text(
        "def g(model, x, w):\n"
        "    def run():\n"
        "        out = model(x)\n"
        "        jax.block_until_ready(out)\n"
        "        return out\n"
        "    a = w.sync('serving.batch', run)\n"
        "    b = w.sync('serving.batch', lambda: x.wait_to_read())\n"
        "    return a, b\n")
    assert [f for f in mxlint.run([str(ok)], root=str(tmp_path))
            if f.rule == "serving-blocking-call"] == []
    # identical blocking code OUTSIDE serving/ is not this rule's business
    other = tmp_path / "mxnet_tpu" / "elsewhere.py"
    other.write_text("def f(x):\n    x.wait_to_read()\n")
    assert [f for f in mxlint.run([str(other)], root=str(tmp_path))
            if f.rule == "serving-blocking-call"] == []
    # the real serving package is clean under the rule
    findings = [f for f in mxlint.run(["mxnet_tpu/serving"])
                if f.rule == "serving-blocking-call"]
    assert findings == [], findings


@pytest.mark.lint
def test_mxlint_print_call_rule(tmp_path):
    """print-call: bare print() inside the mxnet_tpu/ package fires;
    __main__ demo blocks, tools/-style scripts outside the package, and
    noqa'd lines are exempt."""
    import mxlint

    pkg = tmp_path / "mxnet_tpu"
    pkg.mkdir(parents=True)
    bad = pkg / "planted.py"
    bad.write_text(
        "def report(x):\n"
        "    print('status:', x)\n"          # fires
        "    print('ok')  # noqa: print-call\n"  # suppressed
        "    return x\n"
        "if __name__ == '__main__':\n"
        "    print(report(1))\n")             # __main__ block: exempt
    findings = [f for f in mxlint.run([str(bad)], root=str(tmp_path))
                if f.rule == "print-call"]
    assert len(findings) == 1 and findings[0].line == 2
    assert "mxnet_tpu.log" in findings[0].message
    # identical code OUTSIDE the package (tools/, scripts) is exempt
    script = tmp_path / "tools" / "script.py"
    script.parent.mkdir()
    script.write_text("def f(x):\n    print(x)\n")
    assert [f for f in mxlint.run([str(script)], root=str(tmp_path))
            if f.rule == "print-call"] == []
    # the telemetry package itself is print-free (structured export only)
    findings = [f for f in mxlint.run(["mxnet_tpu/telemetry"])
                if f.rule == "print-call"]
    assert findings == [], findings


@pytest.mark.lint
def test_mxlint_baseline_gate_blocks_regressions(tmp_path):
    """Baseline semantics: within-count passes, one extra finding fails."""
    import mxlint

    f = tmp_path / "m.py"
    f.write_text("a = x.asnumpy()\n")
    base = tmp_path / "base.txt"
    base.write_text("host-sync m.py 1  # tolerated legacy sync\n")
    assert mxlint.main([str(f), "--root", str(tmp_path),
                        "--baseline", str(base)]) == 0
    f.write_text("a = x.asnumpy()\nb = y.asnumpy()\n")
    assert mxlint.main([str(f), "--root", str(tmp_path),
                        "--baseline", str(base)]) == 1


def test_verifier_smoke_every_model_zoo_symbol():
    """Every model-zoo network traces to a Symbol that passes the graph
    verifier with only an input-shape hint (deferred-init parameter shapes
    resolve abstractly — no forward pass, no device compile)."""
    from mxnet_tpu.gluon.model_zoo import vision

    checked = 0
    for name in vision.__all__:
        if name == "get_model":
            continue
        net = getattr(vision, name)(classes=10)
        net.initialize()
        sym = net._trace_symbol()
        issues = sym.verify(raise_on_error=False, data=(1, 3, 224, 224))
        errors = [i for i in issues if i.is_error]
        assert not errors, f"{name}: {errors[:3]}"
        checked += 1
    assert checked >= 30  # the whole zoo, not a sample


def test_chaos_smoke_recovers(tmp_path):
    """tools/chaos_smoke.py: 2-epoch toy fit under the canned fault
    schedule — NaN guard absorbs a poisoned batch, checkpoint-write
    retry absorbs an injected write failure, an injected crash is
    recovered via CheckpointManager resume, an injected hang surfaces as
    a StallError + bundle, an injected SIGTERM preemption drains
    gracefully and resumes resharded on half the simulated devices, and
    the phase-6 serving drill passes (wedged serving batch -> bundle +
    continued service; subprocess SIGTERM under load -> all admitted
    requests answered, exit 75), and the phase-8 gang drill recovers a
    supervised 2-worker run from a mid-epoch SIGKILL (generation bump,
    resharded resume, loss parity) — exit code 0. The phase-17 planet-
    scale drill (four fleets' worth of subprocess workers) is skipped
    here to hold the tier-1 budget; test_chaos_smoke_hedging_drill
    runs it in the slow tier."""
    import chaos_smoke

    from mxnet_tpu import faults, preempt

    faults.reset()
    try:
        rc = chaos_smoke.main(["--epochs", "2", "--steps", "4",
                               "--skip-hedging-drill",
                               "--dir", str(tmp_path)])
    finally:
        faults.reset()
        preempt.uninstall()
    assert rc == 0
    assert (tmp_path / "MANIFEST.json").exists()
    # phase 4 left a drain-event record next to the checkpoints
    assert any(f.startswith("drain-") for f in os.listdir(tmp_path))
    # phase 6 wrote a serving-stall crash bundle into the crash dir
    crash = tmp_path / "crash"
    assert crash.is_dir() and any(
        "serving_batch" in f for f in os.listdir(crash))
    # phase 7 verified the /metrics scrape; every bundle embeds a
    # non-empty flight-recorder tail (telemetry acceptance)
    import json

    for bundle in os.listdir(crash):
        with open(crash / bundle / "flight.json") as f:
            assert json.load(f), f"empty flight tail in {bundle}"
    # phase 8 left the cluster supervisor's world record: a 1-restart
    # generation-2 recovery, stopped cleanly
    with open(tmp_path / "gang" / "run" / "world.json") as f:
        world = json.load(f)
    assert world["supervisor"]["state"] == "stopped"
    assert world["generation"]["train"] == 2
    assert world["ledger"]["train"]["restarts_total"] == 1
    # phase 16 left the SIGKILLed-and-restarted supervisor's record:
    # incarnation 2 with re-adoptions and zero healthy-worker restarts
    with open(tmp_path / "cluster" / "run" / "world.json") as f:
        world = json.load(f)
    assert world["incarnation"] == 2
    assert any(a["kind"] == "adopt" for a in world["actions"])


@pytest.mark.slow
def test_chaos_smoke_hedging_drill(tmp_path):
    """tools/chaos_smoke.py --phases 17: the planet-scale serving
    drill on its own — the 2-host straggler fleet where hedging must
    cut p99 >=3x with zero errors, the full host loss under one
    cluster.json with zero client-visible errors, and the QoS
    starvation order (batch starves before interactive; unmeetable
    deadlines drop before a batch slot) — exit code 0."""
    import chaos_smoke

    from mxnet_tpu import faults, preempt

    faults.reset()
    try:
        rc = chaos_smoke.main(["--phases", "17",
                               "--dir", str(tmp_path)])
    finally:
        faults.reset()
        preempt.uninstall()
    assert rc == 0
    # drill A left both fleets' per-host run dirs behind — the merged-
    # scrape topology the router placed workers across
    for label in ("hedge-off", "hedge-on"):
        run = tmp_path / "hedge" / label / "run"
        assert (run / "host-local").is_dir()
        assert (run / "host-slow").is_dir()
