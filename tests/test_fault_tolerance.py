"""Fault tolerance: atomic checkpointing, fault injection, kill-and-resume.

The headline contract (ISSUE 2): a training run killed mid-epoch — by an
injected fault or a real SIGKILL — resumes from the CheckpointManager
manifest and reaches BIT-EXACT final parameters versus an uninterrupted
run; a checkpoint truncated on disk is detected by checksum and load falls
back to the previous good epoch.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import checkpoint, faults, gluon
from mxnet_tpu.checkpoint import CheckpointManager, atomic_write, crc32_file
from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults():
    """Every test starts and ends with no armed schedule."""
    faults.reset()
    yield
    faults.reset()


# ------------------------------------------------------------ faults.py ----

def test_retry_decorator_backoff_and_filtering():
    calls = []

    @faults.retry(retries=3, backoff=0.0)
    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return 42

    assert flaky() == 42
    assert len(calls) == 3

    # exhaustion re-raises the last error
    @faults.retry(retries=2, backoff=0.0)
    def always():
        raise OSError("permanent")

    with pytest.raises(OSError, match="permanent"):
        always()

    # non-matching exception types propagate immediately
    attempts = []

    @faults.retry(retries=5, backoff=0.0, retry_on=(OSError,))
    def wrong_type():
        attempts.append(1)
        raise ValueError("not retryable")

    with pytest.raises(ValueError):
        wrong_type()
    assert len(attempts) == 1

    # on_retry observes each failed attempt
    seen = []
    fn = faults.retry(lambda: (_ for _ in ()).throw(OSError("x")),
                      retries=2, backoff=0.0,
                      on_retry=lambda a, e: seen.append(a))
    with pytest.raises(OSError):
        fn()
    assert seen == [1, 2]


def test_fault_schedule_triggers():
    faults.configure("p:raise@2")
    faults.point("p")  # 1st: no fire
    with pytest.raises(faults.InjectedFault):
        faults.point("p")
    faults.point("p")  # 3rd: no fire (single-shot trigger)
    assert faults.stats()["p"] == (3, 1)

    faults.configure("p:raise@2+")
    faults.point("p")
    for _ in range(3):
        with pytest.raises(faults.InjectedFault):
            faults.point("p")

    # list trigger + multiple points in one spec
    faults.configure("a:raise@1,3;b:delay@*:0")
    with pytest.raises(faults.InjectedFault):
        faults.point("a")
    faults.point("a")
    with pytest.raises(faults.InjectedFault):
        faults.point("a")
    faults.point("b")
    assert faults.stats()["b"] == (1, 1)


def test_fault_probabilistic_trigger_is_seeded():
    def fire_pattern(seed):
        faults.configure("p:raise@p0.5", seed=seed)
        pattern = []
        for _ in range(20):
            try:
                faults.point("p")
                pattern.append(0)
            except faults.InjectedFault:
                pattern.append(1)
        return pattern

    a, b = fire_pattern(3), fire_pattern(3)
    assert a == b, "same seed must replay the same fire pattern"
    assert fire_pattern(4) != a  # and a different seed a different one
    assert sum(a) > 0


def test_fault_env_var_schedule(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_FAULTS", "envpt:raise@1")
    # white-box: force the (once-per-process) env read to happen again
    faults._specs.clear()
    faults._counts.clear()
    faults._fired.clear()
    faults._loaded_env = False
    assert faults.active()
    with pytest.raises(faults.InjectedFault):
        faults.point("envpt")


def test_nan_corruption_returns_poisoned_payload():
    faults.configure("p:nan@1")
    x = np.ones((4, 4), np.float32)
    out = faults.point("p", x)
    assert np.isnan(out).any()
    assert not np.isnan(x).any(), "original payload must not be mutated"


# -------------------------------------------------------- checkpoint.py ----

def test_atomic_write_replaces_and_checksums(tmp_path):
    target = tmp_path / "f.bin"
    crc, size = atomic_write(str(target), lambda p: open(p, "wb").write(b"v1"))
    assert target.read_bytes() == b"v1"
    assert size == 2 and crc == crc32_file(str(target))

    # a writer that dies mid-way leaves the OLD content intact
    def bad_writer(p):
        with open(p, "wb") as f:
            f.write(b"torn")
        raise OSError("disk died")

    with pytest.raises(OSError, match="disk died"):
        atomic_write(str(target), bad_writer)
    assert target.read_bytes() == b"v1"
    assert list(tmp_path.iterdir()) == [target], "no tmp litter"


def test_manager_rotation_and_manifest(tmp_path):
    m = CheckpointManager(tmp_path, prefix="ck", keep=2)
    for e in range(1, 5):
        m.save(e, {"params": f"payload-{e}".encode()}, step=e * 10)
    assert m.epochs() == [3, 4]
    assert m.last_good == 4
    assert not (tmp_path / "ck-0001.params").exists()
    assert not (tmp_path / "ck-0002.params").exists()
    # manifest survives a reopen and carries checksums
    m2 = CheckpointManager(tmp_path, prefix="ck", keep=2)
    entry, paths = m2.load()
    assert entry["epoch"] == 4 and entry["step"] == 40
    with open(m2.manifest_path) as f:
        manifest = json.load(f)
    fi = manifest["checkpoints"][-1]["files"]["params"]
    assert fi["crc32"] == crc32_file(paths["params"])


def test_manager_corruption_falls_back_to_previous_good(tmp_path):
    m = CheckpointManager(tmp_path, prefix="ck", keep=5)
    for e in (1, 2, 3):
        m.save(e, {"params": f"payload-{e}".encode()})
    newest = tmp_path / "ck-0003.params"
    newest.write_bytes(b"payload-3"[:4])  # truncated write
    with pytest.warns(UserWarning, match="falling back to epoch 2"):
        entry, paths = m.load()
    assert entry["epoch"] == 2
    assert open(paths["params"], "rb").read() == b"payload-2"

    # everything corrupt -> loud failure, never a silent fresh start
    (tmp_path / "ck-0002.params").write_bytes(b"x")
    (tmp_path / "ck-0001.params").unlink()
    with pytest.raises(ValueError, match="failed checksum"):
        m.load()


def test_manager_tolerates_torn_manifest(tmp_path):
    m = CheckpointManager(tmp_path, prefix="ck")
    m.save(1, {"params": b"p"})
    (tmp_path / "MANIFEST.json").write_text('{"checkpoints": [{"ep')
    with pytest.warns(UserWarning, match="corrupt checkpoint manifest"):
        m2 = CheckpointManager(tmp_path, prefix="ck")
    assert m2.resume() is None  # fresh manifest: nothing vouched for


def test_ckpt_write_fault_leaves_previous_checkpoint(tmp_path):
    m = CheckpointManager(tmp_path, prefix="ck", keep=5)
    m.save(1, {"params": b"good"})
    faults.configure("ckpt.write:raise@1")
    with pytest.raises(faults.InjectedFault):
        m.save(2, {"params": b"never-lands"})
    faults.reset()
    entry, paths = m.load()
    assert entry["epoch"] == 1
    assert open(paths["params"], "rb").read() == b"good"


# ------------------------------------------------------- clear messages ----

def test_load_params_clear_errors(tmp_path):
    from mxnet_tpu import model

    missing = tmp_path / "nope.params"
    with pytest.raises(FileNotFoundError, match=str(missing)):
        model.load_params(str(missing))

    garbage = tmp_path / "bad.params"
    garbage.write_bytes(b"this is not an npz container")
    with pytest.raises(ValueError, match="corrupt params file"):
        model.load_params(str(garbage))

    with pytest.raises(FileNotFoundError, match="symbol file not found"):
        model.load_checkpoint(str(tmp_path / "prefix"), 3)

    (tmp_path / "prefix-symbol.json").write_text("{not json!")
    with pytest.raises(ValueError, match="corrupt symbol file"):
        model.load_checkpoint(str(tmp_path / "prefix"), 3)


def test_trainer_state_clear_errors(tmp_path):
    net, tr = _make_trainer()
    with pytest.raises(FileNotFoundError, match="nope.npz"):
        tr.load_states(str(tmp_path / "nope.npz"))
    bad = tmp_path / "bad.npz"
    bad.write_bytes(b"garbage")
    with pytest.raises(ValueError, match="corrupt trainer state"):
        tr.load_states(str(bad))


# -------------------------------------------------------- trainer guard ----

def _batch(epoch, step):
    rs = np.random.RandomState(1000 * epoch + step)
    x = rs.randn(8, 6).astype(np.float32)
    y = (x @ rs.randn(6, 4) * 0.5).astype(np.float32)
    return mx.nd.array(x), mx.nd.array(y)


def _make_trainer(seed=7, **kw):
    mx.random.seed(seed)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu"))
    net.add(gluon.nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net(_batch(1, 0)[0])
    kw.setdefault("mesh", DeviceMesh({"dp": 8}))
    return net, ShardedTrainer(net, gluon.loss.L2Loss(), "adam",
                               {"learning_rate": 0.05}, **kw)


def _params_of(net):
    return {k: p.data().asnumpy().copy()
            for k, p in net.collect_params().items()}


def test_nan_guard_skips_bad_step_and_recovers():
    net, tr = _make_trainer(max_consecutive_skips=3)
    x, y = _batch(1, 0)
    tr.step(x, y)
    before = _params_of(net)
    opt_before = [[np.asarray(s) for s in per] for per in tr._opt_raws]

    faults.configure("trainer.step:nan@1")  # poison ONE batch
    loss = tr.step(x, y)
    assert not np.isfinite(loss.asscalar())
    assert tr.skipped_steps == 1 and tr.consecutive_skips == 1
    after = _params_of(net)
    for k in before:
        np.testing.assert_array_equal(before[k], after[k]), k
    for pb, pa in zip(opt_before, tr._opt_raws):
        for sb, sa in zip(pb, pa):
            np.testing.assert_array_equal(sb, np.asarray(sa))

    faults.reset()
    tr.step(x, y)  # clean step: streak resets, training continues
    assert tr.consecutive_skips == 0
    assert any(not np.array_equal(before[k], v)
               for k, v in _params_of(net).items())


def test_nan_guard_raises_after_consecutive_skips():
    net, tr = _make_trainer(max_consecutive_skips=3)
    x, y = _batch(1, 0)
    tr.step(x, y)
    faults.configure("trainer.step:nan@1+")  # every batch poisoned
    tr.step(x, y)
    tr.step(x, y)
    with pytest.raises(RuntimeError, match="consecutive steps produced "
                                           "non-finite"):
        tr.step(x, y)
    assert tr.skipped_steps == 3


def test_nan_guard_off_lets_nans_through():
    net, tr = _make_trainer(nan_guard=False)
    x, y = _batch(1, 0)
    faults.configure("trainer.step:nan@1")
    tr.step(x, y)
    assert tr.skipped_steps == 0
    assert any(np.isnan(v).any() for v in _params_of(net).values())


def _make_bf16_trainer(optimizer, zero, seed=7):
    """bfloat16 weights with float32 masters around a BatchNorm (float32
    scale and shift with no master, auxiliary state), on four of the
    virtual devices."""
    mx.random.seed(seed)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu", in_units=6),
            gluon.nn.BatchNorm(in_channels=8),
            gluon.nn.Dense(4, in_units=8))
    net.initialize(mx.init.Xavier())
    net.cast("bfloat16")
    params = {"adam": {"learning_rate": 0.05},
              "sgd": {"learning_rate": 0.05, "momentum": 0.9}}[optimizer]
    return net, ShardedTrainer(
        net, gluon.loss.L2Loss(), optimizer,
        dict(params, multi_precision=True), mesh=DeviceMesh({"dp": 4}),
        zero=zero)


def _bf16_batch(epoch, step):
    x, y = _batch(epoch, step)
    return x.astype("bfloat16"), y.astype("bfloat16")


def _device_state(tr):
    """Every array a step writes, on the host: (parameters, optimizer
    state a parameter, aux)."""
    return ([np.asarray(h._data) for h in tr._train_handles],
            [[np.asarray(s) for s in per] for per in tr._opt_raws],
            [np.asarray(h._data) for h in tr._aux_handles])


def _cast_of(master, like):
    import jax.numpy as jnp

    return np.asarray(jnp.asarray(master).astype(like.dtype))


def _mastered(params, opt):
    """(parameter index, master) of the bfloat16 parameters."""
    out = [(i, per[0]) for i, (w, per) in enumerate(zip(params, opt))
           if str(w.dtype) == "bfloat16"]
    assert len(out) == 4 and len(params) == 6
    assert all(str(m.dtype) == "float32" for _, m in out)
    return out


@pytest.mark.parametrize("zero", [False, True], ids=["dp4", "zero4"])
@pytest.mark.parametrize("optimizer", ["adam", "sgd"])
def test_nan_guard_with_bf16_masters_skips_and_continues(optimizer, zero):
    """A mastered parameter is the cast of the SELECTED master and has no
    select of its own in the compiled step: a skipped step must still leave
    it, its master, the rule's state and the aux bit-identical, and the
    next finite step goes on from them."""
    net, tr = _make_bf16_trainer(optimizer, zero)
    x, y = _bf16_batch(1, 0)
    tr.step(x, y)
    tr.step(x, y)
    p0, opt0, aux0 = _device_state(tr)
    assert aux0

    faults.configure("trainer.step:nan@1")
    assert not np.isfinite(tr.step(x, y).asscalar())
    assert tr.skipped_steps == 1
    p1, opt1, aux1 = _device_state(tr)
    for a, b in zip(p0 + aux0 + sum(opt0, []), p1 + aux1 + sum(opt1, [])):
        np.testing.assert_array_equal(a, b)

    faults.reset()
    assert np.isfinite(tr.step(x, y).asscalar())
    p2, opt2, _ = _device_state(tr)
    for (i, m1), (_, m2) in zip(_mastered(p1, opt1), _mastered(p2, opt2)):
        assert not np.array_equal(m1, m2)
        np.testing.assert_array_equal(p2[i], _cast_of(m2, p2[i]))
        # no further from where the skip left it than a step could go
        assert np.abs(m2 - m1).max() < 0.1
    assert all(not np.array_equal(w1, w2) for w1, w2 in zip(p1, p2))


@pytest.mark.parametrize("zero", [False, True], ids=["dp4", "zero4"])
@pytest.mark.parametrize("optimizer", ["adam", "sgd"])
def test_bf16_parameter_is_the_guarded_cast_of_its_master(optimizer, zero):
    """Over finite and skipped steps every bfloat16 parameter is, bit for
    bit, ``where(finite, cast(new master), old parameter)``, the form the
    step had before it derived the parameter from the selected master,
    computed here from the recorded masters; and it is ``cast(master)``."""
    net, tr = _make_bf16_trainer(optimizer, zero)
    faults.configure("trainer.step:nan@3,4")
    prev, _, _ = _device_state(tr)
    finite_seen = []
    for step in range(6):
        loss = tr.step(*_bf16_batch(1, step))
        finite = bool(np.isfinite(loss.asscalar()))
        finite_seen.append(finite)
        now, opt, _ = _device_state(tr)
        for i, master in _mastered(now, opt):
            want = _cast_of(master, now[i]) if finite else prev[i]
            np.testing.assert_array_equal(now[i], want)
            np.testing.assert_array_equal(now[i], _cast_of(master, now[i]))
        prev = now
    assert finite_seen == [True, True, False, False, True, True]


# ---------------------------------------------------- kill-and-resume ------

def _train(trainer, manager, epochs, steps, start_epoch=0):
    for epoch in range(start_epoch + 1, epochs + 1):
        for step in range(steps):
            x, y = _batch(epoch, step)
            trainer.step(x, y)
        trainer.save_checkpoint(manager, epoch)


def test_injected_fault_kill_and_resume_bit_exact(tmp_path):
    epochs, steps = 3, 4

    # ---- uninterrupted reference trajectory
    net_a, tr_a = _make_trainer()
    mgr_a = CheckpointManager(tmp_path / "a", prefix="ft")
    _train(tr_a, mgr_a, epochs, steps)
    ref = _params_of(net_a)

    # ---- interrupted: an injected fault kills epoch 3 mid-flight
    net_b, tr_b = _make_trainer()
    mgr_b = CheckpointManager(tmp_path / "b", prefix="ft")
    faults.configure("trainer.step:raise@11")  # step 3 of epoch 3
    with pytest.raises(faults.InjectedFault):
        _train(tr_b, mgr_b, epochs, steps)
    faults.reset()
    assert mgr_b.last_good == 2  # epochs 1-2 checkpointed before the kill

    # ---- "restart the job": fresh process state, resume from manifest
    net_c, tr_c = _make_trainer(seed=999)  # different init — must not matter
    entry = tr_c.resume(mgr_b)
    assert entry["epoch"] == 2 and entry["step"] == 2 * steps
    _train(tr_c, mgr_b, epochs, steps, start_epoch=entry["epoch"])

    got = _params_of(net_c)
    # gluon auto-prefixes differ between instances: compare positionally
    # (collect_params order is structural)
    assert len(ref) == len(got)
    for (ka, va), (kb, vb) in zip(ref.items(), got.items()):
        np.testing.assert_array_equal(va, vb, err_msg=f"{ka} vs {kb}")


def test_resume_falls_back_past_truncated_states_file(tmp_path):
    epochs, steps = 3, 2
    net, tr = _make_trainer()
    mgr = CheckpointManager(tmp_path, prefix="ft")
    _train(tr, mgr, epochs, steps)

    # truncate the newest states file — simulates dying mid-write on a
    # filesystem without atomic rename (or a torn copy)
    newest = tmp_path / "ft-0003.states"
    newest.write_bytes(newest.read_bytes()[:128])

    net2, tr2 = _make_trainer(seed=999)
    with pytest.warns(UserWarning, match="falling back to epoch 2"):
        entry = tr2.resume(mgr)
    assert entry["epoch"] == 2
    assert tr2._t == 2 * steps


@pytest.mark.skipif(not hasattr(os, "kill"), reason="needs POSIX kill")
def test_sigkill_subprocess_kill_and_resume_bit_exact(tmp_path):
    """The real thing: a child process is SIGKILLed mid-epoch (fault mode
    'kill' — no cleanup, no atexit, exactly a preemption), restarted with
    resume, and must land on bit-exact params vs an uninterrupted child."""
    env_base = {**os.environ, "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": REPO + os.pathsep
                + os.environ.get("PYTHONPATH", ""),
                "FT_EPOCHS": "3", "FT_STEPS": "4"}
    child = os.path.join(REPO, "tests", "_ft_child.py")

    def run(ckpt_dir, out, extra):
        env = {**env_base, "FT_CKPT_DIR": str(ckpt_dir),
               "FT_OUT": str(out), **extra}
        env.pop("MXNET_TPU_FAULTS", None)
        env.update({k: v for k, v in extra.items()})
        return subprocess.run([sys.executable, child], env=env,
                              capture_output=True, text=True, timeout=240)

    # uninterrupted reference
    ref_out = tmp_path / "ref.npz"
    proc = run(tmp_path / "ref", ref_out, {})
    assert proc.returncode == 0, proc.stderr

    # killed mid-epoch-3 (step 11 of 12): SIGKILL, no exit handlers
    kill_dir = tmp_path / "kill"
    proc = run(kill_dir, tmp_path / "never.npz",
               {"MXNET_TPU_FAULTS": "trainer.step:kill@11"})
    assert proc.returncode == -9, (proc.returncode, proc.stderr)
    assert not (tmp_path / "never.npz").exists()
    manifest = json.loads((kill_dir / "MANIFEST.json").read_text())
    assert manifest["last_good"] == 2

    # restart with resume -> completes, bit-exact vs reference
    res_out = tmp_path / "resumed.npz"
    proc = run(kill_dir, res_out, {"FT_RESUME": "1"})
    assert proc.returncode == 0, proc.stderr
    ref = dict(np.load(ref_out))
    got = dict(np.load(res_out))
    assert ref.keys() == got.keys()
    for k in ref:
        np.testing.assert_array_equal(ref[k], got[k]), k


# ------------------------------------------------- estimator integration ---

def test_checkpoint_handler_rotation_and_resume(tmp_path):
    from mxnet_tpu.gluon import Trainer
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.contrib.estimator import (CheckpointHandler,
                                                   Estimator)

    def toy_net():
        mx.random.seed(3)
        net = gluon.nn.Dense(3)
        net.initialize(mx.init.Xavier())
        net(mx.nd.zeros((4, 5)))
        return net

    rs = np.random.RandomState(0)
    data = [(mx.nd.array(rs.randn(4, 5).astype(np.float32)),
             mx.nd.array(rs.randint(0, 3, 4).astype(np.float32)))
            for _ in range(2)]

    net = toy_net()
    est = Estimator(net, gloss.SoftmaxCrossEntropyLoss(), context=mx.cpu(),
                    trainer=Trainer(net.collect_params(), "sgd",
                                    {"learning_rate": 0.05}))
    handler = CheckpointHandler(str(tmp_path), model_prefix="m",
                                max_checkpoints=2)
    est.fit(data, epochs=3, event_handlers=[handler])

    manifest = json.loads((tmp_path / "MANIFEST.json").read_text())
    assert [e["epoch"] for e in manifest["checkpoints"]] == [2, 3]
    assert not (tmp_path / "m-0001.params").exists()

    # fresh estimator resumes the newest good checkpoint at train_begin
    net2 = toy_net()
    est2 = Estimator(net2, gloss.SoftmaxCrossEntropyLoss(),
                     context=mx.cpu(),
                     trainer=Trainer(net2.collect_params(), "sgd",
                                     {"learning_rate": 0.05}))
    resumer = CheckpointHandler(str(tmp_path), model_prefix="m",
                                max_checkpoints=2,
                                resume_from_checkpoint=True)
    resumer.train_begin(est2)
    assert resumer.trained_epochs == 3
    for (_, a), (_, b) in zip(net.collect_params().items(),
                              net2.collect_params().items()):
        np.testing.assert_array_equal(a.data().asnumpy(),
                                      b.data().asnumpy())

    # a truncated newest checkpoint falls back to the previous epoch
    params3 = tmp_path / "m-0003.params"
    params3.write_bytes(params3.read_bytes()[:64])
    net3 = toy_net()
    est3 = Estimator(net3, gloss.SoftmaxCrossEntropyLoss(),
                     context=mx.cpu(),
                     trainer=Trainer(net3.collect_params(), "sgd",
                                     {"learning_rate": 0.05}))
    resumer3 = CheckpointHandler(str(tmp_path), model_prefix="m",
                                 max_checkpoints=2,
                                 resume_from_checkpoint=True)
    with pytest.warns(UserWarning, match="falling back to epoch 2"):
        resumer3.train_begin(est3)
    assert resumer3.trained_epochs == 2


# ----------------------------------------------------------- io / kvstore --

def test_io_decode_fault_surfaces_at_next(tmp_path):
    """A fault raised inside the prefetch producer thread surfaces at
    next(), not as a hang (the deferred-exception contract for data)."""
    from mxnet_tpu import recordio
    from mxnet_tpu.io import ImageRecordIter

    try:
        from PIL import Image
    except ImportError:
        pytest.skip("PIL unavailable")
    import io as _io

    rec_path = str(tmp_path / "d.rec")
    idx_path = str(tmp_path / "d.idx")
    rec = recordio.MXIndexedRecordIO(idx_path, rec_path, "w")
    rs = np.random.RandomState(0)
    for i in range(8):
        img = Image.fromarray(rs.randint(0, 255, (10, 10, 3), np.uint8))
        buf = _io.BytesIO()
        img.save(buf, format="JPEG")
        rec.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i), i, 0), buf.getvalue()))
    rec.close()

    faults.configure("io.decode:raise@2")
    it = ImageRecordIter(path_imgrec=rec_path, path_imgidx=idx_path,
                         data_shape=(3, 8, 8), batch_size=4,
                         prefetch_buffer=1, preprocess_threads=1)
    it.next()  # batch 1 decodes fine
    with pytest.raises(faults.InjectedFault):
        it.next()
    it.close()


def test_kvstore_push_fault_injection():
    kv = mx.kv.create("local")
    kv.init("w", mx.nd.zeros((3,)))
    faults.configure("kvstore.push:raise@2")
    kv.push("w", mx.nd.ones((3,)))
    with pytest.raises(faults.InjectedFault):
        kv.push("w", mx.nd.ones((3,)))
    faults.reset()
    out = mx.nd.zeros((3,))
    kv.pull("w", out=out)
    np.testing.assert_allclose(out.asnumpy(), np.ones(3))
