"""Streaming data plane tests: fused native decode+augment (bit-parity
with the Python fallback), per-host sharded readers, deterministic
mid-epoch resume (in-process and SIGKILL-subprocess), TokenRecordIter,
trainer checkpoint integration, and the native-unavailable surfacing."""
import io as _io
import json
import os
import signal
import subprocess
import sys
import zlib

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import native, recordio
from mxnet_tpu.io import (ImageRecordIter, NDArrayIter, PrefetchingIter,
                          TokenRecordIter, write_token_shard)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write_rec(path, n=40, hw=32, png_at=None, seed=0):
    """JPEG .rec whose source size equals the rand_crop decode size for
    data_shape (3,24,24) — so native and PIL decodes are bit-identical
    (no resize) and the augmentation stream is the only variable."""
    from PIL import Image

    rs = np.random.RandomState(seed)
    rec = recordio.MXIndexedRecordIO(path + ".idx", path + ".rec", "w")
    for i in range(n):
        arr = rs.randint(0, 255, (hw, hw, 3), np.uint8)
        buf = _io.BytesIO()
        if png_at is not None and i == png_at:
            Image.fromarray(arr).save(buf, "PNG")
        else:
            Image.fromarray(arr).save(buf, "JPEG", quality=95)
        rec.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i), i, 0), buf.getvalue()))
    rec.close()
    return path + ".rec"


def _aug_kw(rec, **over):
    kw = dict(path_imgrec=rec, data_shape=(3, 24, 24), batch_size=4,
              shuffle=True, rand_crop=True, rand_mirror=True,
              color_jitter=0.2, seed=5, round_batch=False,
              prefetch_buffer=0, num_parts=1, part_index=0)
    kw.update(over)
    return kw


def _stream(it):
    return [b.data[0].asnumpy() for b in it]


def _force_python_augment(monkeypatch):
    monkeypatch.setattr(native, "decode_augment_batch",
                        lambda *a, **k: None)
    monkeypatch.setattr(native, "decode_jpeg_batch",
                        lambda *a, **k: None)


# ------------------------------------------------------------- tentpole --

def test_augmented_stream_deterministic(tmp_path):
    rec = _write_rec(str(tmp_path / "a"))
    a = _stream(ImageRecordIter(**_aug_kw(rec)))
    b = _stream(ImageRecordIter(**_aug_kw(rec)))
    assert len(a) == 10
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    # a different seed draws a different augmentation stream
    c = _stream(ImageRecordIter(**_aug_kw(rec, seed=6)))
    assert any(not np.array_equal(x, y) for x, y in zip(a, c))


def test_native_augment_bit_parity_with_python(tmp_path, monkeypatch):
    """The fused native loop and the pure-Python fallback produce
    bit-identical augmented batches at seed parity (crop + mirror +
    color jitter; source size == decode size so no resize divergence)."""
    if not native.status()["augment"]:
        pytest.skip("native fused augment not built on this host")
    rec = _write_rec(str(tmp_path / "b"))
    nat = _stream(ImageRecordIter(**_aug_kw(rec)))
    _force_python_augment(monkeypatch)
    py = _stream(ImageRecordIter(**_aug_kw(rec)))
    assert len(nat) == len(py) == 10
    for x, y in zip(nat, py):
        np.testing.assert_array_equal(x, y)


def test_augment_failed_record_retried_with_same_params(tmp_path):
    """A record the native libjpeg loop rejects (a PNG) is retried
    through PIL INSIDE the augmented path with the SAME per-image
    params — the whole stream matches an all-PIL run bit-exactly."""
    if not native.status()["augment"]:
        pytest.skip("native fused augment not built on this host")
    rec = _write_rec(str(tmp_path / "c"), png_at=3)
    nat = _stream(ImageRecordIter(**_aug_kw(rec)))
    orig_a, orig_j = native.decode_augment_batch, native.decode_jpeg_batch
    native.decode_augment_batch = lambda *a, **k: None
    native.decode_jpeg_batch = lambda *a, **k: None
    try:
        py = _stream(ImageRecordIter(**_aug_kw(rec)))
    finally:
        native.decode_augment_batch = orig_a
        native.decode_jpeg_batch = orig_j
    for x, y in zip(nat, py):
        np.testing.assert_array_equal(x, y)


def test_mid_epoch_state_resume(tmp_path):
    """state_dict at batch N -> fresh iterator -> identical remaining
    stream, including the next epoch's shuffle."""
    rec = _write_rec(str(tmp_path / "d"))
    it = ImageRecordIter(**_aug_kw(rec))
    ref = _stream(it)
    it.reset()
    ref2 = _stream(it)  # epoch 1 (different shuffle than epoch 0)
    assert any(not np.array_equal(x, y) for x, y in zip(ref, ref2))

    it3 = ImageRecordIter(**_aug_kw(rec))
    seen = [it3.next().data[0].asnumpy() for _ in range(3)]
    state = it3.state_dict()
    assert state["global_pos"] == 12 and state["epoch"] == 0
    it4 = ImageRecordIter(**_aug_kw(rec))
    it4.load_state_dict(state)
    rest = _stream(it4)
    assert len(rest) == len(ref) - 3
    for x, y in zip(seen + rest, ref):
        np.testing.assert_array_equal(x, y)
    it4.reset()  # epoch rolls over exactly like the uninterrupted run
    for x, y in zip(_stream(it4), ref2):
        np.testing.assert_array_equal(x, y)


def test_state_resume_with_prefetch_producer(tmp_path):
    """The in-iterator prefetch producer runs ahead of the consumer;
    state_dict still snapshots the CONSUMED position."""
    rec = _write_rec(str(tmp_path / "e"))
    ref = _stream(ImageRecordIter(**_aug_kw(rec)))
    it = ImageRecordIter(**_aug_kw(rec, prefetch_buffer=2))
    for _ in range(2):
        it.next()
    state = it.state_dict()
    assert state["consumed"] == 2
    it2 = ImageRecordIter(**_aug_kw(rec, prefetch_buffer=2))
    it2.load_state_dict(state)
    rest = _stream(it2)
    for x, y in zip(rest, ref[2:]):
        np.testing.assert_array_equal(x, y)


def test_sharded_readers_tile_the_epoch(tmp_path):
    """Union of the rank streams == the epoch prefix, no overlap, equal
    step counts (block-cyclic slicing)."""
    rec = _write_rec(str(tmp_path / "f"), n=64)
    streams = {}
    for r in range(4):
        it = ImageRecordIter(**_aug_kw(rec, num_parts=4, part_index=r))
        streams[r] = [int(l) for b in it for l in b.label[0].asnumpy()]
    sizes = {r: len(v) for r, v in streams.items()}
    assert sizes == {0: 16, 1: 16, 2: 16, 3: 16}
    allseen = sum(streams.values(), [])
    assert len(allseen) == len(set(allseen)) == 64  # disjoint + complete
    # every rank shuffles identically: the union IS the global order
    it0 = ImageRecordIter(**_aug_kw(rec, num_parts=1, part_index=0))
    global_order = [int(l) for b in it0 for l in b.label[0].asnumpy()]
    assert set(allseen) == set(global_order)


def test_shard_shrink_4_to_2_repartitions_bitexact(tmp_path):
    """A checkpoint cut on a 4-rank gang resumes on 2 ranks at the same
    GLOBAL stream position — remaining batches (augmentation included)
    match the uninterrupted 2-rank run bit-exactly."""
    rec = _write_rec(str(tmp_path / "g"), n=64)
    it4 = ImageRecordIter(**_aug_kw(rec, num_parts=4, part_index=0))
    for _ in range(2):
        it4.next()
    state = it4.state_dict()
    assert state["global_pos"] == 32
    for r in range(2):
        ref = _stream(ImageRecordIter(
            **_aug_kw(rec, num_parts=2, part_index=r)))
        it2 = ImageRecordIter(**_aug_kw(rec, num_parts=2, part_index=r))
        it2.load_state_dict(state)
        rest = _stream(it2)
        start = state["global_pos"] // (4 * 2)
        assert len(rest) == len(ref) - start
        for x, y in zip(rest, ref[start:]):
            np.testing.assert_array_equal(x, y)


def test_indivisible_resume_position_raises(tmp_path):
    rec = _write_rec(str(tmp_path / "h"), n=64)
    it4 = ImageRecordIter(**_aug_kw(rec, num_parts=4, part_index=0))
    it4.next()
    state = it4.state_dict()  # global_pos 16
    it3 = ImageRecordIter(**_aug_kw(rec, num_parts=3, part_index=0))
    with pytest.raises(ValueError, match="global batch boundary"):
        it3.load_state_dict(state)  # 16 % (4*3) != 0


def test_prefetching_iter_state_excludes_staged(tmp_path):
    """PrefetchingIter.state_dict snapshots at the consumer position:
    the staged-ahead batch replays after a load."""
    data = np.arange(80).reshape(40, 2).astype(np.float32)
    ref = _stream(PrefetchingIter(NDArrayIter(data, batch_size=4)))
    it = PrefetchingIter(NDArrayIter(data, batch_size=4))
    for _ in range(3):
        it.next()
    state = it.state_dict()
    assert state["delivered"] == 3
    it2 = PrefetchingIter(NDArrayIter(data, batch_size=4))
    it2.load_state_dict(state)
    rest = _stream(it2)
    assert len(rest) == len(ref) - 3
    for x, y in zip(rest, ref[3:]):
        np.testing.assert_array_equal(x, y)


def test_prefetching_iter_state_wraps_record_reader(tmp_path):
    rec = _write_rec(str(tmp_path / "i"))
    ref = _stream(PrefetchingIter(ImageRecordIter(**_aug_kw(rec))))
    it = PrefetchingIter(ImageRecordIter(**_aug_kw(rec)))
    for _ in range(2):
        it.next()
    state = it.state_dict()
    assert state["iters"][0]["consumed"] == 2  # not the staged position
    it2 = PrefetchingIter(ImageRecordIter(**_aug_kw(rec)))
    it2.load_state_dict(state)
    for x, y in zip(_stream(it2), ref[2:]):
        np.testing.assert_array_equal(x, y)


def test_token_record_iter(tmp_path):
    """Fixed-length token blocks through the native reader: next-token
    shift, deterministic shuffle, sharding and state grammar."""
    path = str(tmp_path / "t.rec")
    toks = np.arange(2000, dtype=np.int32)
    nblk = write_token_shard(path, toks, seq_len=16)
    assert nblk == 124  # ceil((2000 - 16) / 16) stride-16 windows
    it = TokenRecordIter(path, seq_len=16, batch_size=4, shuffle=True,
                         seed=1, num_parts=1, part_index=0)
    b = it.next()
    assert b.data[0].shape == (4, 16) and b.label[0].shape == (4, 16)
    np.testing.assert_array_equal(b.data[0].asnumpy()[:, 1:],
                                  b.label[0].asnumpy()[:, :-1])
    # blocks overlap by one token (stride seq_len): consecutive records
    # of the unshuffled stream continue the corpus
    it_seq = TokenRecordIter(path, seq_len=16, batch_size=2,
                             num_parts=1, part_index=0)
    b0 = it_seq.next()
    assert int(b0.data[0].asnumpy()[1, 0]) == \
        int(b0.label[0].asnumpy()[0, -1])
    # state resume
    st = it.state_dict()
    it2 = TokenRecordIter(path, seq_len=16, batch_size=4, shuffle=True,
                          seed=1, num_parts=1, part_index=0)
    it2.load_state_dict(st)
    np.testing.assert_array_equal(it2.next().data[0].asnumpy(),
                                  it.next().data[0].asnumpy())
    # sharding tiles the epoch
    ids = []
    for r in range(2):
        itr = TokenRecordIter(path, seq_len=16, batch_size=4,
                              shuffle=True, seed=1, num_parts=2,
                              part_index=r)
        ids += [int(b.data[0].asnumpy()[i, 0]) for b in itr
                for i in range(4)]
    assert len(ids) == len(set(ids))
    # malformed shard refused with a named error
    bad = str(tmp_path / "bad.rec")
    with open(bad, "wb") as f:
        f.write(native.recordio_pack([b"x" * 7]))
    with pytest.raises(ValueError, match="fixed-length token blocks"):
        TokenRecordIter(bad, seq_len=16)


def test_trainer_checkpoint_carries_data_state(tmp_path):
    """ShardedTrainer.save_checkpoint(data_iter=) persists the stream
    position in the CRC-manifested checkpoint meta; resume(data_iter=)
    restores it — the full CheckpointManager round trip."""
    from mxnet_tpu import checkpoint
    from mxnet_tpu.gluon import loss as gloss, nn
    from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer

    rec = _write_rec(str(tmp_path / "j"))

    def build(seed):
        mx.random.seed(seed)
        net = nn.HybridSequential()
        net.add(nn.Dense(8, activation="relu"), nn.Dense(2))
        net.initialize(mx.init.Xavier())
        net(mx.nd.zeros((2, 3 * 24 * 24)))
        return ShardedTrainer(net, gloss.L2Loss(), "sgd",
                              {"learning_rate": 0.01},
                              mesh=DeviceMesh({"dp": 1}))

    manager = checkpoint.CheckpointManager(str(tmp_path / "ck"),
                                           prefix="dp", keep=3)
    it = ImageRecordIter(**_aug_kw(rec))
    ref = _stream(ImageRecordIter(**_aug_kw(rec)))
    trainer = build(0)
    for i in range(3):
        b = it.next()
        trainer.step(b.data[0].reshape((4, -1)), mx.nd.zeros((4, 2)))
    trainer.save_checkpoint(manager, epoch=1, data_iter=it)
    entry, _paths = manager.load()
    assert entry["meta"]["data_state"]["consumed"] == 3  # JSON round trip

    trainer2 = build(1)
    it2 = ImageRecordIter(**_aug_kw(rec))
    entry2 = trainer2.resume(manager, data_iter=it2)
    assert entry2["epoch"] == 1
    rest = _stream(it2)
    assert len(rest) == len(ref) - 3
    for x, y in zip(rest, ref[3:]):
        np.testing.assert_array_equal(x, y)


def test_sigkill_mid_epoch_resume_bitexact(tmp_path):
    """The acceptance drill, as subprocesses: SIGKILL at batch N inside
    the augmented streaming loop -> resume from the manager-persisted
    state -> the remaining stream (augmentation included) is bit-exact
    vs the uninterrupted run. Also resharded: the 4-rank cut resumes on
    a 2-rank gang matching the uninterrupted 2-rank stream."""
    rec = _write_rec(str(tmp_path / "k"), n=48)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "DP_REC": rec,
           "DP_BATCH": "4"}
    env.pop("MXNET_TPU_FAULTS", None)

    def run(**kv):
        e = {**env, **{k: str(v) for k, v in kv.items()}}
        return subprocess.run([sys.executable,
                               os.path.join(REPO, "tests",
                                            "_dataplane_child.py")],
                              env=e, capture_output=True, text=True,
                              timeout=120)

    ref_out = str(tmp_path / "ref.npz")
    p = run(DP_OUT=ref_out, DP_CKPT=str(tmp_path / "refck"))
    assert p.returncode == 0, p.stderr[-1500:]
    p = run(DP_KILL_AFTER=3, DP_CKPT=str(tmp_path / "ck"))
    assert p.returncode == -signal.SIGKILL, (p.returncode,
                                             p.stderr[-1500:])
    res_out = str(tmp_path / "res.npz")
    p = run(DP_RESUME=1, DP_OUT=res_out, DP_CKPT=str(tmp_path / "ck"))
    assert p.returncode == 0, p.stderr[-1500:]
    ref, res = dict(np.load(ref_out)), dict(np.load(res_out))
    assert int(res["__start__"]) == 3
    np.testing.assert_array_equal(res["crcs"], ref["crcs"][3:])

    # resharded 4 -> 2: kill a 4-rank reader, resume as 2 ranks
    ref2_out = str(tmp_path / "ref2.npz")
    p = run(DP_OUT=ref2_out, DP_CKPT=str(tmp_path / "ref2ck"),
            DP_PARTS=2, DP_PART=0)
    assert p.returncode == 0, p.stderr[-1500:]
    p = run(DP_KILL_AFTER=2, DP_CKPT=str(tmp_path / "ck4"),
            DP_PARTS=4, DP_PART=0)
    assert p.returncode == -signal.SIGKILL
    res2_out = str(tmp_path / "res2.npz")
    p = run(DP_RESUME=1, DP_OUT=res2_out, DP_CKPT=str(tmp_path / "ck4"),
            DP_PARTS=2, DP_PART=0)
    assert p.returncode == 0, p.stderr[-1500:]
    ref2, res2 = dict(np.load(ref2_out)), dict(np.load(res2_out))
    start = int(res2["__start__"])  # 2 4-rank batches == 4 2-rank ones
    assert start == 4
    np.testing.assert_array_equal(res2["crcs"], ref2["crcs"][start:])


# ----------------------------------------------------------- satellites --

def test_native_status_and_unavailable_warns_once(monkeypatch, caplog,
                                                  tmp_path):
    """_build/_load failure is cached, surfaced ONCE as a warning +
    telemetry counter, and explained by status()/diagnose."""
    import ctypes as _ctypes
    import logging

    from mxnet_tpu.telemetry import registry as _registry

    st = native.status()
    assert st["available"] and st["error"] is None
    saved = (native._lib, native._tried, native._error)
    cmd = ["g++"]
    monkeypatch.setattr(native, "_build", lambda: (_ for _ in ()).throw(
        subprocess.CalledProcessError(1, cmd, stderr=b"jpeglib.h: no")))
    monkeypatch.setattr(_ctypes, "CDLL",
                        lambda *a, **k: (_ for _ in ()).throw(
                            OSError("undefined symbol")))
    # the library is untracked and built on first use: a fresh checkout
    # has none, so the probe goes through _build
    monkeypatch.setattr(native, "_LIB_PATH", str(tmp_path / "absent.so"))
    native._lib, native._tried, native._error = None, False, None
    try:
        with caplog.at_level(logging.WARNING, logger="mxnet_tpu.native"):
            assert not native.available()
            assert not native.available()  # cached: probes once
        warns = [r for r in caplog.records
                 if "native IO library unavailable" in r.getMessage()]
        assert len(warns) == 1
        bad = native.status()
        assert bad["available"] is False
        assert "build failed" in bad["error"]
        assert "jpeglib" in bad["error"]
        series = _registry.counter(
            "mxtpu_native_unavailable_total",
            "Native IO library probe/build failures (Python fallback "
            "active)")
        assert series.series().get((), 0.0) >= 1
    finally:
        native._lib, native._tried, native._error = saved


def test_iter_bench_augment_mode(tmp_path):
    """benchmark/iter_bench.py --augment: reports img/s, img/s/core,
    the Python-fallback comparison and per-thread scaling, and drops
    the result where diagnose finds it."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import iter_bench

    line = iter_bench.run_augment(num_images=24, src_size=48,
                                  batch_size=8,
                                  data_shape=(3, 32, 32), epochs=1,
                                  threads=2)
    assert line["metric"] == "iter_bench_augment"
    assert line["value"] > 0 and line["img_s_per_core"] > 0
    assert line["python_img_s"] > 0
    assert "1" in line["thread_scaling"]
    assert line["native_augment"] == native.status()["augment"]
    iter_bench._persist(line)
    with open(iter_bench.LAST_RESULT_PATH) as f:
        assert json.load(f)["metric"] == "iter_bench_augment"


def test_diagnose_dataplane_section():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import diagnose

    out = diagnose.check_dataplane()
    assert out["native"]["available"] == native.available()
    assert out["native"]["augment"] == native.status()["augment"]
    assert "cores" in out


def test_dataplane_records_counter(tmp_path):
    from mxnet_tpu.telemetry import registry as _registry

    rec = _write_rec(str(tmp_path / "m"), n=8)
    counter = _registry.counter(
        "mxtpu_dataplane_records_total",
        "Records decoded by the streaming data plane", labels=("path",))
    path = "native" if native.status()["augment"] else "python"
    before = counter.series().get((path,), 0.0)
    list(ImageRecordIter(**_aug_kw(rec)))
    assert counter.series().get((path,), 0.0) >= before + 8


@pytest.mark.perf
def test_augment_overhead_within_noise_at_one_thread(tmp_path):
    """Fusing the augmenters into the decode loop must be ~free: the
    augmented native path stays within noise of plain decode at 1
    thread (generous envelope — decode dominates; the guard catches a
    quadratic augmenter or an accidental extra copy)."""
    import time

    if not native.status()["augment"]:
        pytest.skip("native fused augment not built on this host")
    rec = _write_rec(str(tmp_path / "p"), n=48)

    def run(**over):
        kw = _aug_kw(rec, preprocess_threads=1, shuffle=False, **over)
        it = ImageRecordIter(**kw)
        list(it)  # warm (page cache, pools)
        it.reset()
        t0 = time.perf_counter()
        list(it)
        return time.perf_counter() - t0

    plain = min(run(rand_crop=False, rand_mirror=False, color_jitter=0.0)
                for _ in range(3))
    aug = min(run() for _ in range(3))
    assert aug <= plain * 1.8 + 0.05, (aug, plain)
