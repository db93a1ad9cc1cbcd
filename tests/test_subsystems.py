"""Tests for profiler / callback / monitor / visualization / runtime /
util / amp (parity model: tests/python/unittest/test_profiler.py,
test_amp.py, and the callback/monitor doctests in the reference)."""
import json
import logging
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import amp, util
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon import loss as gloss


# ------------------------------------------------------------- profiler ----

def test_profiler_trace_and_aggregate(tmp_path):
    fname = str(tmp_path / "profile.json")
    mx.profiler.reset()
    mx.profiler.set_config(filename=fname, aggregate_stats=True)
    mx.profiler.set_state("run")
    a = mx.nd.ones((32, 32))
    ((a * 2) + 1).sum().wait_to_read()
    mx.profiler.set_state("stop")
    mx.profiler.dump()
    trace = json.load(open(fname))
    names = [e["name"] for e in trace["traceEvents"]]
    assert "sum" in names and "_mul_scalar" in names
    assert all({"ts", "dur", "ph"} <= set(e) for e in trace["traceEvents"])
    table = mx.profiler.dumps(sort_by="count")
    assert "sum" in table and "Count" in table


def test_profiler_pause_resume():
    mx.profiler.reset()
    mx.profiler.set_state("run")
    mx.profiler.pause()
    mx.nd.ones((4,)).sum().wait_to_read()
    mx.profiler.resume()
    assert mx.profiler.state() == "run"
    mx.profiler.set_state("stop")
    # nothing recorded while paused
    assert "sum" not in mx.profiler.dumps()


def test_profiler_instrumentation_objects(tmp_path):
    mx.profiler.reset()
    mx.profiler.set_state("run")
    domain = mx.profiler.Domain("test")
    with domain.new_task("work"):
        pass
    counter = domain.new_counter("ctr", 10)
    counter += 5
    domain.new_marker("mark").mark()
    mx.profiler.set_state("stop")
    fname = str(tmp_path / "p.json")
    mx.profiler.set_config(filename=fname)
    mx.profiler.dump()
    evs = json.load(open(fname))["traceEvents"]
    assert any(e["name"] == "work" for e in evs)
    assert any(e["ph"] == "C" for e in evs)
    assert any(e["ph"] == "i" for e in evs)


def test_profiler_hybrid_cachedop_event():
    mx.profiler.reset()
    net = nn.Dense(4)
    net.initialize()
    net.hybridize()
    x = mx.nd.ones((2, 8))
    net(x)  # compile outside profile window
    mx.profiler.set_state("run")
    net(x).wait_to_read()
    mx.profiler.set_state("stop")
    assert "CachedOp" in mx.profiler.dumps()


# ------------------------------------------------------------- callback ----

def _batch_param(epoch, nbatch, metric=None):
    from mxnet_tpu.module.base_module import BatchEndParam

    return BatchEndParam(epoch=epoch, nbatch=nbatch, eval_metric=metric,
                         locals=None)


def test_speedometer_logs(caplog):
    sp = mx.callback.Speedometer(batch_size=32, frequent=2, auto_reset=False)
    metric = mx.metric.Accuracy()
    metric.update([mx.nd.array([1, 1])], [mx.nd.array([[0.1, 0.9],
                                                       [0.8, 0.2]])])
    with caplog.at_level(logging.INFO):
        for i in range(1, 5):
            sp(_batch_param(0, i, metric))
    assert any("samples/sec" in r.message for r in caplog.records)


def test_do_checkpoint(tmp_path):
    prefix = str(tmp_path / "model")
    data = mx.sym.var("data")
    fc = mx.sym.FullyConnected(data, num_hidden=3, name="fc")
    arg = {"fc_weight": mx.nd.ones((3, 4)), "fc_bias": mx.nd.zeros((3,))}
    cb = mx.callback.do_checkpoint(prefix, period=1)
    cb(0, fc, arg, {})
    assert os.path.exists(prefix + "-symbol.json")
    assert os.path.exists(prefix + "-0001.params")
    sym, args, auxs = mx.model.load_checkpoint(prefix, 1)
    np.testing.assert_allclose(args["fc_weight"].asnumpy(), np.ones((3, 4)))


def test_log_train_metric(caplog):
    metric = mx.metric.Accuracy()
    metric.update([mx.nd.array([1])], [mx.nd.array([[0.1, 0.9]])])
    cb = mx.callback.log_train_metric(1)
    with caplog.at_level(logging.INFO):
        cb(_batch_param(0, 1, metric))
    assert any("accuracy" in r.message for r in caplog.records)


# -------------------------------------------------------------- monitor ----

def test_monitor_collects_stats():
    data = mx.sym.var("data")
    fc = mx.sym.FullyConnected(data, num_hidden=3, name="fc")
    ex = fc.simple_bind(mx.cpu(), data=(2, 4))
    ex.copy_params_from({"fc_weight": mx.nd.ones((3, 4)),
                         "fc_bias": mx.nd.zeros((3,))})
    mon = mx.monitor.Monitor(interval=1, pattern=".*weight.*", sort=True)
    mon.install(ex)
    mon.tic()
    ex.forward(is_train=True, data=np.ones((2, 4), np.float32))
    res = mon.toc()
    names = [k for _, k, _ in res]
    assert "fc_weight" in names
    assert all("bias" not in k for k in names)
    mon.toc_print()  # smoke


def test_monitor_interval():
    mon = mx.monitor.Monitor(interval=2)
    mon.tic()
    assert mon.activated
    res = mon.toc()
    mon.tic()  # step 1: not activated (1 % 2 != 0)
    assert not mon.activated


# -------------------------------------------------------- visualization ----

def test_print_summary_counts_params(capsys):
    data = mx.sym.var("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=64, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu", name="relu1")
    fc2 = mx.sym.FullyConnected(act, num_hidden=10, name="fc2")
    total = mx.visualization.print_summary(fc2, shape={"data": (32, 100)})
    out = capsys.readouterr().out
    assert total == 100 * 64 + 64 + 64 * 10 + 10
    assert "fc1(FullyConnected)" in out
    assert "(32, 64)" in out


def test_plot_network_gated():
    data = mx.sym.var("data")
    fc = mx.sym.FullyConnected(data, num_hidden=2, name="fc")
    try:
        import graphviz  # noqa: F401

        dot = mx.visualization.plot_network(fc)
        assert "fc" in dot.source
    except ImportError:
        with pytest.raises(ImportError):
            mx.visualization.plot_network(fc)


# -------------------------------------------------------------- runtime ----

def test_runtime_features():
    feats = mx.runtime.Features()
    assert feats.is_enabled("XLA")
    assert feats.is_enabled("CPU")
    assert not feats.is_enabled("CUDNN")
    with pytest.raises(RuntimeError):
        feats.is_enabled("NO_SUCH_FEATURE")
    assert repr(mx.runtime.Feature("X", True)).endswith("X")


# ----------------------------------------------------------------- util ----

def test_util_np_scopes():
    assert not util.is_np_shape() and not util.is_np_array()
    with util.np_shape(True):
        assert util.is_np_shape()
        with util.np_array(True):
            assert util.is_np_array()
        assert not util.is_np_array()
    assert not util.is_np_shape()


def test_util_use_np_decorator():
    @util.use_np
    def inner():
        return util.is_np_shape(), util.is_np_array()

    assert inner() == (True, True)
    assert not util.is_np_shape()
    util.set_np()
    assert util.is_np_shape() and util.is_np_array()
    util.reset_np()
    assert not util.is_np_shape()


def test_util_env():
    util.setenv("MXNET_TPU_TEST_ENV", "42")
    assert util.getenv("MXNET_TPU_TEST_ENV") == "42"
    util.setenv("MXNET_TPU_TEST_ENV", None)
    assert util.getenv("MXNET_TPU_TEST_ENV") is None


# ------------------------------------------------------------------ amp ----

def _dt(x):
    return np.dtype(x.dtype).name


@pytest.fixture
def amp_off():
    yield
    amp.turn_off()


def test_amp_eager_and_hybrid_cast(amp_off):
    net = nn.Sequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    x = mx.nd.random.uniform(shape=(4, 8))
    ref = net(x).asnumpy()
    amp.init("bfloat16")
    out = net(x)
    assert _dt(out) == "bfloat16"
    net.hybridize()
    out_h = net(x)
    assert _dt(out_h) == "bfloat16"
    np.testing.assert_allclose(out.asnumpy().astype(np.float32), ref,
                               rtol=0.05, atol=0.05)


def test_amp_fp32_ops_stay_fp32(amp_off):
    amp.init("bfloat16")
    x = mx.nd.ones((2, 3)).astype("bfloat16")
    assert str(mx.nd.softmax(x).dtype) == "float32"
    assert str(mx.nd.sum(x).dtype) == "float32"


def test_amp_symbol_path(amp_off):
    data = mx.sym.var("data")
    fc = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    sm = mx.sym.softmax(fc)
    amp.init("bfloat16")
    ex = sm.simple_bind(mx.cpu(), data=(2, 8))
    out = ex.forward(is_train=False,
                     data=np.random.rand(2, 8).astype(np.float32))
    assert str(out[0].dtype) == "float32"  # softmax forced fp32


def test_amp_training_converges(amp_off):
    np.random.seed(0)
    mx.random.seed(0)
    X = np.random.randn(128, 10).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    amp.init("bfloat16")
    net = nn.Sequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(2))
    net.initialize(mx.init.Xavier())
    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.1})
    lfn = gloss.SoftmaxCrossEntropyLoss()
    Xn, yn = mx.nd.array(X), mx.nd.array(y)
    losses = []
    for _ in range(40):
        with mx.autograd.record():
            loss = lfn(net(Xn), yn).mean()
        loss.backward()
        trainer.step(1)
        losses.append(float(loss.asscalar()))
    assert losses[-1] < 0.5 * losses[0], (losses[0], losses[-1])


def test_amp_loss_scaler_dynamics():
    scaler = amp.LossScaler(init_scale=1024, scale_factor=2, scale_window=2)
    scaler.update_scale(overflow=True)
    assert scaler.loss_scale == 512
    scaler.update_scale(False)
    scaler.update_scale(False)
    assert scaler.loss_scale == 1024  # doubled after window


def test_amp_convert_hybrid_block(amp_off):
    net = nn.Dense(4)
    net.initialize()
    x = mx.nd.ones((2, 8))
    net(x)
    net2 = amp.convert_hybrid_block(net, "bfloat16")
    out = net2(x)
    assert _dt(out) == "bfloat16"


def test_amp_generation_invalidates_caches(amp_off):
    net = nn.Dense(3)
    net.initialize()
    net.hybridize()
    x = mx.nd.ones((2, 5))
    out1 = net(x)
    assert _dt(out1) == "float32"
    amp.init("bfloat16")
    out2 = net(x)
    assert _dt(out2) == "bfloat16"
    amp.turn_off()
    out3 = net(x)
    assert _dt(out3) == "float32"


def test_log_get_logger(tmp_path):
    """parity: python/mxnet/log.py getLogger + formatter."""
    import logging

    from mxnet_tpu import log as mxlog

    logfile = str(tmp_path / "t.log")
    lg = mxlog.get_logger("mxtpu_test_logger", filename=logfile,
                          level=mxlog.INFO)
    lg.info("hello %d", 42)
    for h in lg.handlers:
        h.flush()
    assert "hello 42" in open(logfile).read()
    # idempotent: second call must not duplicate handlers
    lg2 = mxlog.get_logger("mxtpu_test_logger")
    assert lg2 is lg and len(lg.handlers) == 1
    assert mxlog.getLogger is mxlog.get_logger
    logging.getLogger("mxtpu_test_logger").handlers.clear()


def test_feedforward_legacy_api(tmp_path):
    """parity: model.py FeedForward — fit/predict/score/save/load over the
    Module adapter."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.model import FeedForward

    rs = np.random.RandomState(0)
    X = rs.rand(128, 8).astype("f")
    w = rs.randn(8).astype("f")
    y = (X @ w > 0).astype("f")

    data = mx.sym.var("data")
    net = mx.sym.FullyConnected(data, num_hidden=16)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=2)
    net = mx.sym.SoftmaxOutput(net, mx.sym.var("softmax_label"),
                            name="softmax")

    model = FeedForward.create(net, X, y, num_epoch=12, optimizer="adam",
                               learning_rate=0.05, numpy_batch_size=32)
    acc = model.score(mx.io.NDArrayIter(X, y, batch_size=32))
    assert acc > 0.8, acc
    preds = model.predict(mx.io.NDArrayIter(X, batch_size=32))
    assert preds.shape == (128, 2)

    prefix = str(tmp_path / "ff")
    model.save(prefix, epoch=12)
    loaded = FeedForward.load(prefix, 12)
    preds2 = loaded.predict(mx.io.NDArrayIter(X, batch_size=32))
    np.testing.assert_allclose(preds2, preds, rtol=1e-5)


def test_model_zoo_get_model_names():
    from mxnet_tpu.gluon.model_zoo import vision

    names = vision.get_model_names()
    assert "resnet50_v1" in names and "mobilenet1_0" in names \
        and len(names) >= 25


# ----------------------------------------------------------- bulking -------

def _mlp():
    net = nn.Sequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    return net


def _run_mlp(net, x_np, bulk_size):
    x = mx.nd.array(x_np)
    with mx.engine.bulk(bulk_size):
        with mx.autograd.record():
            out = net(x)
            loss = (out * out).sum()
        loss.backward()
        fwd = out.asnumpy()
        grads = {k: p.grad().asnumpy()
                 for k, p in net.collect_params().items()}
    return fwd, grads


def test_bulk_numerics_match_unbulked_mlp():
    """Fused-segment execution and its one-tape-node VJP must reproduce
    per-op dispatch numerics (forward AND parameter grads)."""
    np.random.seed(7)
    mx.random.seed(7)
    net = _mlp()
    x_np = np.random.rand(8, 12).astype(np.float32)
    fwd_u, grads_u = _run_mlp(net, x_np, 1)       # today's per-op path
    for p in net.collect_params().values():
        p.zero_grad()
    fwd_b, grads_b = _run_mlp(net, x_np, 16)      # bulked
    np.testing.assert_allclose(fwd_b, fwd_u, rtol=1e-5, atol=1e-6)
    assert grads_u.keys() == grads_b.keys()
    for k in grads_u:
        np.testing.assert_allclose(grads_b[k], grads_u[k],
                                   rtol=1e-5, atol=1e-6, err_msg=k)


def test_bulk_flush_on_sync_points():
    with mx.engine.bulk(8):
        a = mx.nd.ones((4,))
        b = a * 2
        c = b + 1
        assert mx.engine.bulk_pending() == 2
        # metadata is statically known: no flush
        assert b.shape == (4,) and str(c.dtype) == "float32"
        assert mx.engine.bulk_pending() == 2
        # value read flushes the whole segment
        np.testing.assert_allclose(c.asnumpy(), np.full(4, 3.0))
        assert mx.engine.bulk_pending() == 0
        # waitall is a sync point
        d = a + 5
        assert mx.engine.bulk_pending() == 1
        mx.nd.waitall()
        assert mx.engine.bulk_pending() == 0
        np.testing.assert_allclose(d.asnumpy(), np.full(4, 6.0))
        # control flow on values forces too
        e = (a * 3).sum()
        assert mx.engine.bulk_pending() == 2  # _mul_scalar + sum
        assert bool(e > 11.0)
        assert mx.engine.bulk_pending() == 0
        # in-place mutation is a sync point (ordering + tape identity)
        f = a * 7
        assert mx.engine.bulk_pending() == 1
        a[:] = 0
        assert mx.engine.bulk_pending() == 0
        np.testing.assert_allclose(f.asnumpy(), np.full(4, 7.0))
        # segment-size limit auto-flushes (BulkFlush analogue)
        x = mx.nd.ones((4,))
        for _ in range(9):
            x = x * 1.5
        assert mx.engine.bulk_pending() == 1
        np.testing.assert_allclose(x.asnumpy(), np.full(4, 1.5 ** 9),
                                   rtol=1e-6)
    assert mx.engine.bulk_pending() == 0  # scope exit flushed


def test_bulk_naive_engine_disables(monkeypatch):
    monkeypatch.setenv("MXNET_ENGINE_TYPE", "NaiveEngine")
    assert mx.engine.bulk_size() == 1
    with mx.engine.bulk(8):
        assert mx.engine.bulk_size() == 1  # naive wins over the knob
        a = mx.nd.ones((4,))
        b = a * 2
        assert mx.engine.bulk_pending() == 0  # executed eagerly
        np.testing.assert_allclose(b.asnumpy(), np.full(4, 2.0))


def test_bulk_nested_contexts(monkeypatch):
    monkeypatch.delenv("MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN", raising=False)
    monkeypatch.setattr(mx.engine, "_env_bulk", None)
    monkeypatch.setattr(mx.engine._tls, "bulk_size", None, raising=False)
    assert mx.engine.bulk_size() == 1  # default: per-op dispatch
    with mx.engine.bulk(4):
        assert mx.engine.bulk_size() == 4
        a = mx.nd.ones((2,))
        b = a * 2
        assert mx.engine.bulk_pending() == 1
        with mx.engine.bulk(0):
            # entering the inner scope flushed the outer segment
            assert mx.engine.bulk_pending() == 0
            c = b + 1  # bulking off: executes per-op
            assert mx.engine.bulk_pending() == 0
        assert mx.engine.bulk_size() == 4  # restored
        d = c * 3
        assert mx.engine.bulk_pending() == 1
        np.testing.assert_allclose(d.asnumpy(), np.full(2, 9.0))
    assert mx.engine.bulk_size() == 1
    assert mx.engine.bulk_pending() == 0


def test_bulk_profiler_segment_events(tmp_path):
    fname = str(tmp_path / "bulk_profile.json")
    mx.profiler.reset()
    mx.profiler.set_config(filename=fname, aggregate_stats=True)
    a = mx.nd.ones((8,))
    with mx.engine.bulk(8):
        mx.profiler.set_state("run")
        ((a * 2) + 1).sum().wait_to_read()
        mx.profiler.set_state("stop")
    mx.profiler.dump()
    evs = json.load(open(fname))["traceEvents"]
    seg = [e for e in evs if e["name"].startswith("BulkSegment")]
    assert seg and seg[0]["args"]["op_count"] == 3
    assert "_mul_scalar" in seg[0]["args"]["ops"]
