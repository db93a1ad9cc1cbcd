"""Parallelism tests on the 8-device virtual CPU mesh.

Parity model: tests/python/unittest/test_kvstore.py + multi_device_exec —
multi-device logic tested without accelerators (SURVEY §4 'multi-device
logic is testable without GPUs'); here the devices are the virtual CPU mesh.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon import nn, loss as gloss
from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer, sharding_rules


def _make_net():
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu", in_units=16),
            nn.Dense(4, in_units=32))
    net.initialize(mx.init.Xavier())
    return net


def test_mesh_construction():
    mesh = DeviceMesh()
    assert mesh.num_devices == 8
    assert mesh.size("dp") == 8
    mesh = DeviceMesh({"dp": 4, "tp": 2})
    assert mesh.size("tp") == 2
    assert mesh.axis_names == ("dp", "tp")
    # smaller meshes take a device prefix
    assert DeviceMesh({"dp": 3}).num_devices == 3
    with pytest.raises(ValueError):
        DeviceMesh({"dp": 16})  # more than available


def test_sharding_rules():
    net = _make_net()
    mesh = DeviceMesh({"dp": 4, "tp": 2})
    rules = sharding_rules(net.collect_params(), mesh)
    w_specs = [v for k, v in rules.items() if k.endswith("weight")]
    assert all(s and s[0] == "tp" for s in w_specs)  # 32 and 4... 4%2==0
    b_specs = [v for k, v in rules.items() if k.endswith("bias")]
    assert all(s == () for s in b_specs)


@pytest.mark.parametrize("axes", [{"dp": 8}, {"dp": 4, "tp": 2}])
def test_sharded_trainer_converges(axes):
    np.random.seed(0)
    mx.random.seed(0)
    net = _make_net()
    mesh = DeviceMesh(axes)
    st = ShardedTrainer(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                        {"learning_rate": 0.1, "momentum": 0.9}, mesh=mesh)
    rng = np.random.default_rng(0)
    centers = rng.normal(0, 2.0, (4, 16))
    labels = rng.integers(0, 4, 64)
    data = (centers[labels] + rng.normal(0, 0.3, (64, 16))).astype(np.float32)
    x, y = mx.nd.array(data), mx.nd.array(labels.astype(np.float32))
    losses = [float(st.step(x, y).asscalar()) for _ in range(25)]
    assert losses[-1] < losses[0] * 0.2, f"no convergence: {losses[::6]}"
    # sharded predict agrees with labels
    acc = (st.predict(x).argmax(axis=1).asnumpy() == labels).mean()
    assert acc > 0.95


def test_sharded_matches_single_device():
    """dp-sharded training step == single-device training step (the
    correctness core of data parallelism: allreduced grads = full-batch
    grads)."""
    def run(mesh_axes):
        np.random.seed(3)
        mx.random.seed(3)
        net = _make_net()
        mesh = DeviceMesh(mesh_axes, devices=None)
        st = ShardedTrainer(net, gloss.L2Loss(), "sgd",
                            {"learning_rate": 0.05}, mesh=mesh)
        rng = np.random.default_rng(1)
        x = mx.nd.array(rng.normal(size=(32, 16)).astype(np.float32))
        y = mx.nd.array(rng.normal(size=(32, 4)).astype(np.float32))
        for _ in range(5):
            loss = st.step(x, y)
        st.unshard()
        return [p.data().asnumpy() for p in net.collect_params().values()], \
            float(loss.asscalar())

    params8, loss8 = run({"dp": 8})
    params1, loss1 = run({"dp": 1})
    assert abs(loss8 - loss1) < 1e-5
    for a, b in zip(params8, params1):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_batchnorm_stats_update_in_sharded_step():
    np.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, in_units=8), nn.BatchNorm(axis=-1, in_channels=16),
            nn.Dense(2, in_units=16))
    net.initialize()
    bn = net[1]
    rm0 = bn.running_mean.data().asnumpy().copy()
    st = ShardedTrainer(net, gloss.L2Loss(), "sgd", {"learning_rate": 0.01},
                        mesh=DeviceMesh({"dp": 8}))
    x = mx.nd.array(np.random.rand(16, 8).astype(np.float32) + 1.0)
    y = mx.nd.array(np.random.rand(16, 2).astype(np.float32))
    st.step(x, y)
    rm1 = bn.running_mean.data().asnumpy()
    assert not np.allclose(rm0, rm1), "BN stats not updated in sharded step"


def test_uneven_batch_raises_cleanly():
    net = _make_net()
    st = ShardedTrainer(net, gloss.L2Loss(), "sgd", {},
                        mesh=DeviceMesh({"dp": 8}))
    x = mx.nd.ones((12, 16))  # 12 % 8 != 0
    y = mx.nd.ones((12, 4))
    with pytest.raises(Exception):
        st.step(x, y)


def test_graft_entry_dryrun():
    """The driver's multichip dry run must pass on the virtual mesh."""
    import sys
    sys.path.insert(0, "/root/repo")
    import __graft_entry__ as g

    g.dryrun_multichip(8)


def test_ring_attention_matches_reference():
    """Ring attention over the sp axis is numerically exact vs single-device
    attention (full and causal)."""
    import jax.numpy as jnp

    from mxnet_tpu.parallel import attention, ring_attention

    np.random.seed(0)
    B, H, S, D = 2, 4, 64, 16
    q = jnp.array(np.random.randn(B, H, S, D).astype(np.float32))
    k = jnp.array(np.random.randn(B, H, S, D).astype(np.float32))
    v = jnp.array(np.random.randn(B, H, S, D).astype(np.float32))
    mesh = DeviceMesh({"sp": 8})
    for causal in (False, True):
        ref = np.asarray(attention(q, k, v, causal=causal))
        out = np.asarray(ring_attention(q, k, v, mesh, causal=causal))
        assert np.abs(ref - out).max() < 1e-5, f"causal={causal}"


def test_ring_attention_ndarray_api():
    from mxnet_tpu.parallel import ring_attention

    q = mx.nd.random.uniform(shape=(1, 2, 32, 8))
    out = ring_attention(q, q, q, DeviceMesh({"sp": 8}), causal=True)
    assert out.shape == (1, 2, 32, 8)
    assert isinstance(out, mx.nd.NDArray)


def test_pipeline_parallel_matches_sequential():
    """GPipe microbatch pipeline over the pp axis: forward AND jax.grad
    backward are exact vs the sequential stack (parallel/pipeline.py)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel import pipeline_apply, stack_stage_params

    S, M, B, D = 4, 8, 16, 12
    rs = np.random.RandomState(0)
    stage_params = [
        {"w": jnp.asarray(rs.randn(D, D) * 0.3, jnp.float32),
         "b": jnp.asarray(rs.randn(D) * 0.1, jnp.float32)}
        for _ in range(S)]
    stacked = stack_stage_params(stage_params)

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    mesh = DeviceMesh({"pp": S})
    fn = pipeline_apply(stage_fn, mesh, num_microbatches=M)
    x = jnp.asarray(rs.randn(B, D), jnp.float32)
    ref = x
    for p in stage_params:
        ref = stage_fn(p, ref)
    assert float(jnp.abs(fn(stacked, x) - ref).max()) < 1e-5

    def loss_pipe(sp):
        return jnp.sum(fn(sp, x) ** 2)

    def loss_seq(plist):
        h = x
        for p in plist:
            h = stage_fn(p, h)
        return jnp.sum(h ** 2)

    g_pipe = jax.grad(loss_pipe)(stacked)
    g_seq = jax.grad(loss_seq)(stage_params)
    for s in range(S):
        for k in ("w", "b"):
            assert float(jnp.abs(g_pipe[k][s] - g_seq[s][k]).max()) < 1e-4


def test_moe_expert_parallel_matches_dense():
    """Top-1 Switch MoE over the ep axis: output, aux loss and router
    gradient match the dense oracle (parallel/moe.py)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel import moe_apply, stack_expert_params

    E, N, D = 8, 32, 6
    rs = np.random.RandomState(0)
    experts = [{"w": jnp.asarray(rs.randn(D, D) * 0.5, jnp.float32)}
               for _ in range(E)]
    router_w = jnp.asarray(rs.randn(D, E), jnp.float32)
    x = jnp.asarray(rs.randn(N, D), jnp.float32)

    def expert_fn(p, xx):
        return jnp.tanh(xx @ p["w"])

    mesh = DeviceMesh({"ep": E})
    fn = moe_apply(expert_fn, mesh)
    y, aux = fn(stack_expert_params(experts), router_w, x)

    probs = np.asarray(jax.nn.softmax(x @ router_w, axis=-1))
    assign = probs.argmax(-1)
    ref = np.zeros((N, D), np.float32)
    for i in range(N):
        e = assign[i]
        ref[i] = probs[i, e] * np.tanh(
            np.asarray(x[i]) @ np.asarray(experts[e]["w"]))
    assert float(np.abs(np.asarray(y) - ref).max()) < 1e-5
    f = np.bincount(assign, minlength=E) / N
    assert abs(float(aux) - E * float((f * probs.mean(0)).sum())) < 1e-5

    def loss(params, rw):
        yy, aa = fn(params, rw, x)
        return jnp.sum(yy ** 2) + 0.01 * aa

    g_router = jax.grad(loss, argnums=1)(stack_expert_params(experts),
                                         router_w)
    assert float(jnp.abs(g_router).max()) > 0


def _mk_trainer_net(seed=0):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon

    mx.random.seed(seed)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(32, activation="relu"))
    net.add(gluon.nn.Dense(8))
    net.initialize(mx.init.Xavier())
    return net


def _train_steps(trainer_kwargs, steps=3, seed=0):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer

    net = _mk_trainer_net(seed)
    x = mx.nd.array(np.random.RandomState(1).randn(16, 12)
                    .astype(np.float32))
    y = mx.nd.array(np.random.RandomState(2).randint(0, 8, 16)
                    .astype(np.float32))
    net(x)
    tr = ShardedTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
                        {"learning_rate": 0.01},
                        mesh=DeviceMesh({"dp": 8}), **trainer_kwargs)
    losses = [float(tr.step(x, y).asscalar()) for _ in range(steps)]
    tr.unshard()
    # positional (auto-names differ between nets: global name counters)
    params = [p.data().asnumpy() for p in net.collect_params().values()]
    return losses, params, tr


def test_sharded_trainer_zero_matches_baseline():
    """ZeRO-1 state sharding changes memory layout, not numerics: losses
    and params match the unsharded-state baseline, and the Adam moments
    really live dp-sharded (sharded_trainer.py _state_spec_for)."""
    base_losses, base_params, _ = _train_steps({})
    z_losses, z_params, tr = _train_steps({"zero": True})
    np.testing.assert_allclose(z_losses, base_losses, rtol=1e-4)
    for zp, bp in zip(z_params, base_params):
        np.testing.assert_allclose(zp, bp, rtol=2e-3, atol=1e-5)
    sharded = [s for per in tr._opt_raws for s in per
               if any(ax == "dp" for ax in (s.sharding.spec or ()))]
    assert sharded, "no optimizer state ended up dp-sharded under zero=True"


def test_sharded_trainer_remat_matches_baseline():
    """jax.checkpoint changes scheduling, not results."""
    base_losses, base_params, _ = _train_steps({})
    r_losses, r_params, _ = _train_steps({"remat": True})
    np.testing.assert_allclose(r_losses, base_losses, rtol=1e-5)
    for rp, bp in zip(r_params, base_params):
        np.testing.assert_allclose(rp, bp, rtol=1e-4, atol=1e-6)


def test_sharded_trainer_grad_accum():
    """accum_steps=N microbatch scan: numerics match the accum=1 run on
    a deterministic net; indivisible batches raise; sub-dp microbatches
    warn about idle devices."""
    import warnings

    import pytest

    base_losses, base_params, _ = _train_steps({})
    a_losses, a_params, _ = _train_steps({"accum_steps": 2})
    np.testing.assert_allclose(a_losses, base_losses, rtol=1e-4)
    for ap, bp in zip(a_params, base_params):
        np.testing.assert_allclose(ap, bp, rtol=2e-3, atol=1e-5)
    with pytest.raises(ValueError, match="not divisible by accum_steps"):
        _train_steps({"accum_steps": 5}, steps=1)  # 16 % 5 != 0
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        _train_steps({"accum_steps": 4}, steps=1)  # microbatch 4 < dp 8
    assert any("idle" in str(x.message) for x in w)


def test_sharded_trainer_checkpoint_resume():
    """save_states/load_states round-trip mid-training: a freshly built
    trainer (different gluon auto-prefixes, ZeRO layout, Dropout in the
    net) continues with EXACTLY the losses of the uninterrupted run —
    entries are positional and the RNG stream is restored
    (sharded_trainer.py save_states)."""
    import tempfile

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer

    x = mx.nd.array(np.random.RandomState(1).randn(16, 12)
                    .astype(np.float32))
    y = mx.nd.array(np.random.RandomState(2).randint(0, 8, 16)
                    .astype(np.float32))

    def make(seed=0, **kw):
        mx.random.seed(seed)
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(32, activation="relu"))
        net.add(gluon.nn.Dropout(0.3))
        net.add(gluon.nn.Dense(8))
        net.initialize(mx.init.Xavier())
        net(x)
        return net, ShardedTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
            {"learning_rate": 0.05}, mesh=DeviceMesh({"dp": 8}), **kw)

    _, tr = make()
    for _ in range(3):
        tr.step(x, y)
    with tempfile.NamedTemporaryFile(suffix=".npz") as f:
        tr.save_states(f.name)
        ref = [float(tr.step(x, y).asscalar()) for _ in range(3)]

        # fresh net instance: new auto-prefixes, ZeRO state layout — the
        # positional format + RNG restore must still reproduce exactly
        net2, tr2 = make(seed=123, zero=True)
        tr2.load_states(f.name)
        got = [float(tr2.step(x, y).asscalar()) for _ in range(3)]

        # mismatched trainer (sgd: different state slots) must refuse
        # loudly BEFORE mutating anything
        net3 = _mk_trainer_net(7)
        net3(x)
        tr3 = ShardedTrainer(net3, gluon.loss.SoftmaxCrossEntropyLoss(),
                             "sgd", {"learning_rate": 0.05},
                             mesh=DeviceMesh({"dp": 8}))
        before = [p.data().asnumpy().copy()
                  for p in net3.collect_params().values()]
        import pytest

        with pytest.raises(ValueError, match="does not match"):
            tr3.load_states(f.name)
        for b, p in zip(before, net3.collect_params().values()):
            np.testing.assert_array_equal(b, p.data().asnumpy())

        # same key set but different architecture (wider layer): shape
        # validation must refuse BEFORE mutating anything
        net4 = gluon.nn.HybridSequential()
        net4.add(gluon.nn.Dense(64, activation="relu"))
        net4.add(gluon.nn.Dropout(0.3))
        net4.add(gluon.nn.Dense(8))
        net4.initialize(mx.init.Xavier())
        net4(x)
        tr4 = ShardedTrainer(net4, gluon.loss.SoftmaxCrossEntropyLoss(),
                             "adam", {"learning_rate": 0.05},
                             mesh=DeviceMesh({"dp": 8}))
        t4_before = tr4._t
        with pytest.raises(ValueError, match="has shape"):
            tr4.load_states(f.name)
        assert tr4._t == t4_before
    np.testing.assert_allclose(got, ref, rtol=1e-4)
    assert tr2._t == tr._t


def test_sharded_trainer_checkpoint_bf16():
    """bf16 params round-trip bit-exactly through the npz checkpoint
    (stored as uint16 bits — npy cannot hold bf16)."""
    import tempfile

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer

    x = mx.nd.array(np.random.RandomState(0).randn(8, 6).astype(np.float32))
    net = _mk_trainer_net(5)
    net(x.astype("float32"))
    net.cast("bfloat16")
    xb = x.astype("bfloat16")
    y = mx.nd.array(np.zeros(8, np.float32))
    tr = ShardedTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                        {"learning_rate": 0.01, "momentum": 0.9},
                        mesh=DeviceMesh({"dp": 8}))
    tr.step(xb, y)
    import jax

    want = [np.asarray(jax.device_get(h._data).astype("float32"))
            for h in tr._train_handles]
    with tempfile.NamedTemporaryFile(suffix=".npz") as f:
        tr.save_states(f.name)
        tr.step(xb, y)  # mutate past the checkpoint
        tr.load_states(f.name)
    got = [np.asarray(jax.device_get(h._data).astype("float32"))
           for h in tr._train_handles]
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    assert str(tr._train_handles[0]._data.dtype) == "bfloat16"


def test_ring_attention_backward_matches_dense():
    """SP TRAINING guarantee: jax.grad through the ring schedule (scan of
    ppermutes) equals dense-attention gradients for q, k and v."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel import attention, ring_attention_sharded

    np.random.seed(0)
    B, H, S, D = 2, 2, 32, 8
    q, k, v = (jnp.asarray(np.random.randn(B, H, S, D), jnp.float32)
               for _ in range(3))
    fn = ring_attention_sharded(DeviceMesh({"sp": 8}), causal=True)

    def loss_ring(q, k, v):
        return jnp.sum(fn(q, k, v) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(attention(q, k, v, causal=True) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_dense):
        assert float(jnp.abs(a - b).max()) < 1e-5


def test_sharded_trainer_lr_scheduler():
    """lr_scheduler in optimizer_params drives a per-step traced lr (no
    recompilation): the schedule's decayed steps must match manual SGD
    with the decayed rates exactly."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer

    # FactorScheduler is STATEFUL (base_lr decays in place): the
    # trainer and the manual reference each need their own instance
    def make_sched():
        return mx.lr_scheduler.FactorScheduler(step=2, factor=0.5,
                                               base_lr=0.2)

    x = mx.nd.array(np.random.RandomState(1).randn(16, 12)
                    .astype(np.float32))
    y = mx.nd.array(np.random.RandomState(2).randn(16, 4)
                    .astype(np.float32))

    def make():
        mx.random.seed(0)
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(4, in_units=12))
        net.initialize(mx.init.Xavier())
        net(x)
        return net

    net = make()
    tr = ShardedTrainer(net, gluon.loss.L2Loss(), "sgd",
                        {"learning_rate": 0.2,
                         "lr_scheduler": make_sched()},
                        mesh=DeviceMesh({"dp": 8}))
    for _ in range(4):
        tr.step(x, y)
    tr.unshard()
    got = [p.data().asnumpy() for p in net.collect_params().values()]

    # manual: same per-step decayed rates through separate trainers
    net2 = make()
    raws = [p.data()._data for p in net2.collect_params().values()]
    ref_sched = make_sched()
    lrs = [float(ref_sched(t)) for t in range(1, 5)]  # _t pre-increments

    def loss_fn(ws, x_, y_):
        import jax.numpy as jnp

        pred = x_ @ ws[0].T + ws[1]
        return jnp.mean(jnp.square(pred - y_)) / 2.0

    import jax.numpy as jnp

    xs, ys = jnp.asarray(x.asnumpy()), jnp.asarray(y.asnumpy())
    ws = [jnp.asarray(r) for r in raws]
    for lr in lrs:
        grads = jax.grad(loss_fn)(ws, xs, ys)
        # trainer wd defaults to 0; weight has wd_mult 1 but wd=0
        ws = [w - lr * g for w, g in zip(ws, grads)]
    for a, b in zip(got, ws):
        np.testing.assert_allclose(a, np.asarray(b), rtol=1e-4, atol=1e-6)


def test_sharded_trainer_scheduler_checkpoint_rewind():
    """Schedulers decay in place; load_states must rewind their state so
    a resumed run reproduces the uninterrupted schedule exactly."""
    import tempfile

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer

    x = mx.nd.array(np.random.RandomState(1).randn(16, 12)
                    .astype(np.float32))
    y = mx.nd.array(np.random.RandomState(2).randn(16, 4)
                    .astype(np.float32))

    def make():
        mx.random.seed(0)
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(4, in_units=12))
        net.initialize(mx.init.Xavier())
        net(x)
        return ShardedTrainer(
            net, gluon.loss.L2Loss(), "sgd",
            {"learning_rate": 0.2,
             "lr_scheduler": mx.lr_scheduler.FactorScheduler(
                 step=2, factor=0.5)},
            mesh=DeviceMesh({"dp": 8}))

    tr = make()
    # learning_rate must seed the scheduler's base_lr (Optimizer parity)
    assert tr._lr_scheduler.base_lr == 0.2
    for _ in range(4):
        tr.step(x, y)
    with tempfile.NamedTemporaryFile(suffix=".npz") as f:
        tr.save_states(f.name)
        ref = [float(tr.step(x, y).asscalar()) for _ in range(4)]
        tr2 = make()
        for _ in range(10):  # decay tr2's scheduler well past step 4
            tr2.step(x, y)
        tr2.load_states(f.name)
        got = [float(tr2.step(x, y).asscalar()) for _ in range(4)]
    np.testing.assert_allclose(got, ref, rtol=1e-5)


def test_sharded_trainer_set_learning_rate():
    """set_learning_rate changes the traced lr without recompilation;
    raises UserWarning while a scheduler drives it and the property
    consults the scheduler (gluon Trainer / Optimizer contract)."""
    x = mx.nd.ones((8, 12))
    y = mx.nd.zeros((8, 4))
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(4, in_units=12))
    net.initialize(mx.init.Xavier())
    net(x)
    tr = ShardedTrainer(net, gloss.L2Loss(), "sgd",
                        {"learning_rate": 0.1}, mesh=DeviceMesh({"dp": 8}))
    tr.step(x, y)
    compiled = tr._step_fn
    w_before = [p.data().asnumpy().copy()
                for p in net.collect_params().values()]
    tr.learning_rate = 0.0  # freeze (gluon property-setter idiom)
    tr.step(x, y)
    assert tr._step_fn is compiled  # no recompilation
    tr.unshard()
    for b, p in zip(w_before, net.collect_params().values()):
        np.testing.assert_allclose(p.data().asnumpy(), b, rtol=1e-6)
    tr2 = ShardedTrainer(net, gloss.L2Loss(), "sgd",
                         {"learning_rate": 0.1,
                          "lr_scheduler":
                          mx.lr_scheduler.FactorScheduler(step=5)},
                         mesh=DeviceMesh({"dp": 8}))
    with pytest.raises(UserWarning, match="LRScheduler"):
        tr2.set_learning_rate(0.5)
    assert tr.learning_rate == 0.0
    assert tr2.learning_rate == 0.1  # property consults the scheduler


# --------------------------------------------------------------------------
# full optimizer zoo inside the compiled step (VERDICT r4 item 4):
# ShardedTrainer numerics must equal the eager gluon Trainer driving the
# same optimizer (which itself is tested against reference numerics in
# test_optimizer.py)

_ZOO = [
    ("sgd", {"momentum": 0.9, "wd": 1e-3}),
    ("nag", {"momentum": 0.9}),
    ("signum", {"momentum": 0.9, "wd_lh": 1e-3}),
    ("lars", {"momentum": 0.9, "eta": 0.01}),
    ("lbsgd", {"momentum": 0.9, "warmup_strategy": "linear",
               "warmup_epochs": 1, "updates_per_epoch": 4}),
    ("dcasgd", {"momentum": 0.9, "lamda": 0.04}),
    ("adam", {}),
    ("ftml", {}),
    ("lamb", {}),
    ("adagrad", {}),
    ("rmsprop", {}),
    ("rmsprop", {"centered": True}),
    ("adadelta", {}),
    ("ftrl", {}),
    ("adamax", {}),
    ("nadam", {}),
    ("adamax", {"wd": 1e-3, "clip_gradient": 0.01}),
    ("nadam", {"wd": 1e-3, "clip_gradient": 0.01}),
    ("test", {}),
]


def _zoo_data():
    rs = np.random.RandomState(7)
    x = mx.nd.array(rs.randn(16, 12).astype(np.float32))
    y = mx.nd.array(rs.randn(16, 4).astype(np.float32))
    return x, y


def _zoo_net(x):
    mx.random.seed(3)
    net = nn.HybridSequential()
    # weight-only: gluon Trainer applies wd to every Parameter
    # (wd_mult=1.0 default) while the sharded step zeroes bias wd —
    # keep the comparison on the shared semantics
    net.add(nn.Dense(6, in_units=12, use_bias=False),
            nn.Dense(4, in_units=6, use_bias=False))
    net.initialize(mx.init.Xavier())
    net(x)
    return net


@pytest.mark.parametrize("name,params", _ZOO,
                         ids=[f"{n}-{i}" for i, (n, _) in enumerate(_ZOO)])
def test_sharded_trainer_matches_eager_optimizer(name, params):
    from mxnet_tpu import autograd, gluon

    x, y = _zoo_data()
    steps = 3

    # nadam's momentum-schedule state is per-parameter in the compiled
    # rule; the eager reference shares one schedule across params
    # (order-dependent), so compare on a single-parameter net
    def build():
        if name == "nadam":
            mx.random.seed(3)
            net = nn.HybridSequential()
            net.add(nn.Dense(4, in_units=12, use_bias=False))
            net.initialize(mx.init.Xavier())
            net(x)
            return net
        return _zoo_net(x)

    net_s = build()
    tr = ShardedTrainer(net_s, gloss.L2Loss(), name,
                        {"learning_rate": 0.05, **params},
                        mesh=DeviceMesh({"dp": 8}))
    for _ in range(steps):
        tr.step(x, y)
    tr.unshard()
    got = [p.data().asnumpy() for p in net_s.collect_params().values()]

    net_e = build()
    eager = gluon.Trainer(net_e.collect_params(), name,
                          {"learning_rate": 0.05, **params})
    for _ in range(steps):
        with autograd.record():
            loss = gloss.L2Loss()(net_e(x), y).mean()
        loss.backward()
        eager.step(1)
    want = [p.data().asnumpy() for p in net_e.collect_params().values()]

    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-6)


def test_sharded_trainer_sgld_runs():
    """SGLD is stochastic (different rng streams eager vs compiled):
    check the compiled step trains and stays finite."""
    x, y = _zoo_data()
    net = _zoo_net(x)
    tr = ShardedTrainer(net, gloss.L2Loss(), "sgld",
                        {"learning_rate": 0.01},
                        mesh=DeviceMesh({"dp": 8}))
    before = [p.data().asnumpy().copy()
              for p in net.collect_params().values()]
    losses = [float(tr.step(x, y).asscalar()) for _ in range(3)]
    assert all(np.isfinite(losses))
    tr.unshard()
    after = [p.data().asnumpy() for p in net.collect_params().values()]
    assert all(np.isfinite(a).all() for a in after)
    assert any(np.abs(a - b).max() > 0 for a, b in zip(after, before))


def test_sharded_trainer_multi_precision_master_weights():
    """bf16 params + multi_precision=True: fp32 master copy leads each
    state tuple and the trajectory tracks the fp32 run far better than
    a pure-bf16 run after many steps."""
    x, y = _zoo_data()

    def build(dtype):
        net = _zoo_net(x)
        if dtype != "float32":
            net.cast(dtype)
            net(x.astype(dtype))
        return net

    def run(dtype, mp):
        net = build(dtype)
        tr = ShardedTrainer(
            net, gloss.L2Loss(),
            mx.optimizer.SGD(learning_rate=0.05, momentum=0.9,
                             multi_precision=mp),
            mesh=DeviceMesh({"dp": 8}))
        xx = x.astype(dtype) if dtype != "float32" else x
        for _ in range(20):
            tr.step(xx, y)
        if mp:
            assert all(str(per[0].dtype) == "float32"
                       for per in tr._opt_raws)
        tr.unshard()
        return [p.data().asnumpy().astype(np.float32)
                for p in net.collect_params().values()]

    ref = run("float32", False)
    got_mp = run("bfloat16", True)
    got_lp = run("bfloat16", False)
    err_mp = max(np.abs(a - b).max() for a, b in zip(got_mp, ref))
    err_lp = max(np.abs(a - b).max() for a, b in zip(got_lp, ref))
    assert err_mp < err_lp, (err_mp, err_lp)
    assert err_mp < 0.01


def _bf16_trainer(seed=3):
    """Two bias-free bfloat16 layers under Adam with float32 masters."""
    x, y = _zoo_data()
    net = _zoo_net(x)
    net.cast("bfloat16")
    mx.random.seed(seed)
    tr = ShardedTrainer(net, gloss.L2Loss(), "adam",
                        {"learning_rate": 0.01, "multi_precision": True},
                        mesh=DeviceMesh({"dp": 4}))
    return net, tr, x.astype("bfloat16"), y.astype("bfloat16")


def _assert_paired(tr):
    """Every parameter of ``_bf16_trainer`` is bit for bit the cast of its
    master, which the compiled step relies on."""
    import jax.numpy as jnp

    for h, per in zip(tr._train_handles, tr._opt_raws):
        assert str(per[0].dtype) == "float32"
        np.testing.assert_array_equal(
            np.asarray(h._data),
            np.asarray(jnp.asarray(per[0]).astype(h._data.dtype)))


@pytest.mark.parametrize("door", ["set_data", "set_data_then_save",
                                  "resume", "inconsistent_file", "unshard"])
def test_outside_writes_keep_a_bf16_parameter_the_cast_of_its_master(
        door, tmp_path):
    """The compiled step derives a mastered parameter from its master
    alone, so every door that writes one behind the step's back has to
    leave the pair consistent: a value written into the parameter becomes
    its master, a loaded parameter is the cast of the loaded master."""
    from mxnet_tpu.checkpoint import CheckpointManager
    from mxnet_tpu.ndarray import utils as nd_utils

    net, tr, x, y = _bf16_trainer()
    for _ in range(2):
        tr.step(x, y)
    _assert_paired(tr)
    first = next(iter(net.collect_params().values()))
    threes = mx.nd.ones(first.shape) * 3      # far from any Xavier weight
    masters = [np.asarray(per[0]) for per in tr._opt_raws]
    if door == "set_data":
        first.set_data(threes)
        tr.step(x, y)
        _assert_paired(tr)
        # the step went on from the written value, not from the old master
        assert np.abs(np.asarray(tr._opt_raws[0][0]) - 3).max() < 0.05
        assert not np.array_equal(np.asarray(tr._opt_raws[1][0]), masters[1])
    elif door == "set_data_then_save":
        first.set_data(threes)
        tr.save_states(str(tmp_path / "states"))
        net2, tr2, _, _ = _bf16_trainer(seed=4)
        tr2.load_states(str(tmp_path / "states"))
        _assert_paired(tr2)
        np.testing.assert_array_equal(np.asarray(tr2._opt_raws[0][0]), 3)
        np.testing.assert_array_equal(np.asarray(tr2._opt_raws[1][0]),
                                      masters[1])
    elif door == "resume":
        tr.save_checkpoint(CheckpointManager(tmp_path, prefix="ck"), 1)
        net2, tr2, _, _ = _bf16_trainer(seed=4)
        assert tr2.resume(CheckpointManager(tmp_path, prefix="ck"))
        _assert_paired(tr2)
        for _ in range(2):                    # as loaded, and a step on
            for pa, pb in zip(tr._opt_raws, tr2._opt_raws):
                for sa, sb in zip(pa, pb):
                    np.testing.assert_array_equal(np.asarray(sa),
                                                  np.asarray(sb))
            for ha, hb in zip(tr._train_handles, tr2._train_handles):
                np.testing.assert_array_equal(np.asarray(ha._data),
                                              np.asarray(hb._data))
            tr.step(x, y)
            tr2.step(x, y)
    elif door == "inconsistent_file":
        # a file whose parameter is not its master's cast (a trainer of
        # before this rule wrote one after a set_data with no step since)
        payload = tr._state_payload()
        payload["p0"] = threes.astype("bfloat16")
        nd_utils.save(str(tmp_path / "states"), payload)
        tr.load_states(str(tmp_path / "states"))
        _assert_paired(tr)
        np.testing.assert_array_equal(np.asarray(tr._opt_raws[0][0]),
                                      masters[0])
    else:
        tr.unshard()
        _assert_paired(tr)
        tr._state_payload()                   # adopts whatever was written
        for per, m in zip(tr._opt_raws, masters):
            np.testing.assert_array_equal(np.asarray(per[0]), m)


def test_sharded_trainer_optimizer_instance_lr_honored():
    """An Optimizer INSTANCE carries its own lr (and scheduler): the
    compiled step must use it, not the 0.01 default."""
    x, y = _zoo_data()
    net_a = _zoo_net(x)
    tr_a = ShardedTrainer(net_a, gloss.L2Loss(),
                          mx.optimizer.SGD(learning_rate=0.05),
                          mesh=DeviceMesh({"dp": 8}))
    assert tr_a.learning_rate == 0.05
    tr_a.step(x, y)
    tr_a.unshard()
    net_b = _zoo_net(x)
    tr_b = ShardedTrainer(net_b, gloss.L2Loss(), "sgd",
                          {"learning_rate": 0.05},
                          mesh=DeviceMesh({"dp": 8}))
    tr_b.step(x, y)
    tr_b.unshard()
    for pa, pb in zip(net_a.collect_params().values(),
                      net_b.collect_params().values()):
        np.testing.assert_allclose(pa.data().asnumpy(),
                                   pb.data().asnumpy(), rtol=1e-6)
    sched = mx.lr_scheduler.FactorScheduler(step=2, factor=0.5)
    tr_c = ShardedTrainer(_zoo_net(x), gloss.L2Loss(),
                          mx.optimizer.SGD(learning_rate=0.4,
                                           lr_scheduler=sched),
                          mesh=DeviceMesh({"dp": 8}))
    assert tr_c._lr_scheduler is sched
    assert tr_c.learning_rate == 0.4


def test_sharded_trainer_nadam_zero_scalar_state():
    """ZeRO + a scalar state slot (Nadam momentum schedule) + a sharded
    weight: the per-slot sharding must not apply a param-rank spec to
    the rank-0 state."""
    x, y = _zoo_data()
    net = _zoo_net(x)
    tr = ShardedTrainer(net, gloss.L2Loss(), "nadam",
                        {"learning_rate": 0.01},
                        mesh=DeviceMesh({"dp": 4, "tp": 2}), zero=True)
    losses = [float(tr.step(x, y).asscalar()) for _ in range(2)]
    assert all(np.isfinite(losses))


def test_sharded_trainer_lbsgd_warmup_ramp():
    """batch_scale>1 LBSGD: the compiled step must apply the eager
    _get_lbmult lr ramp each step (accumulation itself is accum_steps'
    job). Reference trajectory: compiled SGD-momentum re-fed the ramped
    lr per step."""
    from mxnet_tpu.optimizer import LBSGD

    x, y = _zoo_data()
    base_lr, steps = 0.02, 5
    for strategy, epochs in [("sqrt", 1), ("linear", 0)]:
        mx.random.seed(11)
        net_a = _zoo_net(x)
        with pytest.warns(UserWarning, match="batch_scale"):
            tr_a = ShardedTrainer(
                net_a, gloss.L2Loss(), "lbsgd",
                {"learning_rate": base_lr, "momentum": 0.9,
                 "warmup_strategy": strategy, "batch_scale": 4,
                 "warmup_epochs": epochs, "updates_per_epoch": 3},
                mesh=DeviceMesh({"dp": 8}))
        for _ in range(steps):
            tr_a.step(x, y)
        tr_a.unshard()

        ref_opt = LBSGD(momentum=0.9, warmup_strategy=strategy,
                        batch_scale=4, warmup_epochs=epochs,
                        updates_per_epoch=3)
        mx.random.seed(11)
        net_b = _zoo_net(x)
        tr_b = ShardedTrainer(net_b, gloss.L2Loss(), "sgd",
                              {"learning_rate": base_lr, "momentum": 0.9},
                              mesh=DeviceMesh({"dp": 8}))
        for t in range(1, steps + 1):
            tr_b.set_learning_rate(base_lr * ref_opt._get_lbmult(t))
            tr_b.step(x, y)
        tr_b.unshard()
        for pa, pb in zip(net_a.collect_params().values(),
                          net_b.collect_params().values()):
            np.testing.assert_allclose(pa.data().asnumpy(),
                                       pb.data().asnumpy(),
                                       rtol=1e-5, atol=1e-7)


def test_sharded_trainer_instance_rejects_leftover_params():
    x, _ = _zoo_data()
    net = _zoo_net(x)
    with pytest.raises(ValueError, match="Optimizer instance"):
        ShardedTrainer(net, gloss.L2Loss(),
                       mx.optimizer.SGD(learning_rate=0.05),
                       {"momentum": 0.9}, mesh=DeviceMesh({"dp": 8}))


def test_sharded_trainer_instance_lr_seeds_param_scheduler():
    """A scheduler passed via optimizer_params must be seeded with the
    INSTANCE's lr, not the 0.01 default."""
    x, _ = _zoo_data()
    net = _zoo_net(x)
    sched = mx.lr_scheduler.FactorScheduler(step=100, factor=0.5)
    tr = ShardedTrainer(net, gloss.L2Loss(),
                        mx.optimizer.SGD(learning_rate=0.4),
                        {"lr_scheduler": sched},
                        mesh=DeviceMesh({"dp": 8}))
    assert sched.base_lr == 0.4
    assert tr.learning_rate == 0.4


# ------------------------------- the rng stream around the compiled step ---

def _parent_order(monkeypatch):
    """Make a trainer run the order this one replaced: the host draws
    ``next_key()`` BEFORE the step, the compiled step uses the key it is
    handed as it is, and nothing moves the stream afterwards. Three
    patches around the same trainer code: the step's own ``split`` (the
    first ``jax.random.split`` of a tracer while the step is traced) hands
    back its argument, ``current_key`` draws, ``advance`` does nothing."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import random as mxrand

    real_split = jax.random.split
    traced = []

    def split(key, num=2):
        if not traced and isinstance(key, jax.core.Tracer):
            traced.append(key)
            return jnp.stack([key, key])
        return real_split(key, num)

    monkeypatch.setattr(jax.random, "split", split)
    monkeypatch.setattr(mxrand, "current_key", mxrand.next_key)
    monkeypatch.setattr(mxrand, "advance", lambda: None)
    return traced


@pytest.mark.parametrize("optimizer", ["adam", "sgld"])
def test_rng_stream_is_the_one_next_key_before_the_step_gave(
        optimizer, monkeypatch):
    """The step takes ``split(key)[1]`` of the stream's current key
    itself and the host advances the stream behind it: losses, parameters
    and the global key after every step are bit for bit those of a loop
    that draws ``next_key()`` ahead of the step, with a reseed and eager
    draws in between (dropout in the net; sgld's noise is a stochastic
    update rule)."""
    import jax

    from mxnet_tpu import random as mxrand

    rs = np.random.RandomState(5)
    x = mx.nd.array(rs.randn(16, 12).astype(np.float32))
    y = mx.nd.array(rs.randn(16, 4).astype(np.float32))

    def run():
        np.random.seed(1)
        mx.random.seed(1)
        net = nn.HybridSequential()
        net.add(nn.Dense(32, activation="relu", in_units=12),
                nn.Dropout(0.4), nn.Dense(4, in_units=32))
        net.initialize(mx.init.Xavier())
        tr = ShardedTrainer(net, gloss.L2Loss(), optimizer,
                            {"learning_rate": 0.01},
                            mesh=DeviceMesh({"dp": 2}))
        seen = []
        for i in range(6):
            if i == 3:
                mx.random.seed(77)
            if i in (2, 3, 5):
                seen.append(mx.nd.random.uniform(shape=(3,)).asnumpy())
            seen.append(np.float32(tr.step(x, y).asscalar()))
            seen.extend(p.data().asnumpy()
                        for p in net.collect_params().values())
            seen.append(np.asarray(jax.device_get(mxrand._state.key)))
        return seen

    got = run()
    traced = _parent_order(monkeypatch)
    want = run()
    assert len(traced) == 1   # the reference's step took its key as given
    assert len(got) == len(want)
    for i, (a, b) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(a, b, err_msg=f"item {i}")
    # the masks differ from step to step: the stream did move
    assert not np.array_equal(got[-1], got[len(got) // 2 - 1])


# ------------------------------------- donated inputs let go under the step ---

def test_donated_inputs_are_gone_when_the_step_returns_unless_aliased():
    """The step lets go of its donated inputs before the guard's read:
    once it returns nothing of them is left in distcheck's registry but
    what the caller kept an alias of, and that alias still raises the
    param-named error."""
    from mxnet_tpu.analysis import distcheck

    np.random.seed(0)
    mx.random.seed(0)
    net = _make_net()
    st = ShardedTrainer(net, gloss.L2Loss(), "sgd",
                        {"learning_rate": 0.05, "momentum": 0.9},
                        mesh=DeviceMesh({"dp": 2}))
    rng = np.random.default_rng(1)
    x = mx.nd.array(rng.normal(size=(8, 16)).astype(np.float32))
    y = mx.nd.array(rng.normal(size=(8, 4)).astype(np.float32))
    st.step(x, y)
    level = distcheck.donated_count()
    for _ in range(3):
        st.step(x, y)
        assert distcheck.donated_count() == level
    pname = st._param_names[0]
    stale = mx.nd.NDArray(net.collect_params()[pname].data()._data)
    st.step(x, y)
    assert distcheck.donated_count() == level + 1
    with pytest.raises(distcheck.DonatedBufferError) as ei:
        stale * 2
    assert ei.value.name == pname and "step 5" in str(ei.value)
    del stale, ei
    assert distcheck.donated_count() == level
