"""Operator correctness tests.

Parity model: tests/python/unittest/test_operator.py — forward vs numpy
oracle, backward vs central finite differences (check_numeric_gradient),
shapes/dtypes, multi-output ops.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd as ag
from mxnet_tpu.test_utils import (assert_almost_equal, check_numeric_gradient,
                                  simple_forward)


def test_elemwise_unary_forward():
    x = np.random.rand(3, 4).astype(np.float32) + 0.5
    cases = {
        "sqrt": np.sqrt, "square": np.square, "exp": np.exp, "log": np.log,
        "abs": np.abs, "sign": np.sign, "floor": np.floor, "ceil": np.ceil,
        "round": np.round, "rsqrt": lambda a: 1 / np.sqrt(a),
        "reciprocal": lambda a: 1 / a, "cbrt": np.cbrt,
        "log2": np.log2, "log10": np.log10, "log1p": np.log1p,
        "expm1": np.expm1, "sin": np.sin, "cos": np.cos, "tan": np.tan,
        "sinh": np.sinh, "cosh": np.cosh, "tanh": np.tanh,
        "arcsin": lambda a: np.arcsin(a - 0.5), "arctan": np.arctan,
        "sigmoid": lambda a: 1 / (1 + np.exp(-a)),
        "relu": lambda a: np.maximum(a, 0),
        "softsign": lambda a: a / (1 + np.abs(a)),
        "erf": None, "gamma": None, "gammaln": None, "erfinv": None,
    }
    for name, ref in cases.items():
        if name == "arcsin":
            out = simple_forward(name, x - 0.5)
            assert_almost_equal(out, ref(x), names=(name, "numpy"))
            continue
        out = simple_forward(name, x)
        if ref is not None:
            assert_almost_equal(out, ref(x), names=(name, "numpy"))
        else:
            assert out.shape == x.shape


def test_elemwise_binary_forward():
    a = np.random.rand(3, 4).astype(np.float32) + 0.5
    b = np.random.rand(3, 4).astype(np.float32) + 0.5
    for name, ref in {
        "elemwise_add": np.add, "elemwise_sub": np.subtract,
        "elemwise_mul": np.multiply, "elemwise_div": np.divide,
        "broadcast_maximum": np.maximum, "broadcast_minimum": np.minimum,
        "broadcast_hypot": np.hypot, "broadcast_power": np.power,
    }.items():
        assert_almost_equal(simple_forward(name, a, b), ref(a, b),
                            names=(name, "numpy"))


def test_numeric_gradients():
    x = np.random.rand(2, 3) + 0.5
    for op in ["sqrt", "exp", "log", "sigmoid", "tanh", "square"]:
        check_numeric_gradient(op, [x])
    check_numeric_gradient("broadcast_mul", [x, np.random.rand(2, 3) + 0.5])
    check_numeric_gradient("dot", [np.random.rand(2, 3), np.random.rand(3, 2)])


def test_fully_connected():
    data = np.random.rand(4, 10).astype(np.float32)
    w = np.random.rand(5, 10).astype(np.float32)
    b = np.random.rand(5).astype(np.float32)
    out = simple_forward("FullyConnected", data, w, b, num_hidden=5)
    assert_almost_equal(out, data @ w.T + b, rtol=1e-3, atol=1e-4)
    out = simple_forward("FullyConnected", data, w, num_hidden=5, no_bias=True)
    assert_almost_equal(out, data @ w.T, rtol=1e-3, atol=1e-4)


def test_convolution_shapes():
    # NCHW conv, kernel 3x3, pad 1: same spatial dims
    data = np.random.rand(2, 3, 8, 8).astype(np.float32)
    w = np.random.rand(4, 3, 3, 3).astype(np.float32)
    b = np.zeros(4, np.float32)
    out = simple_forward("Convolution", data, w, b, kernel=(3, 3), pad=(1, 1),
                         num_filter=4)
    assert out.shape == (2, 4, 8, 8)
    out = simple_forward("Convolution", data, w, b, kernel=(3, 3), stride=(2, 2),
                         num_filter=4)
    assert out.shape == (2, 4, 3, 3)


def test_convolution_vs_naive():
    # tiny conv checked against explicit loops
    data = np.random.rand(1, 1, 4, 4).astype(np.float32)
    w = np.random.rand(1, 1, 2, 2).astype(np.float32)
    out = simple_forward("Convolution", data, w, np.zeros(1, np.float32),
                         kernel=(2, 2), num_filter=1)
    ref = np.zeros((1, 1, 3, 3), np.float32)
    for i in range(3):
        for j in range(3):
            ref[0, 0, i, j] = (data[0, 0, i:i + 2, j:j + 2] * w[0, 0]).sum()
    assert_almost_equal(out, ref, rtol=1e-4, atol=1e-5)


def test_pooling():
    data = np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4)
    out = simple_forward("Pooling", data, kernel=(2, 2), stride=(2, 2),
                         pool_type="max")
    assert_almost_equal(out, np.array([[[[5, 7], [13, 15]]]], np.float32))
    out = simple_forward("Pooling", data, kernel=(2, 2), stride=(2, 2),
                         pool_type="avg")
    assert_almost_equal(out, np.array([[[[2.5, 4.5], [10.5, 12.5]]]], np.float32))
    out = simple_forward("Pooling", data, global_pool=True, pool_type="avg",
                         kernel=(2, 2))
    assert out.shape == (1, 1, 1, 1)
    assert out[0, 0, 0, 0] == pytest.approx(7.5)


def test_softmax():
    x = np.random.rand(3, 5).astype(np.float32)
    out = simple_forward("softmax", x)
    e = np.exp(x - x.max(axis=-1, keepdims=True))
    assert_almost_equal(out, e / e.sum(axis=-1, keepdims=True))
    assert_almost_equal(simple_forward("log_softmax", x),
                        np.log(e / e.sum(axis=-1, keepdims=True)),
                        rtol=1e-4, atol=1e-5)


def test_batchnorm_inference_and_training():
    x = np.random.rand(4, 3, 5, 5).astype(np.float32)
    gamma = np.random.rand(3).astype(np.float32)
    beta = np.random.rand(3).astype(np.float32)
    mean = x.mean(axis=(0, 2, 3))
    var = x.var(axis=(0, 2, 3))
    out = simple_forward("BatchNorm", x, gamma, beta, mean, var,
                         use_global_stats=True, fix_gamma=False)
    if isinstance(out, tuple):
        out = out[0]
    ref = (x - mean[None, :, None, None]) / np.sqrt(var[None, :, None, None] + 1e-3)
    ref = ref * gamma[None, :, None, None] + beta[None, :, None, None]
    assert_almost_equal(out, ref, rtol=1e-3, atol=1e-4)


def test_layernorm():
    x = np.random.rand(4, 10).astype(np.float32)
    g = np.ones(10, np.float32)
    b = np.zeros(10, np.float32)
    out = simple_forward("LayerNorm", x, g, b)
    if isinstance(out, tuple):
        out = out[0]
    mu = x.mean(-1, keepdims=True)
    sd = np.sqrt(x.var(-1, keepdims=True) + 1e-5)
    assert_almost_equal(out, (x - mu) / sd, rtol=1e-3, atol=1e-4)


def test_activation():
    x = np.random.randn(3, 4).astype(np.float32)
    for act, ref in {
        "relu": lambda a: np.maximum(a, 0),
        "sigmoid": lambda a: 1 / (1 + np.exp(-a)),
        "tanh": np.tanh,
        "softrelu": lambda a: np.log1p(np.exp(a)),
    }.items():
        assert_almost_equal(simple_forward("Activation", x, act_type=act),
                            ref(x), names=(act, "numpy"))


def test_embedding():
    w = np.random.rand(10, 4).astype(np.float32)
    idx = np.array([1, 3, 5], np.float32)
    out = simple_forward("Embedding", idx, w, input_dim=10, output_dim=4)
    assert_almost_equal(out, w[[1, 3, 5]])


def test_transpose_slice_ops():
    x = np.arange(24, dtype=np.float32).reshape(2, 3, 4)
    assert_almost_equal(simple_forward("transpose", x, axes=(2, 0, 1)),
                        x.transpose(2, 0, 1))
    assert_almost_equal(
        simple_forward("slice", x, begin=(0, 1, 0), end=(2, 3, 2)),
        x[0:2, 1:3, 0:2])
    assert_almost_equal(
        simple_forward("slice_axis", x, axis=1, begin=1, end=3), x[:, 1:3])
    assert_almost_equal(simple_forward("flip", x, axis=1), x[:, ::-1])
    assert_almost_equal(simple_forward("tile", x, reps=(1, 2, 1)),
                        np.tile(x, (1, 2, 1)))


def test_where_clip_maximum():
    cond = np.array([1, 0, 1], np.float32)
    a = np.array([1, 2, 3], np.float32)
    b = np.array([10, 20, 30], np.float32)
    assert_almost_equal(simple_forward("where", cond, a, b),
                        np.where(cond > 0, a, b))
    x = np.array([-2, 0.5, 3], np.float32)
    assert_almost_equal(simple_forward("clip", x, a_min=-1, a_max=1),
                        np.clip(x, -1, 1))


def test_topk_sort():
    x = np.array([[3, 1, 2], [0, 5, 4]], np.float32)
    out = simple_forward("topk", x, k=2, ret_typ="value")
    assert_almost_equal(out, np.array([[3, 2], [5, 4]], np.float32))
    assert_almost_equal(simple_forward("sort", x), np.sort(x))
    assert_almost_equal(simple_forward("argsort", x), np.argsort(x))


def test_gather_scatter():
    x = np.random.rand(3, 4).astype(np.float32)
    idx = np.array([[0, 2], [1, 3]], np.float32)
    out = simple_forward("gather_nd", x, idx)
    assert_almost_equal(out, x[[0, 2], [1, 3]])


def test_batch_dot():
    a = np.random.rand(4, 2, 3).astype(np.float32)
    b = np.random.rand(4, 3, 5).astype(np.float32)
    assert_almost_equal(simple_forward("batch_dot", a, b),
                        np.einsum("bij,bjk->bik", a, b), rtol=1e-3, atol=1e-4)


def test_sequence_mask():
    x = np.ones((4, 2, 3), np.float32)  # (seq, batch, feat)
    lens = np.array([2, 4], np.float32)
    out = simple_forward("SequenceMask", x, lens, use_sequence_length=True,
                         value=0.0)
    assert out[2:, 0].sum() == 0
    assert out[:, 1].sum() == 12


def test_optimizer_ops():
    w = np.random.rand(5).astype(np.float32)
    g = np.random.rand(5).astype(np.float32)
    out = simple_forward("sgd_update", w, g, lr=0.1, wd=0.0)
    assert_almost_equal(out, w - 0.1 * g)
    # momentum
    mom = np.zeros(5, np.float32)
    out_w, out_m = simple_forward("sgd_mom_update", w, g, mom, lr=0.1,
                                  momentum=0.9, wd=0.0)
    assert_almost_equal(out_m, -0.1 * g)
    assert_almost_equal(out_w, w - 0.1 * g)
    # adam
    m = np.zeros(5, np.float32)
    v = np.zeros(5, np.float32)
    out = simple_forward("adam_update", w, g, m, v, lr=0.01, beta1=0.9,
                         beta2=0.999, epsilon=1e-8, wd=0.0)
    assert len(out) == 3


def test_dropout_modes():
    x = mx.nd.ones((100, 100))
    key = mx.nd.NDArray(mx.random.next_key())
    out = mx.nd.invoke("Dropout", x, key, p=0.5, training=True)
    if isinstance(out, tuple):
        out = out[0]
    # prediction: identity without a key
    ident = mx.nd.invoke("Dropout", x, p=0.5, training=False)
    assert ident.asnumpy().sum() == 100 * 100
    # roughly half zeroed, survivors scaled by 2
    frac = (out.asnumpy() == 0).mean()
    assert 0.3 < frac < 0.7


def _dropout_raw(x, key, **kw):
    """The op's own function over jax arrays (what a compiled graph
    traces), training on."""
    from mxnet_tpu.ops import registry

    return registry.get("Dropout").fn(x, key, training=True, **kw)


@pytest.mark.parametrize("p", [0.1, 0.5, 0.9])
def test_dropout_keeps_its_share(p):
    """Bernoulli(1 - p) an element: the kept share of 1e6 elements lies
    within four binomial standard deviations of ``keep``."""
    import jax
    import jax.numpy as jnp

    n, keep = 10 ** 6, 1.0 - p
    out = _dropout_raw(jnp.ones((1000, 1000), jnp.float32),
                       jax.random.PRNGKey(7), p=p)
    kept = int((np.asarray(out) != 0).sum())
    assert abs(kept - n * keep) < 4 * np.sqrt(n * keep * p), kept


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_dropout_kept_values_are_x_over_keep(dtype):
    """Kept values are ``x / keep`` computed in the input's type, the rest
    exact zeros, and the result keeps the type."""
    import jax
    import jax.numpy as jnp

    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (64, 96), dtype=np.float32) + 3.0, dtype)
    out = _dropout_raw(x, jax.random.PRNGKey(1), p=0.1)
    assert out.dtype == x.dtype
    want = np.asarray((x / 0.9).astype(jnp.float32))
    got = np.asarray(out.astype(jnp.float32))
    kept = got != 0
    assert 0.8 < kept.mean() < 0.97
    np.testing.assert_array_equal(got[kept], want[kept])


def test_dropout_backward_uses_the_forward_mask():
    """The cotangent is ``1 / keep`` where the output was kept and zero
    exactly where it was dropped."""
    import jax
    import jax.numpy as jnp

    x = jnp.full((128, 64), 2.0, jnp.float32)
    key = jax.random.PRNGKey(3)
    out, pull = jax.vjp(lambda t: _dropout_raw(t, key, p=0.25), x)
    grad, = pull(jnp.ones_like(out))
    kept = np.asarray(out) != 0
    np.testing.assert_array_equal(np.asarray(grad) != 0, kept)
    np.testing.assert_array_equal(np.asarray(grad)[kept],
                                  np.float32(1.0) / np.float32(0.75))
    # and through the imperative front end's tape
    xs = mx.nd.ones((64, 64)) * 2
    xs.attach_grad()
    with ag.record():
        ys = mx.nd.Dropout(xs, p=0.5)
    ys.backward()
    np.testing.assert_array_equal(xs.grad.asnumpy() == 0, ys.asnumpy() == 0)
    assert set(np.unique(xs.grad.asnumpy())) == {0.0, 2.0}


def test_dropout_same_key_same_mask_another_key_another():
    import jax
    import jax.numpy as jnp

    x = jnp.ones((256, 256), jnp.float32)
    a = np.asarray(_dropout_raw(x, jax.random.PRNGKey(5), p=0.5))
    b = np.asarray(_dropout_raw(x, jax.random.PRNGKey(5), p=0.5))
    c = np.asarray(_dropout_raw(x, jax.random.PRNGKey(6), p=0.5))
    np.testing.assert_array_equal(a, b)
    assert 0.4 < ((a != 0) != (c != 0)).mean() < 0.6
    # reproducible from the global seed
    outs = []
    for _ in range(2):
        mx.random.seed(11)
        with ag.train_mode():
            outs.append(mx.nd.Dropout(mx.nd.ones((32, 32)), p=0.5).asnumpy())
    np.testing.assert_array_equal(*outs)


def test_dropout_axes_broadcast_one_mask():
    """``axes=(1,)``: one draw a (row, column), shared along axis 1."""
    import jax
    import jax.numpy as jnp

    x = jnp.ones((64, 16, 32), jnp.float32)
    out = np.asarray(_dropout_raw(x, jax.random.PRNGKey(2), p=0.5,
                                  axes=(1,)))
    assert (out == out[:, :1, :]).all()
    assert 0.4 < (out[:, 0, :] != 0).mean() < 0.6


@pytest.mark.parametrize("case", ["not_training", "p_zero", "no_key"])
def test_dropout_is_the_identity(case):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import registry

    fn = registry.get("Dropout").fn
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (8, 8), dtype=np.float32))
    key = jax.random.PRNGKey(0)
    out = {"not_training": lambda: fn(x, key, p=0.5, training=False),
           "p_zero": lambda: fn(x, key, p=0.0, training=True),
           "no_key": lambda: fn(x, None, p=0.5, training=True)}[case]()
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


@pytest.mark.parametrize("how", ["jit", "scan"])
def test_dropout_mask_is_the_eager_one(how):
    """A key gives the mask it gives eagerly under ``jit`` and for a
    microbatch of a ``lax.scan`` (gradient accumulation draws one key a
    microbatch)."""
    import jax
    import jax.numpy as jnp

    x = jnp.ones((4, 48, 64), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(9), 4)
    eager = np.stack([np.asarray(_dropout_raw(x[i], keys[i], p=0.3))
                      for i in range(4)])
    if how == "jit":
        got = np.stack([np.asarray(jax.jit(
            lambda t, k: _dropout_raw(t, k, p=0.3))(x[i], keys[i]))
            for i in range(4)])
    else:
        _, got = jax.lax.scan(
            lambda c, tk: (c, _dropout_raw(tk[0], tk[1], p=0.3)), 0,
            (x, keys))
    np.testing.assert_array_equal(np.asarray(got), eager)
    assert not (eager[0] == eager[1]).all()


def test_dropout_vmap_over_keys_runs():
    import jax
    import jax.numpy as jnp

    x = jnp.ones((3, 32, 32), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    out = np.asarray(jax.vmap(
        lambda t, k: _dropout_raw(t, k, p=0.5))(x, keys))
    assert out.shape == (3, 32, 32)
    assert set(np.unique(out)) == {0.0, 2.0}
    assert 0.4 < (out != 0).mean() < 0.6
    assert not (out[0] == out[1]).all()


def test_random_ops():
    mx.random.seed(42)
    u = mx.nd.random.uniform(0.0, 1.0, shape=(1000,))
    arr = u.asnumpy()
    assert arr.min() >= 0 and arr.max() <= 1
    assert 0.4 < arr.mean() < 0.6
    n = mx.nd.random.normal(0.0, 1.0, shape=(2000,))
    assert abs(n.asnumpy().mean()) < 0.2
    # seeding reproduces streams (parity: mx.random.seed)
    mx.random.seed(7)
    a = mx.nd.random.uniform(shape=(5,)).asnumpy()
    mx.random.seed(7)
    b = mx.nd.random.uniform(shape=(5,)).asnumpy()
    assert (a == b).all()


def test_multi_device_consistency():
    """parity: check_consistency across ctxs (test_utils.py:1546)."""
    from mxnet_tpu.test_utils import check_consistency

    check_consistency(lambda a, b: mx.nd.dot(a, b), [(3, 4), (4, 5)])
    check_consistency(lambda a: a.sigmoid().sum() * 2, [(6, 6)])


def test_legacy_tail_ops():
    """batch_take/diag/split_v2/UpSampling/Crop/relu6/fill_element_0index/
    unravel+ravel/multi_sum_sq/digamma (parity: indexing_op.cc,
    diag_op.cc, matrix_op.cc split_v2, upsampling.cc, crop.cc)."""
    a = mx.nd.array(np.arange(12, dtype="float32").reshape(3, 4))
    assert mx.nd.batch_take(a, mx.nd.array([1, 2, 0])).asnumpy().tolist() \
        == [1.0, 6.0, 8.0]
    np.testing.assert_allclose(
        mx.nd.diag(mx.nd.array([1.0, 2.0])).asnumpy(),
        np.diag([1.0, 2.0]))
    p = mx.nd.split_v2(a, sections=2, axis=1)
    assert p[0].shape == (3, 2) and p[1].shape == (3, 2)
    p2 = mx.nd.split_v2(a, indices=(1, 3), axis=1)
    assert [x.shape[1] for x in p2] == [1, 2, 1]
    u = mx.nd.UpSampling(mx.nd.array(np.arange(4, dtype="f").reshape(
        1, 1, 2, 2)), scale=2)
    assert u.shape == (1, 1, 4, 4)
    assert u.asnumpy()[0, 0, 0, 1] == 0.0  # nearest: repeated
    c = mx.nd.Crop(mx.nd.ones((1, 1, 8, 8)), h_w=(4, 4), center_crop=True)
    assert c.shape == (1, 1, 4, 4)
    assert mx.nd.relu6(mx.nd.array([-1.0, 8.0])).asnumpy().tolist() \
        == [0.0, 6.0]
    fl = mx.nd.fill_element_0index(
        a.copy(), mx.nd.array([9.0, 9.0, 9.0]), mx.nd.array([0, 1, 2]))
    assert fl.asnumpy()[1, 1] == 9
    ui = mx.nd.unravel_index(mx.nd.array([5, 7], dtype="int32"),
                             shape=(3, 4))
    assert ui.asnumpy().tolist() == [[1, 1], [1, 3]]
    ri = mx.nd.ravel_multi_index(mx.nd.array([[1, 1], [1, 3]],
                                             dtype="int32"), shape=(3, 4))
    assert ri.asnumpy().tolist() == [5, 7]
    s2 = mx.nd.multi_sum_sq(mx.nd.array([3.0, 4.0]), mx.nd.array([1.0]))
    assert s2.shape == (2,)  # ONE output vector (contrib/multi_sum_sq.cc)
    assert s2.asnumpy().tolist() == [25.0, 1.0]
    # multi-input nearest upsampling: inputs scaled to a common size, then
    # channel-concatenated (upsampling.cc multi_input_mode='concat')
    um = mx.nd.UpSampling(mx.nd.ones((1, 1, 2, 2)), mx.nd.ones((1, 2, 4, 4)),
                          scale=2, num_args=2)
    assert um.shape == (1, 3, 4, 4)
    assert float(mx.nd.digamma(mx.nd.array([1.0])).asscalar()) < 0


def test_multi_tensor_optimizer_ops():
    """multi_sgd/preloaded/multi_lamb/adamw families (parity:
    optimizer_op.cc MultiSGDUpdate, contrib/adamw.cc, multi_lamb.cc)."""
    rs = np.random.RandomState(0)
    w1, g1 = rs.rand(3).astype("f"), rs.rand(3).astype("f")
    w2, g2 = rs.rand(2).astype("f"), rs.rand(2).astype("f")
    o = mx.nd.multi_sgd_update(mx.nd.array(w1), mx.nd.array(g1),
                               mx.nd.array(w2), mx.nd.array(g2),
                               lrs=(0.1, 0.2), wds=(0.0, 0.0),
                               num_weights=2)
    np.testing.assert_allclose(o[0].asnumpy(), w1 - 0.1 * g1, rtol=1e-5)
    np.testing.assert_allclose(o[1].asnumpy(), w2 - 0.2 * g2, rtol=1e-5)
    op = mx.nd.preloaded_multi_sgd_update(
        mx.nd.array(w1), mx.nd.array(g1), mx.nd.array(w2), mx.nd.array(g2),
        mx.nd.array([0.1, 0.2]), mx.nd.array([0.0, 0.0]), num_weights=2)
    np.testing.assert_allclose(op[0].asnumpy(), o[0].asnumpy(), rtol=1e-6)

    # adamw: loss-scale skip contract — non-finite rescale = no update
    w = mx.nd.array(rs.rand(4).astype("f"))
    g = mx.nd.array(rs.rand(4).astype("f"))
    m, v = mx.nd.zeros((4,)), mx.nd.zeros((4,))
    upd = mx.nd.adamw_update(w, g, m, v, mx.nd.array([1.0]), lr=0.1)
    assert not np.allclose(upd[0].asnumpy(), w.asnumpy())
    skip = mx.nd.adamw_update(w, g, m, v, mx.nd.array([np.inf]), lr=0.1)
    np.testing.assert_allclose(skip[0].asnumpy(), w.asnumpy())

    ml = mx.nd.multi_lamb_update(
        mx.nd.array(w1), mx.nd.array(g1), mx.nd.zeros((3,)),
        mx.nd.zeros((3,)), learning_rates=(0.01,), wds=(0.0,),
        step_count=(1,), num_tensors=1)
    assert len(ml) == 3 and not np.allclose(ml[0].asnumpy(), w1)

    # all_finite / reset_arrays / amp_multicast
    assert float(mx.nd.all_finite(mx.nd.array([1.0, 2.0])).asscalar()) == 1
    assert float(mx.nd.all_finite(
        mx.nd.array([1.0, np.inf])).asscalar()) == 0
    z = mx.nd.reset_arrays(mx.nd.ones((2,)), mx.nd.ones((3,)),
                           num_arrays=2)
    assert z[0].asnumpy().sum() == 0 and z[1].asnumpy().sum() == 0
    outs = mx.nd.amp_multicast(mx.nd.ones((2,)).astype("float16"),
                               mx.nd.ones((2,)), num_outputs=2)
    assert str(outs[0].dtype) == "float32"


def test_quantized_op_tail():
    """quantized act/flatten/concat/elemwise/pooling + asym quantize + KL
    calibration (parity: src/operator/quantization/)."""
    rs = np.random.RandomState(1)
    x = rs.randn(2, 4).astype("f")
    q, mn, mxr = mx.nd._contrib_quantize_v2(mx.nd.array(x))
    scale = max(abs(float(mn.asscalar())), abs(float(mxr.asscalar()))) / 127
    deq = mx.nd._contrib_dequantize(q, mn, mxr)
    np.testing.assert_allclose(deq.asnumpy(), x, atol=scale * 1.01)
    a = mx.nd._contrib_quantized_act(q, mn, mxr)
    assert int(a[0].asnumpy().min()) >= 0
    f = mx.nd._contrib_quantized_flatten(q, mn, mxr)
    assert f[0].shape == (2, 4)
    cc = mx.nd._contrib_quantized_concat(q, q, mn, mxr, mn, mxr, dim=1)
    assert cc[0].shape == (2, 8)
    ea = mx.nd._contrib_quantized_elemwise_add(q, q, mn, mxr, mn, mxr)
    np.testing.assert_allclose(
        mx.nd._contrib_dequantize(ea[0], ea[1], ea[2]).asnumpy(),
        2 * x, atol=4 * scale)
    qa = mx.nd._contrib_quantize_asym(mx.nd.array(x))
    assert str(qa[0].dtype) == "int8"
    h, e = mx.nd._histogram(mx.nd.array(x), bin_cnt=32, range=(-3, 3))
    lo, hi = mx.nd._contrib_calibrate_entropy(h, e)
    assert float(hi.asscalar()) > 0 > float(lo.asscalar())


def test_transformer_interleaved_matmuls():
    """parity: contrib/transformer.cc interleaved attention matmuls vs
    einsum oracle."""
    rs = np.random.RandomState(2)
    seq, b, h, d = 5, 2, 3, 4
    qkv = rs.randn(seq, b, 3 * h * d).astype("f")
    att = mx.nd._contrib_interleaved_matmul_selfatt_qk(mx.nd.array(qkv),
                                                       heads=h)
    x = qkv.reshape(seq, b, h, 3, d)
    q, k, v = x[:, :, :, 0], x[:, :, :, 1], x[:, :, :, 2]
    ref = np.einsum("qbhd,kbhd->bhqk", q / np.sqrt(d), k) \
        .reshape(b * h, seq, seq)
    np.testing.assert_allclose(att.asnumpy(), ref, atol=1e-5)
    out = mx.nd._contrib_interleaved_matmul_selfatt_valatt(
        mx.nd.array(qkv), att, heads=h)
    ref_out = np.einsum("bhqk,kbhd->qbhd", ref.reshape(b, h, seq, seq),
                        v).reshape(seq, b, h * d)
    np.testing.assert_allclose(out.asnumpy(), ref_out, atol=1e-5)


def test_box_codec_and_matching():
    anchors = np.array([[[0., 0., 2., 2.], [1., 1., 3., 3.]]], "f")
    dec = mx.nd._contrib_box_decode(mx.nd.array(np.zeros((1, 2, 4), "f")),
                                    mx.nd.array(anchors))
    np.testing.assert_allclose(dec.asnumpy(), anchors, atol=1e-5)
    data = np.array([[[0.9, 0.1], [0.8, 0.75]]], "f")
    rowm, colm = mx.nd._contrib_bipartite_matching(mx.nd.array(data),
                                                   threshold=0.0)
    assert rowm.asnumpy().tolist() == [[0.0, 1.0]]
    assert colm.asnumpy().tolist() == [[0.0, 1.0]]


def test_npi_tail_and_image_ops():
    rs = np.random.RandomState(3)
    np.testing.assert_allclose(mx.nd._npi_hanning(M=5).asnumpy(),
                               np.hanning(5), atol=1e-6)
    assert mx.nd._npi_delete(mx.nd.array([1., 2., 3.]),
                             obj=1).asnumpy().tolist() == [1., 3.]
    parts = mx.nd._npi_hsplit(mx.nd.ones((2, 6)), indices_or_sections=3)
    assert len(parts) == 3 and parts[0].shape == (2, 2)
    assert mx.nd._npi_ediff1d(mx.nd.array([1., 4., 9.]),
                              to_begin=0.0).asnumpy().tolist() == [0., 3., 5.]
    img = mx.nd.array(rs.randint(0, 255, (4, 6, 3)).astype("uint8"))
    t = mx.nd._image_to_tensor(img)
    assert t.shape == (3, 4, 6) and float(t.asnumpy().max()) <= 1.0
    assert mx.nd._image_resize(img, size=(3, 2)).shape == (2, 3, 3)
    assert mx.nd._image_crop(img, x=1, y=1, width=3,
                             height=2).shape == (2, 3, 3)
    # legacy creation + sparse_retain
    assert mx.nd.invoke("_arange", start=0.0, stop=3.0,
                        repeat=2).asnumpy().tolist() == [0, 0, 1, 1, 2, 2]
    sr = mx.nd._sparse_retain(
        mx.nd.array(np.arange(6, dtype="f").reshape(3, 2)),
        mx.nd.array([0, 2]))
    assert sr.asnumpy()[1].tolist() == [0, 0]
    a = rs.rand(3, 3).astype("f")
    a = a @ a.T + 3 * np.eye(3, dtype="f")
    np.testing.assert_allclose(
        mx.nd._linalg_det(mx.nd.array(a)).asnumpy(),
        np.linalg.det(a), rtol=1e-4)


# ------------------------------------------------------ parameter schema ---
# SURVEY §5.6: dmlc::Parameter equivalent (exemplar declaration:
# reference src/operator/control_flow.cc:35-59) — reflected per-op param
# schemas with validation, string coercion, and schema dumps.

def test_schema_unknown_param_structured_error():
    from mxnet_tpu.ops.schema import OpParamError

    x = mx.nd.ones((2, 3))
    with pytest.raises(OpParamError, match="'softmax'.*'axsi'.*axis"):
        mx.nd.invoke("softmax", x, axsi=1)
    # symbolic path: error at COMPOSE time, before any execution
    data = mx.sym.Variable("data")
    with pytest.raises(OpParamError, match="unknown parameter"):
        mx.sym.invoke("softmax", data, axsi=1)


def test_schema_string_coercion():
    """dmlc-style parsing: symbol-JSON/C-ABI string params become typed."""
    x = mx.nd.random.uniform(shape=(1, 3, 8, 8))
    w = mx.nd.random.uniform(shape=(4, 3, 3, 3))
    out = mx.nd.invoke("Convolution", x, w, kernel="(3, 3)",
                       num_filter="4", no_bias="True")
    assert out.shape == (1, 4, 6, 6)


def test_schema_choices_and_range():
    from mxnet_tpu.ops.schema import OpParamError

    x = mx.nd.ones((2, 3))
    with pytest.raises(OpParamError, match="expected one of"):
        mx.nd.invoke("Activation", x, act_type="gelu_bogus")
    with pytest.raises(OpParamError, match="above maximum"):
        mx.nd.invoke("Dropout", x, p=1.5)


def test_schema_dump():
    from mxnet_tpu.ops import registry

    schemas = registry.op_schemas()
    assert len(schemas) == len(registry.list_ops())
    conv = schemas["Convolution"]
    assert "data" in conv["inputs"]
    names = {p["name"]: p for p in conv["params"]}
    assert names["num_filter"]["default"] == 1
    act = {p["name"]: p for p in schemas["Activation"]["params"]}
    assert "relu" in act["act_type"]["choices"]


def test_schema_type_enforcement_and_override_check():
    from mxnet_tpu.ops.schema import OpParamError, OpSchema

    x = mx.nd.random.uniform(shape=(1, 3, 8, 8))
    w = mx.nd.random.uniform(shape=(4, 3, 3, 3))
    with pytest.raises(OpParamError, match="expected tuple"):
        mx.nd.invoke("Convolution", x, w, kernel=3, num_filter=4)
    with pytest.raises(OpParamError, match="expected int"):
        mx.nd.invoke("Convolution", x, w, kernel=(3, 3), num_filter="(4,)")
    # typo'd enrichment keys must fail loudly, not mint new params
    with pytest.raises(ValueError, match="does not match"):
        OpSchema.from_fn("Pooling",
                         lambda data, pool_type="max": data,
                         {"pool_typ": {"choices": ("max",)}})


def test_schema_optional_arrays_are_inputs():
    from mxnet_tpu.ops import registry

    conv = registry.get("Convolution").schema.describe()
    assert "bias" in conv["inputs"]
    assert "bias" not in [p["name"] for p in conv["params"]]
    drop = registry.get("Dropout").schema.describe()
    assert "key" in drop["inputs"]
