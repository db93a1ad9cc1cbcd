"""NDArray core semantics tests.

Parity model: tests/python/unittest/test_ndarray.py in the reference —
creation, arithmetic, mutation, slicing, context moves, serialization-ready
properties, async sync points.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.test_utils import assert_almost_equal, default_context


def test_creation():
    a = mx.nd.zeros((3, 4))
    assert a.shape == (3, 4)
    assert a.dtype == np.float32
    assert a.asnumpy().sum() == 0

    import jax

    with jax.enable_x64():
        b = mx.nd.ones((2,), dtype=np.float64)
        assert b.dtype == np.float64
        assert_almost_equal(b, np.ones(2))

    # python lists default to float32 regardless of content (parity:
    # mx.nd.array dtype rule — never int64/float64 from plain lists)
    assert mx.nd.array([1, 2, 3]).dtype == np.float32
    assert mx.nd.array([1.5]).dtype == np.float32
    # numpy sources keep their dtype
    assert mx.nd.array(np.array([1, 2], dtype=np.int32)).dtype == np.int32

    c = mx.nd.full((2, 2), 7)
    assert_almost_equal(c, np.full((2, 2), 7.0))

    d = mx.nd.array([[1, 2], [3, 4]])
    assert_almost_equal(d, np.array([[1, 2], [3, 4]]))

    e = mx.nd.arange(0, 10, 2)
    assert_almost_equal(e, np.arange(0, 10, 2, dtype=np.float32))

    f = mx.nd.eye(3)
    assert_almost_equal(f, np.eye(3))


def test_arithmetic():
    a_np = np.random.rand(3, 4).astype(np.float32)
    b_np = np.random.rand(3, 4).astype(np.float32)
    a, b = mx.nd.array(a_np), mx.nd.array(b_np)
    assert_almost_equal(a + b, a_np + b_np)
    assert_almost_equal(a - b, a_np - b_np)
    assert_almost_equal(a * b, a_np * b_np)
    assert_almost_equal(a / b, a_np / b_np)
    assert_almost_equal(a ** 2, a_np ** 2)
    assert_almost_equal(-a, -a_np)
    assert_almost_equal(abs(a - b), np.abs(a_np - b_np))
    # scalar, including reversed
    assert_almost_equal(a + 1, a_np + 1)
    assert_almost_equal(1 + a, 1 + a_np)
    assert_almost_equal(2 - a, 2 - a_np)
    assert_almost_equal(2 / a, 2 / a_np)
    assert_almost_equal(a % 2, a_np % 2)
    assert_almost_equal(2 ** a, 2 ** a_np)


def test_comparisons():
    a = mx.nd.array([1, 2, 3])
    b = mx.nd.array([3, 2, 1])
    assert_almost_equal(a == b, np.array([0, 1, 0], dtype=np.float32))
    assert_almost_equal(a != b, np.array([1, 0, 1], dtype=np.float32))
    assert_almost_equal(a > b, np.array([0, 0, 1], dtype=np.float32))
    assert_almost_equal(a >= 2, np.array([0, 1, 1], dtype=np.float32))
    assert_almost_equal(a < b, np.array([1, 0, 0], dtype=np.float32))


def test_broadcast():
    a = mx.nd.ones((3, 1))
    b = mx.nd.ones((1, 4))
    assert (a + b).shape == (3, 4)
    c = mx.nd.ones((3, 4))
    assert (c + 1.0).shape == (3, 4)
    assert a.broadcast_to((3, 4)).shape == (3, 4)


def test_mutation():
    a = mx.nd.zeros((3, 4))
    a[:] = 5
    assert a.asnumpy().sum() == 60
    a[1] = 0
    assert a.asnumpy()[1].sum() == 0
    a[0, 2] = 9
    assert a.asnumpy()[0, 2] == 9
    a += 1
    assert a.asnumpy()[1, 0] == 1
    b = mx.nd.ones((3, 4))
    a[:] = b
    assert_almost_equal(a, np.ones((3, 4)))


def test_indexing():
    a_np = np.arange(24, dtype=np.float32).reshape(2, 3, 4)
    a = mx.nd.array(a_np)
    assert_almost_equal(a[1], a_np[1])
    assert_almost_equal(a[0, 1], a_np[0, 1])
    assert_almost_equal(a[:, 1:3], a_np[:, 1:3])
    assert_almost_equal(a[1, 2, 3], a_np[1, 2, 3])
    idx = mx.nd.array([0, 1])
    assert_almost_equal(a[idx], a_np[[0, 1]])


def test_shape_ops():
    a = mx.nd.arange(0, 24).reshape(2, 3, 4)
    assert a.reshape(6, 4).shape == (6, 4)
    assert a.reshape((-1,)).shape == (24,)
    assert a.transpose().shape == (4, 3, 2)
    assert a.transpose((1, 0, 2)).shape == (3, 2, 4)
    assert a.flatten().shape == (2, 12)
    assert a.expand_dims(0).shape == (1, 2, 3, 4)
    assert a.expand_dims(0).squeeze(0).shape == (2, 3, 4)
    assert a.swapaxes(0, 2).shape == (4, 3, 2)
    assert mx.nd.concat(a, a, dim=1).shape == (2, 6, 4)
    assert mx.nd.stack(a, a, axis=0).shape == (2, 2, 3, 4)
    outs = a.split(3, axis=1)
    assert len(outs) == 3 and outs[0].shape == (2, 1, 4)


def test_reduce():
    a_np = np.random.rand(3, 4, 5).astype(np.float32)
    a = mx.nd.array(a_np)
    assert_almost_equal(a.sum(), a_np.sum())
    assert_almost_equal(a.sum(axis=1), a_np.sum(axis=1))
    assert_almost_equal(a.mean(axis=(0, 2)), a_np.mean(axis=(0, 2)))
    assert_almost_equal(a.max(axis=0), a_np.max(axis=0))
    assert_almost_equal(a.min(), a_np.min())
    assert_almost_equal(a.argmax(axis=2), np.argmax(a_np, axis=2))
    assert_almost_equal(a.norm(), np.linalg.norm(a_np.reshape(-1)))


def test_dot():
    a_np = np.random.rand(4, 5).astype(np.float32)
    b_np = np.random.rand(5, 3).astype(np.float32)
    assert_almost_equal(mx.nd.dot(mx.nd.array(a_np), mx.nd.array(b_np)),
                        a_np @ b_np)
    assert_almost_equal(
        mx.nd.dot(mx.nd.array(a_np), mx.nd.array(b_np.T), transpose_b=True),
        a_np @ b_np)


def test_astype_copy():
    a = mx.nd.ones((2, 2))
    b = a.astype(np.float16)
    assert b.dtype == np.float16
    c = a.copy()
    c[:] = 0
    assert a.asnumpy().sum() == 4  # copy is deep


def test_scalar_conversion():
    a = mx.nd.array([3.5])
    assert float(a) == 3.5
    assert a.asscalar() == pytest.approx(3.5)
    with pytest.raises(ValueError):
        bool(mx.nd.ones((2,)))


def test_context_moves():
    ctx = default_context()
    a = mx.nd.ones((2, 2), ctx=ctx)
    assert a.context.device_type in ("cpu", "tpu", "gpu")
    b = a.as_in_context(mx.cpu(0))
    assert b.context.device_type == "cpu"
    c = mx.nd.zeros((2, 2))
    a.copyto(c)
    assert c.asnumpy().sum() == 4


def test_waitall_and_sync():
    a = mx.nd.ones((16, 16))
    for _ in range(5):
        a = a * 1.0 + 0.0
    a.wait_to_read()
    mx.nd.waitall()
    assert a.asnumpy().sum() == 256


def test_take_one_hot():
    a = mx.nd.array([[1, 2], [3, 4], [5, 6]])
    idx = mx.nd.array([0, 2])
    assert_almost_equal(a.take(idx), np.array([[1, 2], [5, 6]]))
    oh = mx.nd.array([1, 0, 2]).one_hot(3)
    assert_almost_equal(oh, np.eye(3)[[1, 0, 2]])


def test_iter_len():
    a = mx.nd.arange(0, 6).reshape(3, 2)
    assert len(a) == 3
    rows = list(a)
    assert len(rows) == 3 and rows[2].shape == (2,)


def test_int64_index_posture():
    """Large-tensor (int64 index) posture. The reference gates
    >2^31-element tensors behind MXNET_INT64_TENSOR_SIZE and tests them
    nightly (tests/nightly/test_large_array.py). Here the gate is JAX
    x64: with it OFF (production default) int64 inputs store as int32 —
    fine below 2^31 elements; inside `jax.experimental.enable_x64()`
    int64 indices/labels are preserved end-to-end, which is the
    large-tensor mode. This pins both halves of that contract."""
    import numpy as np

    import mxnet_tpu as mx

    # default runtime: int64 narrows to int32 (documented posture)
    idx32 = mx.nd.array(np.array([0, 2, 1], np.int64), dtype="int64")
    assert str(idx32.dtype) == "int32"
    data = mx.nd.array(np.arange(12).reshape(4, 3).astype("f"))
    out = mx.nd.take(data, idx32)
    np.testing.assert_array_equal(out.asnumpy(),
                                  data.asnumpy()[[0, 2, 1]])

    # large-tensor mode: x64 scope preserves int64 end-to-end
    import tempfile

    import jax

    with jax.enable_x64():
        idx = mx.nd.array(np.array([0, 2, 1], np.int64), dtype="int64")
        assert str(idx.dtype) == "int64"
        out = mx.nd.take(data, idx)
        np.testing.assert_array_equal(out.asnumpy(),
                                      data.asnumpy()[[0, 2, 1]])
        with tempfile.NamedTemporaryFile(suffix=".npz") as f:
            mx.nd.save(f.name, {"i": idx})
            back = mx.nd.load(f.name)["i"]
        assert str(back.dtype) == "int64"
        np.testing.assert_array_equal(back.asnumpy(), idx.asnumpy())
