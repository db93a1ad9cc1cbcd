"""Exact GELU (``ops/math.py`` ``exact_gelu``, ``LeakyReLU``'s
``act_type="gelu"``): its value is ``jax.nn.gelu(x, approximate=False)`` bit
for bit; under differentiation the forward keeps ``erfc`` and the backward
reads it, no further from the float64 slope than jax's own rule; through the
tape, hybridized and not, and through both registrations of ``LeakyReLU``."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops import math as mops
from mxnet_tpu.ops import registry

EDGES = np.array([0.0, -0.0, np.inf, -np.inf, np.nan], np.float32)
CALLS = {
    "exact_gelu": mops.exact_gelu,
    "nn_registration": lambda x: registry.get("LeakyReLU").fn(
        x, act_type="gelu"),
    "math_registration": lambda x: mops.leaky_relu_elementwise.fn(
        x, act_type="gelu"),
}


def _values(dtype, n=4001):
    return jnp.asarray(np.concatenate(
        [np.linspace(-6.0, 6.0, n, dtype=np.float32), EDGES]), dtype)


def _jax_gelu(x):
    return jax.nn.gelu(x, approximate=False)


def _bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32, 8: np.uint64}[a.itemsize])


def _slope64(x):
    x = np.asarray(x, np.float64)
    cdf = 0.5 * np.vectorize(math.erfc)(-x * math.sqrt(0.5))
    return cdf + x * np.exp(-0.5 * x * x) / math.sqrt(2.0 * math.pi)


@pytest.mark.parametrize("call", sorted(CALLS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "float16"])
def test_value_is_jax_exact_gelu_bit_for_bit(call, dtype):
    x = _values(dtype)
    # like with like: inside a jit XLA keeps a narrow type's intermediates
    # wide, so jitted and eager jax.nn.gelu differ in bfloat16 themselves
    for got, want in ((CALLS[call](x), _jax_gelu(x)),
                      (jax.jit(CALLS[call])(x), jax.jit(_jax_gelu)(x))):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_differentiated_forward_gives_the_same_value(dtype):
    """The forward rule (``erfc`` kept behind the barrier) returns what
    the undifferentiated call returns."""
    x = _values(dtype)
    got, _ = jax.vjp(mops.exact_gelu, x)
    np.testing.assert_array_equal(_bits(got), _bits(_jax_gelu(x)))
    got = jax.jit(lambda v: jax.vjp(mops.exact_gelu, v)[0])(x)
    np.testing.assert_array_equal(_bits(got), _bits(jax.jit(_jax_gelu)(x)))


@pytest.mark.parametrize("call", sorted(CALLS))
def test_float32_gradient_is_jax_grad(call):
    x = _values("float32")[:-len(EDGES)]
    cot = jnp.asarray(np.random.default_rng(0).standard_normal(
        x.shape, dtype=np.float32))
    want = jax.vjp(_jax_gelu, x)[1](cot)[0]
    for f in (CALLS[call], jax.jit(CALLS[call])):
        got = jax.vjp(f, x)[1](cot)[0]
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    np.testing.assert_allclose(jax.grad(lambda v: CALLS[call](v).sum())(x),
                               _slope64(x), rtol=0, atol=1e-6)


@pytest.mark.parametrize("dtype", ["bfloat16", "float16"])
def test_narrow_gradient_is_no_further_from_float64_than_jax(dtype):
    """The rule takes the slope in float32 from the stored ``h`` and
    ``erfc`` and rounds once; jax's takes every term in ``h``'s type."""
    x = _values(dtype)[:-len(EDGES)]
    exact = _slope64(np.asarray(x, np.float64))
    ours = np.asarray(jax.grad(lambda v: mops.exact_gelu(v).astype(
        jnp.float32).sum())(x), np.float64)
    jaxs = np.asarray(jax.grad(lambda v: _jax_gelu(v).astype(
        jnp.float32).sum())(x), np.float64)
    assert np.abs(ours - exact).max() <= np.abs(jaxs - exact).max()
    assert np.abs(ours - exact).mean() <= np.abs(jaxs - exact).mean()
    # one rounding of a slope in [-0.13, 1.13], erfc's own rounding inside
    eps = float(jnp.finfo(dtype).eps)
    assert np.abs(ours - exact).max() <= 1.5 * eps


def test_float64_stays_float64():
    with jax.enable_x64():
        x = jnp.linspace(-6.0, 6.0, 1001, dtype=jnp.float64)
        out, pull = jax.vjp(mops.exact_gelu, x)
        (grad,) = pull(jnp.ones_like(x))
        assert out.dtype == grad.dtype == jnp.float64
        np.testing.assert_array_equal(_bits(out), _bits(_jax_gelu(x)))
        np.testing.assert_allclose(grad, _slope64(x), rtol=0, atol=1e-14)


def test_the_forward_keeps_erfc_behind_a_barrier():
    """What the backward is handed: ``h`` and ``erfc`` in ``h``'s type, one
    ``optimization_barrier`` in the differentiated program and none in the
    plain one, one ``erfc`` in both."""
    x = jnp.ones((4, 8), jnp.bfloat16)
    plain = str(jax.make_jaxpr(mops.exact_gelu)(x))
    grad = str(jax.make_jaxpr(jax.grad(
        lambda v: mops.exact_gelu(v).astype(jnp.float32).sum()))(x))
    assert plain.count("optimization_barrier") == 0
    assert grad.count("optimization_barrier") == 1
    assert plain.count("erfc") == grad.count("erfc") == 1
    _, (h, e) = mops._exact_gelu_fwd(x)
    assert h.dtype == e.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        _bits(e), _bits(jax.lax.erfc(-x * jnp.bfloat16(np.sqrt(0.5)))))


@pytest.mark.parametrize("hybridize", [False, True],
                         ids=["imperative", "hybridized"])
@pytest.mark.parametrize("layer", ["GELU", "npx.gelu", "LeakyReLU"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_through_the_tape(layer, hybridize, dtype):
    class Net(nn.HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.act = nn.GELU()

        def hybrid_forward(self, F, x):
            if layer == "GELU":
                return self.act(x)
            if layer == "npx.gelu":
                return mx.npx.gelu(x)
            return F.LeakyReLU(x, act_type="gelu")

    net = Net()
    net.initialize()
    if hybridize:
        net.hybridize()
    values = _values(dtype)[:-len(EDGES)].reshape(-1, 1)[::8]
    array = mx.np.array if layer == "npx.gelu" else mx.nd.array
    x = array(np.asarray(values, np.float32)).astype(dtype)
    x.attach_grad()
    with autograd.record():
        y = net(x)
        loss = (y.astype("float32") * 3.0).sum()
    loss.backward()
    # the tape differentiates an op eagerly and runs it jitted otherwise
    assert any(np.array_equal(_bits(y._data), _bits(f(values)))
               for f in (_jax_gelu, jax.jit(_jax_gelu)))
    want = 3.0 * _slope64(np.asarray(values, np.float64))
    tol = 1e-5 if dtype == "float32" else 3 * 2.0 ** -7
    np.testing.assert_allclose(np.asarray(x.grad._data, np.float64), want,
                               rtol=0, atol=tol)
    assert x.grad._data.dtype == jnp.dtype(dtype)
