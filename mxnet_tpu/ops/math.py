"""Elementwise / scalar / broadcast / logic ops.

Parity target: `src/operator/tensor/elemwise_*.{h,cc,cu}` and
`src/operator/mshadow_op.h` in the reference (~36k LoC of templated CPU/GPU
kernels + registration macros `tensor/elemwise_unary_op.h:810-873`).

TPU-native: each op is one jax.numpy/lax expression; XLA fuses chains of
these into single kernels (replacing the reference's NVRTC pointwise-fusion
pass, `src/executor/pointwise_fusion_pass.cc`). Binary `elemwise_*` ops
require identical shapes (as in the reference); `broadcast_*` ops use numpy
broadcasting. Scalar variants bake the scalar into the executable just like
the reference's `_plus_scalar(scalar=...)` parameterised kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as _np

from .registry import register

# ---------------------------------------------------------------- unary ----

_UNARY = {
    "negative": jnp.negative,
    "abs": jnp.abs,
    "sign": jnp.sign,
    "round": jnp.round,
    "rint": jnp.rint,
    "ceil": jnp.ceil,
    "floor": jnp.floor,
    "trunc": jnp.trunc,
    "fix": jnp.trunc,
    "square": jnp.square,
    "sqrt": jnp.sqrt,
    "rsqrt": jax.lax.rsqrt,
    "cbrt": jnp.cbrt,
    "exp": jnp.exp,
    "expm1": jnp.expm1,
    "log": jnp.log,
    "log10": jnp.log10,
    "log2": jnp.log2,
    "log1p": jnp.log1p,
    "sin": jnp.sin,
    "cos": jnp.cos,
    "tan": jnp.tan,
    "arcsin": jnp.arcsin,
    "arccos": jnp.arccos,
    "arctan": jnp.arctan,
    "sinh": jnp.sinh,
    "cosh": jnp.cosh,
    "tanh": jnp.tanh,
    "arcsinh": jnp.arcsinh,
    "arccosh": jnp.arccosh,
    "arctanh": jnp.arctanh,
    "erf": jax.lax.erf,
    "erfinv": jax.lax.erf_inv,
    "gamma": lambda x: jnp.exp(jax.lax.lgamma(x)),
    "gammaln": jax.lax.lgamma,
    "sigmoid": jax.nn.sigmoid,
    "softsign": jax.nn.soft_sign,
    "relu": jax.nn.relu,
    "reciprocal": jnp.reciprocal,
    "logical_not": jnp.logical_not,
}

for _name, _fn in _UNARY.items():
    register(_name)(_fn)

register("copy", aliases=("identity", "_copy"))(lambda x: jnp.asarray(x))
register("zeros_like")(jnp.zeros_like)
register("ones_like")(jnp.ones_like)


@jax.custom_vjp
def _exact_gelu(x):
    return jax.nn.gelu(x, approximate=False)


def _exact_gelu_fwd(x):
    # jax.nn.gelu's own expression, term for term, in x's type
    e = jax.lax.optimization_barrier(
        jax.lax.erfc(-x * _np.sqrt(0.5).astype(x.dtype)))
    return 0.5 * x * e, (x, e)


def _exact_gelu_bwd(res, g):
    x, e = res
    wide = jnp.promote_types(x.dtype, jnp.float32)
    h = x.astype(wide)
    slope = 0.5 * e.astype(wide) \
        + h * jnp.exp(-0.5 * h * h) * (1.0 / _np.sqrt(2.0 * _np.pi))
    return ((g.astype(wide) * slope).astype(x.dtype),)


_exact_gelu.defvjp(_exact_gelu_fwd, _exact_gelu_bwd)


def exact_gelu(x):
    """Exact GELU, the erf form: ``jax.nn.gelu(x, approximate=False)`` =
    ``0.5 * x * erfc(-x * sqrt(0.5))``, bit for bit, in every dtype; the
    one function behind ``LeakyReLU(act_type="gelu")``, ``gluon.nn.GELU``
    and ``npx.gelu``.

    Under reverse-mode differentiation the forward hands the backward
    ``h`` and ``e = erfc(-h * sqrt(0.5))`` in ``h``'s type, ``e`` behind
    ``jax.lax.optimization_barrier``, and the backward is ``g * (0.5 * e +
    h * exp(-h * h / 2) / sqrt(2 pi))`` taken in float32 (float64 for a
    float64 ``h``) from the stored values and rounded once, where jax's own
    rule takes the same terms in ``h``'s type. Outside differentiation
    nothing is kept and the program is jax's. A ``jax.custom_vjp``:
    forward mode (``jax.jvp``) over this op is gone; nothing in the package
    uses it.

    Why: XLA expands ``erfc`` over float32 into three branch polynomials,
    an ``exponential``, two ``divide``s and five ``select``s (~140 vector
    operations an element), prices that chain as a cheap producer and
    COPIES it into every fusion that wants ``gelu(h)`` or its slope. In
    ``bert_base``'s step (``bf16[32,384,3072]``, 12 layers; compiled for
    the described v5e) that is ``ffn2``'s product, ``ffn2.weight``'s dW
    product and twice the dX fusion: 48 ``exponential`` instructions over
    ``f32[32,384,3072]``, four a layer. A ``custom_vjp`` alone changes
    nothing (XLA fuses the forward chain back into every consumer); with
    the barrier ``e`` is written once by ``ffn1``'s product and read by the
    rest: 24, two a layer (``erfc``'s, and the density's in the dX fusion;
    ``tests/test_tpu_compile.py`` holds a two-layer block to it).

    Device ms from a trace (``benchmark/opperf.py --gelu-sweep``; my chip
    run, PR 35; TPU v5e), bfloat16, batch 32 x 384, 768 / 3,072. ``ffn2``'s
    product alone, forward only: 0.3044 without an activation, **0.8202**
    with ``jax.nn.gelu`` on its operand. Forward + backward of
    ``LayerNorm(x + ffn2(f(ffn1(x))))`` alone in a jit (without the residual
    and LayerNorm behind it the compiler keeps ``gelu(h)`` itself and
    copies nothing), as ``jax.nn.gelu`` / this form / ``gelu(h)`` kept
    behind the barrier beside ``e``:

    ==========================  ========  =========  ==================
    ms                          jax       kept erfc  kept erfc + value
    ==========================  ========  =========  ==================
    all device operations       3.4834    2.4491     2.4779
    ``exponential``s            4         2          2
    ``ffn1``'s product          0.3374    0.6871     0.7062
    ``ffn2``'s product          0.8376    0.3185     0.3191
    ``ffn2.weight``'s dW        0.8049    0.3359     0.3359
    dX with ``ffn1``'s bias     0.7787    0.3840     0.3870
    ==========================  ========  =========  ==================

    In ``bert_base_train_s384`` (same call, one seed, parent / this form /
    value kept too): 387.99 / 446.02 / 438.97 samples/s, device busy 76.59
    / 66.01 / 67.10 ms a step, ``peak_hbm_gib`` 5.37 / 6.15 / 6.89: keeping
    ``gelu(h)`` too saves ``ffn2``'s product 0.05 ms a layer (0.319 against
    0.369) and loses more than that to the copies and slices 0.85 GB more
    of residuals bring; ``e`` alone won and is the one form (six pairs
    sharing seeds: medians 392.41 / 448.87 samples/s). A layer's four
    fusions in that cell, parent / this form: ``ffn2``'s product 0.824 /
    0.371 ms, ``ffn2.weight``'s dW 0.695 / 0.326, the dX fusion 0.755 /
    0.319, ``ffn1``'s product 0.334 / 0.688: what is left is ``erfc``
    itself in ``ffn1``'s epilogue and the density's ``exp`` in the dX
    fusion."""
    with jax.named_scope("act.gelu"):
        return _exact_gelu(x)


leaky_relu_elementwise = register("LeakyReLU")(
    lambda x, act_type="leaky", slope=0.25: {
        "leaky": lambda: jnp.where(x >= 0, x, slope * x),
        "elu": lambda: jnp.where(x >= 0, x, slope * jnp.expm1(x)),
        "selu": lambda: 1.0507009873554805 * jnp.where(
            x >= 0, x, 1.6732632423543772 * jnp.expm1(x)),
        "gelu": lambda: exact_gelu(x),
    }[act_type]()
)
register("hard_sigmoid")(lambda x, alpha=0.2, beta=0.5: jnp.clip(alpha * x + beta, 0, 1))
register("softplus")(jax.nn.softplus)
register("degrees")(jnp.degrees)
register("radians")(jnp.radians)


@register("clip")
def _clip(x, a_min=None, a_max=None):
    """Clamp every element into [a_min, a_max] (parity: clip,
    matrix_op.cc)."""
    return jnp.clip(x, a_min, a_max)


@register("Cast", aliases=("cast",))
def _cast(x, dtype="float32"):
    """Cast to the given dtype (parity: Cast, elemwise_unary_op.cc)."""
    from ..base import canonical_dtype

    return x.astype(canonical_dtype(dtype))


@register("amp_cast")
def _amp_cast(x, dtype="bfloat16"):
    """AMP-inserted cast (identity gradient; parity: amp_cast,
    amp_cast.cc)."""
    from ..base import canonical_dtype

    return x.astype(canonical_dtype(dtype))


# --------------------------------------------------------------- binary ----

def _samedim(fn):
    def wrapped(lhs, rhs):
        if lhs.shape != rhs.shape:
            raise ValueError(
                f"elemwise op requires identical shapes, got {lhs.shape} vs "
                f"{rhs.shape}; use the broadcast_* variant")
        return fn(lhs, rhs)

    return wrapped


_BINARY = {
    "add": jnp.add,
    "sub": jnp.subtract,
    "mul": jnp.multiply,
    "div": jnp.divide,
    "mod": jnp.mod,
    "power": jnp.power,
    "maximum": jnp.maximum,
    "minimum": jnp.minimum,
    "hypot": jnp.hypot,
    "equal": jnp.equal,
    "not_equal": jnp.not_equal,
    "greater": jnp.greater,
    "greater_equal": jnp.greater_equal,
    "lesser": jnp.less,
    "lesser_equal": jnp.less_equal,
    "logical_and": jnp.logical_and,
    "logical_or": jnp.logical_or,
    "logical_xor": jnp.logical_xor,
    "arctan2": jnp.arctan2,
}

for _name, _fn in _BINARY.items():
    register(f"elemwise_{_name}", aliases=(f"_{_name}",))(_samedim(_fn))
    register(f"broadcast_{_name}")(_fn)

register("broadcast_like")(lambda x, like: jnp.broadcast_to(x, like.shape))
register("broadcast_to")(lambda x, shape=(): jnp.broadcast_to(x, tuple(shape)))
register("broadcast_axis")(
    lambda x, axis=(), size=(): jnp.broadcast_to(
        x,
        tuple(
            (size[list(axis).index(i)] if i in tuple(axis) else s)
            for i, s in enumerate(x.shape)
        ),
    )
)


# --------------------------------------------------------------- scalar ----

_SCALAR = {
    "_plus_scalar": lambda x, scalar=0.0: x + scalar,
    "_minus_scalar": lambda x, scalar=0.0: x - scalar,
    "_rminus_scalar": lambda x, scalar=0.0: scalar - x,
    "_mul_scalar": lambda x, scalar=1.0: x * scalar,
    "_div_scalar": lambda x, scalar=1.0: x / scalar,
    "_rdiv_scalar": lambda x, scalar=1.0: scalar / x,
    "_mod_scalar": lambda x, scalar=1.0: jnp.mod(x, scalar),
    "_rmod_scalar": lambda x, scalar=1.0: jnp.mod(scalar, x),
    "_power_scalar": lambda x, scalar=1.0: jnp.power(x, scalar),
    "_rpower_scalar": lambda x, scalar=1.0: jnp.power(scalar, x),
    "_maximum_scalar": lambda x, scalar=0.0: jnp.maximum(x, scalar),
    "_minimum_scalar": lambda x, scalar=0.0: jnp.minimum(x, scalar),
    "_equal_scalar": lambda x, scalar=0.0: (x == scalar).astype(x.dtype),
    "_not_equal_scalar": lambda x, scalar=0.0: (x != scalar).astype(x.dtype),
    "_greater_scalar": lambda x, scalar=0.0: (x > scalar).astype(x.dtype),
    "_greater_equal_scalar": lambda x, scalar=0.0: (x >= scalar).astype(x.dtype),
    "_lesser_scalar": lambda x, scalar=0.0: (x < scalar).astype(x.dtype),
    "_lesser_equal_scalar": lambda x, scalar=0.0: (x <= scalar).astype(x.dtype),
}

for _name, _fn in _SCALAR.items():
    register(_name)(_fn)
