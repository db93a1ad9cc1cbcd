"""int8 GEMM with fused dequant+bias+relu — registry family ``int8_gemm``.

PR 14's ``_contrib_quantized_fully_connected`` lowers to a bare
``lax.dot_general`` whose int8 operands scalarize on CPU and whose
dequant/bias epilogue XLA may or may not fuse; this kernel feeds the MXU
int8×int8→int32 tiles directly and applies the per-output-channel
dequantize, bias add and optional relu while the accumulator tile is
still in VMEM — the epilogue never round-trips through HBM.

Contract: ``(qx int8 (M, K), weight int8 (N, K), scale_eff f32 scalar or
(N,)) -> f32 (M, N)`` where ``out = (qx @ weight.T).astype(f32) *
scale_eff [+ bias] [relu]``. ``scale_eff`` is the folded activation ×
weight scale (``s_x * scale`` from the quantized FC op).

Tolerance vs the XLA baseline: BIT-EXACT. The int32 accumulation is
exact in both paths and the f32 epilogue is the same op order
(scale-multiply, then bias add, then max(·, 0)); tests assert ``==``
against the PR 14 fused op output.
"""
from __future__ import annotations

import functools as _functools

import jax
import jax.numpy as jnp

_BN = 128   # output-channel block (lane dim)
_BK = 128   # reduction block


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _gemm_body(x_ref, w_ref, sc_ref, b_ref, o_ref, acc_ref, *, n_kb,
               relu):
    from jax.experimental import pallas as pl

    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # precision pinned: an integer contraction has none to choose, and
    # Mosaic rejects int8 operands under an ambient
    # jax.default_matmul_precision("highest")
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.int32)

    @pl.when(ki == n_kb - 1)
    def _finish():
        out = acc_ref[...].astype(jnp.float32) * sc_ref[...]
        out = out + b_ref[...]
        if relu:
            out = jnp.maximum(out, 0.0)
        o_ref[...] = out


def _kernel(qx, weight, scale_eff, bias=None, relu=False,
            interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = qx.shape
    n = weight.shape[0]
    bm = 128 if m >= 128 else 32  # int8 min sublane tile is 32
    x = _pad_to(_pad_to(qx, 0, bm), 1, _BK)
    w = _pad_to(_pad_to(weight, 0, _BN), 1, _BK)
    mp, kp = x.shape
    np_ = w.shape[0]
    sc = jnp.broadcast_to(
        jnp.asarray(scale_eff, jnp.float32).reshape(-1), (n,))
    sc = _pad_to(sc, 0, _BN).reshape(1, np_)
    if bias is None:
        b = jnp.zeros((1, np_), jnp.float32)
    else:
        b = _pad_to(bias.astype(jnp.float32).reshape(-1), 0,
                    _BN).reshape(1, np_)
    n_kb = kp // _BK
    grid = (mp // bm, np_ // _BN, n_kb)
    out = pl.pallas_call(
        _functools.partial(_gemm_body, n_kb=n_kb, relu=bool(relu)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, _BK), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((_BN, _BK), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((1, _BN), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, _BN), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, _BN), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, _BN), jnp.int32)],
        interpret=interpret,
    )(x, w, sc, b)
    return out[:m, :n]


def _xla(qx, weight, scale_eff, bias=None, relu=False):
    """The PR 14 path verbatim: bare dot_general + unfused epilogue."""
    acc = jax.lax.dot_general(
        qx, weight, (((qx.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * scale_eff
    if bias is not None:
        out = out + bias
    if relu:
        out = jnp.maximum(out, 0.0)
    return out


def _pow2(n):
    p = 1
    while p < n:
        p *= 2
    return p


def _bucket(qx, weight, scale_eff, bias=None, relu=False):
    m, k = qx.shape
    n = weight.shape[0]
    return (f"m{_pow2(m)}_n{_pow2(n)}_k{_pow2(k)}_"
            f"bias{int(bias is not None)}_relu{int(bool(relu))}")


def _supports(qx, weight, scale_eff, bias=None, relu=False):
    if qx.ndim != 2 or weight.ndim != 2:
        return False
    i8 = jnp.dtype(jnp.int8)
    if jnp.dtype(qx.dtype) != i8 or jnp.dtype(weight.dtype) != i8:
        return False
    return qx.shape[1] == weight.shape[1] and qx.size > 0


def _register():
    from . import register_kernel

    register_kernel(
        "int8_gemm", kernel=_kernel, xla=_xla, bucket=_bucket,
        supports=_supports,
        tolerance="bit-exact vs the PR 14 dot_general+epilogue path "
                  "(exact int32 accumulation, same f32 epilogue order)")


_register()
