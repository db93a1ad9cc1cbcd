"""2-bit gradient compression — registry families ``twobit_compress``
and ``twobit_decompress``.

PR 13's kvstore gradient compression runs as unfused XLA soup: the
error-feedback add, two threshold compares, the int8 select and the
residual subtract each stream the gradient through HBM. The compress
kernel does the whole pipeline — ``g = grad + residual``, threshold-
quantize to codes {-1, 0, +1}, write the new residual — in ONE pass
over (rows, 128) tiles; decompress is the matching fused scale-cast of
the (summed) code tensor back to gradient dtype.

Contracts (mirroring ``kvstore/kvstore.py`` bitwise):

  compress:   (grad f32, residual f32, threshold) -> (codes int8,
              new_residual f32) with codes = sign(g) where |g| >= thr
  decompress: (codes intN, threshold) -> codes.astype(f32) * thr
              (the all-reduced code SUM decompresses the same way, so
              values outside {-1,0,+1} are in-contract)

Tolerance vs the XLA baseline: BIT-EXACT for f32 gradients — identical
compare/select/multiply sequence; tests assert ``==``.
"""
from __future__ import annotations

import functools as _functools

import jax
import jax.numpy as jnp

_LANES = 128
_BLOCK_ROWS = 256


def _pad_rows(n):
    rows = -(-n // _LANES)
    return -(-rows // _BLOCK_ROWS) * _BLOCK_ROWS


def _to_tiles(x):
    flat = x.reshape(-1)
    rows = _pad_rows(flat.size)
    pad = rows * _LANES - flat.size
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(rows, _LANES)


def _from_tiles(t, shape, size):
    return t.reshape(-1)[:size].reshape(shape)


def _compress_body(g_ref, r_ref, codes_ref, res_ref, *, thr):
    g = g_ref[...] + r_ref[...]
    # select in the gradient's own dtype and narrow once at the store:
    # Mosaic cannot relayout the f32 compare's (8, 128) mask onto int8's
    # (32, 128) tiles, so an int8 select driven by it does not compile.
    # {-1, 0, +1} are exact in both types — the bits match kvstore's.
    codes = jnp.where(g >= thr, 1.0,
                      jnp.where(g <= -thr, -1.0, 0.0)).astype(g.dtype)
    codes_ref[...] = codes.astype(jnp.int8)
    res_ref[...] = g - codes * thr


def _decompress_body(c_ref, o_ref, *, thr):
    o_ref[...] = c_ref[...].astype(o_ref.dtype) * thr


def _kernel_compress(grad, residual, thr, interpret=False):
    from jax.experimental import pallas as pl

    shape, size = grad.shape, grad.size
    g = _to_tiles(grad)
    r = _to_tiles(residual)
    rows = g.shape[0]
    blk = pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0))
    codes, res = pl.pallas_call(
        _functools.partial(_compress_body, thr=float(thr)),
        grid=(rows // _BLOCK_ROWS,),
        in_specs=[blk, blk],
        out_specs=[blk, blk],
        out_shape=[jax.ShapeDtypeStruct((rows, _LANES), jnp.int8),
                   jax.ShapeDtypeStruct((rows, _LANES), grad.dtype)],
        interpret=interpret,
    )(g, r)
    return (_from_tiles(codes, shape, size),
            _from_tiles(res, shape, size))


def _xla_compress(grad, residual, thr):
    """PR 13 kvstore._quantize math verbatim."""
    g = grad + residual
    one = jnp.int8(1)
    codes = jnp.where(g >= thr, one,
                      jnp.where(g <= -thr, -one, jnp.int8(0)))
    return codes, g - codes.astype(g.dtype) * thr


def _kernel_decompress(codes, thr, dtype=jnp.float32, interpret=False):
    from jax.experimental import pallas as pl

    shape, size = codes.shape, codes.size
    c = _to_tiles(codes)
    rows = c.shape[0]
    blk = pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        _functools.partial(_decompress_body, thr=float(thr)),
        grid=(rows // _BLOCK_ROWS,),
        in_specs=[blk],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.dtype(dtype)),
        interpret=interpret,
    )(c)
    return _from_tiles(out, shape, size)


def _xla_decompress(codes, thr, dtype=jnp.float32):
    return codes.astype(jnp.dtype(dtype)) * thr


def _size_bucket(x):
    n = x.size if hasattr(x, "size") else 1
    p = 1
    while p < max(n, 1):
        p *= 2
    return p


def _bucket_compress(grad, residual, thr):
    return f"n{_size_bucket(grad)}_{jnp.dtype(grad.dtype).name}"


def _bucket_decompress(codes, thr, dtype=jnp.float32):
    return f"n{_size_bucket(codes)}_{jnp.dtype(dtype).name}"


def _supports_compress(grad, residual, thr):
    return (jnp.dtype(grad.dtype) == jnp.dtype(jnp.float32)
            and grad.shape == residual.shape and grad.size > 0)


def _supports_decompress(codes, thr, dtype=jnp.float32):
    return codes.size > 0


def _register():
    from . import register_kernel

    register_kernel(
        "twobit_compress", kernel=_kernel_compress, xla=_xla_compress,
        bucket=_bucket_compress, supports=_supports_compress,
        tolerance="bit-exact vs kvstore._quantize (same compare/select/"
                  "multiply order)")
    register_kernel(
        "twobit_decompress", kernel=_kernel_decompress,
        xla=_xla_decompress, bucket=_bucket_decompress,
        supports=_supports_decompress,
        tolerance="bit-exact (single f32 multiply)")


_register()
