"""Selective scan: the recurrence of a Mamba-1 state-space layer (registry
families ``selective_scan`` and ``selective_scan_bwd``).

    h_t = exp(dt_t * A) * h_(t-1) + (dt_t * x_t) B_t^T      (channels, state)
    y_t = h_t C_t + D * x_t

over ``x, dt (batch, positions, channels)``, ``A (channels, state)``,
``B, C (batch, positions, state)`` and ``D (channels,)``: ``dt`` is the step
AFTER its softplus and ``A`` the negative decay rates, both float32, as is
the state. The state of one layer at 4,096 positions and 5,120 channels of
16 states is 1.34 GB if written out, and the backward wants it again; so
every form here walks the sequence in chunks of ``CHUNK`` positions,
keeps the state of one chunk at a time and saves it at chunk boundaries
only (21 MB), and the backward recomputes inside a chunk.

The Pallas kernels hold the state ``(state, channels)`` in VMEM, channels
along the lanes and ``LANES`` of them a program: the grid is (batch, chunk,
channel block), both of the last two sequential, so that a chunk's ``B``
and ``C`` are laid out once for all its channel blocks. A step needs
``B_t[n]`` in every lane of sublane ``n``; the chunk's ``(positions,
state)`` block has the state along the lanes, and two small MXU products
with constant selectors turn it into ``(positions * state, 128)`` tiles,
exactly (a selector holds ones and zeros). The sum over the state is a
sublane reduction a step; the backward's sums over the channels (dB, dC)
are kept as 128-lane partial sums a step and reduced, transposed to a
lane-major row, by one more MXU product a chunk. There is no matmul in
the recurrence itself: the VPU and the ``exp`` unit do the work, one
``exp`` a (position, channel, state).

The XLA form is the same chunking as two nested ``lax.scan``s with
``jax.checkpoint`` around a chunk: the fallback, what the CPU tests run
beside the interpreter, and the XLA side of both families.

Tolerance: float32 inputs agree with a step-by-step loop to rtol 1e-5 /
atol 1e-5 forward and 1e-4 backward (the kernel sums the state in another
order); bf16 ``x``, ``B``, ``C`` are widened on entry and the output is
rounded once (tests/test_selective_scan.py).
"""
from __future__ import annotations

import functools as _functools

import jax
import jax.numpy as jnp

__all__ = ["selective_scan_reference", "selective_scan_chunked",
           "selective_scan_forward", "selective_scan_backward_kernel",
           "CHUNK", "LANES"]

# Positions a chunk (a multiple of 8) and channels a program. The backward
# keeps a chunk's states and decays in VMEM, 2 x (CHUNK + 1) x state x LANES float32: 4.2
# MiB at 16 states, beside ~2 MiB of tiles, inside the 16 MiB of scoped
# VMEM a v5e gives a call.
CHUNK = 64
LANES = 512


def _step(a, d):
    """One position of the recurrence over a batch: state (batch, channels,
    state), inputs ``(x_t, dt_t (batch, channels), B_t, C_t (batch,
    state))`` -> (state, y_t)."""
    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = jnp.exp(dt_t[..., None] * a) * h \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, (h * c_t[:, None, :]).sum(-1) + d * x_t

    return step


def selective_scan_reference(x, dt, a, b, c, d):
    """The recurrence as a plain ``lax.scan`` over time that writes every
    state's output and keeps, for the backward, every state: the oracle of
    the tests at small sizes."""
    f32 = jnp.float32
    h0 = jnp.zeros((x.shape[0], x.shape[2], a.shape[1]), f32)
    _, y = jax.lax.scan(_step(a, d), h0, tuple(
        t.astype(f32).swapaxes(0, 1) for t in (x, dt, b, c)))
    return y.swapaxes(0, 1).astype(x.dtype)


def _pad_positions(*arrays):
    """Every array (batch, positions, .) padded with zeros to whole chunks:
    a step of ``dt = 0`` leaves the state as it is."""
    s = arrays[0].shape[1]
    pad = -s % CHUNK
    if not pad:
        return arrays
    return tuple(jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in arrays)


def selective_scan_chunked(x, dt, a, b, c, d):
    """The XLA form: a ``lax.scan`` over chunks of ``CHUNK`` positions whose
    body, a ``lax.scan`` over the chunk's steps, is a ``jax.checkpoint``:
    differentiated, it keeps the state at chunk boundaries and recomputes
    the states inside a chunk."""
    f32 = jnp.float32
    bsz, s, dch = x.shape
    chunk = CHUNK
    xp, dtp, bp, cp = _pad_positions(x, dt, b, c)
    n_c = xp.shape[1] // chunk

    def chunks(t):  # (batch, positions, w) -> (chunk, step, batch, w)
        return t.astype(f32).reshape(bsz, n_c, chunk, -1).transpose(
            1, 2, 0, 3)

    @jax.checkpoint
    def one_chunk(h, inp):
        return jax.lax.scan(_step(a, d), h, inp)

    h0 = jnp.zeros((bsz, dch, a.shape[1]), f32)
    _, y = jax.lax.scan(one_chunk, h0,
                        tuple(chunks(t) for t in (xp, dtp, bp, cp)))
    y = y.transpose(2, 0, 1, 3).reshape(bsz, n_c * chunk, dch)
    return y[:, :s].astype(x.dtype)


# ---- the kernels -------------------------------------------------------

_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T


def _exact_dot(a, b, dims):
    """An MXU product one of whose operands holds ones and zeros: exact for
    bf16 values as they are, and for float32 ones at the highest
    precision (three bf16 parts a value)."""
    return jax.lax.dot_general(
        a, b, dims, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
        else jax.lax.Precision.DEFAULT)


def _lane_tiles(m):
    """``(positions, state)`` with the state along the lanes -> ``(positions
    * state, 128)`` float32 whose row ``t * state + n`` holds ``m[t, n]`` in
    every lane: ``pick`` repeats row ``t`` ``state`` times, ``own`` keeps
    column ``n`` of repeat ``n``, and a product with ones spreads it over
    the lanes."""
    steps, n = m.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (steps * n, steps), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (steps * n, steps), 1)
    pick = (rows // n == cols).astype(m.dtype)
    picked = _exact_dot(pick, m, _NN)                     # (steps * n, n)
    own = (jax.lax.broadcasted_iota(jnp.int32, (steps * n, n), 0) % n
           == jax.lax.broadcasted_iota(jnp.int32, (steps * n, n), 1))
    kept = jnp.where(own, picked, 0.0).astype(m.dtype)
    return _exact_dot(kept, jnp.ones((n, 128), m.dtype), _NN)


def _steps(n, body, carry, by):
    """``carry = body(t, carry)`` for ``t`` in ``range(n)``, ``by`` steps
    to a loop iteration (Mosaic unrolls a ``fori_loop`` whole or not at
    all)."""
    def some(i, carry):
        for j in range(by):
            carry = body(i * by + j, carry)
        return carry

    return jax.lax.fori_loop(0, n // by, some, carry)


def _across(tile, lanes):
    """A ``(state, 128)`` tile repeated along the lanes to ``(state,
    lanes)``: the same registers, named again."""
    return tile if lanes == 128 else jnp.concatenate(
        [tile] * (lanes // 128), axis=1)


def _lane_partial(t):
    """``(state, lanes)`` -> ``(state, 128)``: the 128-lane blocks added up
    (register adds; the 128 lanes themselves are summed once a chunk)."""
    out = t[:, :128]
    for j in range(1, t.shape[1] // 128):
        out = out + t[:, j * 128:(j + 1) * 128]
    return out


def _scan_fwd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref,
                     hs_ref, h_all, xs, dts, ys, bb, cb, *, chunk, n_state):
    """One (batch, chunk, channel block) program: the chunk's ``chunk``
    steps over ``lanes`` channels, the state coming from and going back to
    ``h_all[channel block]``. Writes the chunk's outputs and, first, the
    state it started from (what the backward resumes from)."""
    from jax.experimental import pallas as pl

    ci, ji = pl.program_id(1), pl.program_id(2)
    lanes = xs.shape[1]
    f32 = jnp.float32

    @pl.when(jnp.logical_and(ci == 0, ji == 0))
    def _zero():
        h_all[...] = jnp.zeros_like(h_all)

    @pl.when(ji == 0)
    def _lay_out():
        bb[...] = _lane_tiles(b_ref[0])
        cb[...] = _lane_tiles(c_ref[0])

    xs[...] = x_ref[0].astype(f32)
    dts[...] = dt_ref[0].astype(f32)
    a = a_ref[...]
    d_skip = d_ref[...]
    h0 = h_all[ji]
    hs_ref[0, 0] = h0

    def step(t, h):
        row = pl.ds(t, 1)
        tile = pl.ds(pl.multiple_of(t * n_state, n_state), n_state)
        x_t, dt_t = xs[row, :], dts[row, :]
        h = jnp.exp(dt_t * a) * h + (dt_t * x_t) * _across(bb[tile, :], lanes)
        ys[row, :] = (h * _across(cb[tile, :], lanes)).sum(
            axis=0, keepdims=True) + d_skip * x_t
        return h

    h_all[ji] = _steps(chunk, step, h0, 8)
    y_ref[0] = ys[...].astype(y_ref.dtype)


def _scan_bwd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, hs_ref,
                     gy_ref, gx_ref, gdt_ref, gb_ref, gc_ref, ga_ref,
                     gh_all, h_in, decay, xs, dts, gys, gxs, gdts, bb, cb,
                     pb, pc, *, chunk, n_state, n_dblocks):
    """One (batch, chunk, channel block) program of the backward, the
    chunks taken last to first (the caller's index maps). It resumes from
    the state the forward saved at the chunk's start, recomputes the
    chunk's states and decays into VMEM, then walks the chunk backwards:
    ``gh`` is the gradient of the state after step ``t``, carried from
    chunk to chunk in ``gh_all[channel block]``.

        gh    += gy_t C_t^T
        gC_t   = sum over channels of h_t gy_t
        gB_t   = sum over channels of gh (dt_t x_t)
        gu_t   = sum over the state of gh B_t            (u = dt x)
        ga     = gh * h_(t-1) * exp(dt_t A)              (a = dt A)
        gA    += ga dt_t;  gdt_t = sum over the state of ga A + gu_t x_t
        gx_t   = gu_t dt_t + D gy_t;  gh <- gh * exp(dt_t A)
    """
    from jax.experimental import pallas as pl

    ci, ji = pl.program_id(1), pl.program_id(2)
    lanes = xs.shape[1]
    f32 = jnp.float32

    @pl.when(jnp.logical_and(ci == 0, ji == 0))
    def _zero():
        gh_all[...] = jnp.zeros_like(gh_all)
        ga_ref[...] = jnp.zeros_like(ga_ref)

    @pl.when(ji == 0)
    def _lay_out():
        bb[...] = _lane_tiles(b_ref[0])
        cb[...] = _lane_tiles(c_ref[0])
        pb[...] = jnp.zeros_like(pb)
        pc[...] = jnp.zeros_like(pc)

    xs[...] = x_ref[0].astype(f32)
    dts[...] = dt_ref[0].astype(f32)
    gys[...] = gy_ref[0].astype(f32)
    a = a_ref[...]
    d_skip = d_ref[...]

    def tile_at(t):
        return pl.ds(pl.multiple_of(t * n_state, n_state), n_state)

    def forward(t, h):
        row, tile = pl.ds(t, 1), tile_at(t)
        x_t, dt_t = xs[row, :], dts[row, :]
        da = jnp.exp(dt_t * a)
        h_in[tile, :] = h
        decay[tile, :] = da
        return da * h + (dt_t * x_t) * _across(bb[tile, :], lanes)

    h_in[tile_at(chunk), :] = _steps(chunk, forward, hs_ref[0, 0], 8)

    def backward(i, carry):
        gh, ga_sum = carry
        t = chunk - 1 - i
        row, tile = pl.ds(t, 1), tile_at(t)
        x_t, dt_t, gy_t = xs[row, :], dts[row, :], gys[row, :]
        b_t = _across(bb[tile, :], lanes)
        gh = gh + gy_t * _across(cb[tile, :], lanes)
        pc[tile, :] += _lane_partial(h_in[tile_at(t + 1), :] * gy_t)
        pb[tile, :] += _lane_partial(gh * (dt_t * x_t))
        gu = (gh * b_t).sum(axis=0, keepdims=True)
        da = decay[tile, :]
        ga = gh * h_in[tile, :] * da
        gdts[row, :] = (ga * a).sum(axis=0, keepdims=True) + gu * x_t
        gxs[row, :] = gu * dt_t + d_skip * gy_t
        return gh * da, ga_sum + ga * dt_t

    gh, ga_sum = _steps(chunk, backward,
                        (gh_all[ji], jnp.zeros_like(a)), 4)
    gh_all[ji] = gh
    ga_ref[0, ji] += ga_sum
    gx_ref[0] = gxs[...].astype(gx_ref.dtype)
    gdt_ref[0] = gdts[...].astype(gdt_ref.dtype)

    @pl.when(ji == n_dblocks - 1)
    def _reduce():
        # the 128 lanes summed and the (position, state) rows turned into
        # one lane-major row by the same product, ones @ partial^T
        ones = jnp.ones((8, 128), f32)
        gb_ref[0, 0] = _exact_dot(ones, pb[...], _NT)
        gc_ref[0, 0] = _exact_dot(ones, pc[...], _NT)


def _lanes_of(channels):
    """Channels a program: the most of ``LANES`` that divides them in
    whole 128-lane blocks."""
    lanes = LANES
    while lanes > 128 and channels % lanes:
        lanes //= 2
    return lanes


def _operands(x, dt, a, b, c, d):
    f32 = jnp.float32
    xp, dtp, bp, cp = _pad_positions(x, dt, b, c)
    return (xp, dtp.astype(f32), bp, cp, a.astype(f32).T,
            d.astype(f32).reshape(1, -1))


def selective_scan_forward(x, dt, a, b, c, d, interpret=False):
    """``(y, states)`` by the Pallas kernel: ``y (batch, positions,
    channels)`` in ``x``'s type and the float32 state each chunk started
    from, ``(batch, chunks, state, channels)``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, s, dch = x.shape
    n = a.shape[1]
    lanes = _lanes_of(dch)
    chunk = CHUNK
    xp, dtp, bp, cp, at, dk = _operands(x, dt, a, b, c, d)
    n_c, n_d = xp.shape[1] // chunk, dch // lanes
    f32 = jnp.float32
    y, states = pl.pallas_call(
        _functools.partial(_scan_fwd_kernel, chunk=chunk, n_state=n),
        grid=(bsz, n_c, n_d),
        in_specs=[
            pl.BlockSpec((1, chunk, lanes), lambda i, ci, j: (i, ci, j)),
            pl.BlockSpec((1, chunk, lanes), lambda i, ci, j: (i, ci, j)),
            pl.BlockSpec((1, chunk, n), lambda i, ci, j: (i, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, ci, j: (i, ci, 0)),
            pl.BlockSpec((n, lanes), lambda i, ci, j: (0, j)),
            pl.BlockSpec((1, lanes), lambda i, ci, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, lanes), lambda i, ci, j: (i, ci, j)),
            pl.BlockSpec((1, 1, n, lanes), lambda i, ci, j: (i, ci, 0, j)),
        ],
        out_shape=[jax.ShapeDtypeStruct(xp.shape, x.dtype),
                   jax.ShapeDtypeStruct((bsz, n_c, n, dch), f32)],
        scratch_shapes=[pltpu.VMEM((n_d, n, lanes), f32)]
        + [pltpu.VMEM((chunk, lanes), f32)] * 3
        + [pltpu.VMEM((chunk * n, 128), f32)] * 2,
        interpret=interpret,
    )(xp, dtp, bp, cp, at, dk)
    return y[:, :s], states


def selective_scan_backward_kernel(x, dt, a, b, c, d, states, cot,
                                   interpret=False):
    """``(gx, gdt, gA, gB, gC, gD)`` by the Pallas backward kernel from the
    forward's operands and the states it saved at chunk boundaries. dB and
    dC leave the kernel as one lane-major row a chunk (eight equal rows:
    the MXU's tile), dA as a sum a batch row and channel block; the sums
    over the batch, and dD, are plain XLA."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, s, dch = x.shape
    n = a.shape[1]
    lanes = _lanes_of(dch)
    chunk = CHUNK
    xp, dtp, bp, cp, at, dk = _operands(x, dt, a, b, c, d)
    (gyp,) = _pad_positions(cot)
    n_c, n_d = xp.shape[1] // chunk, dch // lanes
    f32 = jnp.float32

    def back(ci):
        return n_c - 1 - ci

    gx, gdt, gb, gc, ga = pl.pallas_call(
        _functools.partial(_scan_bwd_kernel, chunk=chunk, n_state=n,
                           n_dblocks=n_d),
        grid=(bsz, n_c, n_d),
        in_specs=[
            pl.BlockSpec((1, chunk, lanes), lambda i, ci, j: (i, back(ci), j)),
            pl.BlockSpec((1, chunk, lanes), lambda i, ci, j: (i, back(ci), j)),
            pl.BlockSpec((1, chunk, n), lambda i, ci, j: (i, back(ci), 0)),
            pl.BlockSpec((1, chunk, n), lambda i, ci, j: (i, back(ci), 0)),
            pl.BlockSpec((n, lanes), lambda i, ci, j: (0, j)),
            pl.BlockSpec((1, lanes), lambda i, ci, j: (0, j)),
            pl.BlockSpec((1, 1, n, lanes),
                         lambda i, ci, j: (i, back(ci), 0, j)),
            pl.BlockSpec((1, chunk, lanes), lambda i, ci, j: (i, back(ci), j)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, lanes), lambda i, ci, j: (i, back(ci), j)),
            pl.BlockSpec((1, chunk, lanes), lambda i, ci, j: (i, back(ci), j)),
            pl.BlockSpec((1, 1, 8, chunk * n),
                         lambda i, ci, j: (i, back(ci), 0, 0)),
            pl.BlockSpec((1, 1, 8, chunk * n),
                         lambda i, ci, j: (i, back(ci), 0, 0)),
            pl.BlockSpec((1, n_d, n, lanes), lambda i, ci, j: (i, 0, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct(xp.shape, x.dtype),
                   jax.ShapeDtypeStruct(xp.shape, f32),
                   jax.ShapeDtypeStruct((bsz, n_c, 8, chunk * n), f32),
                   jax.ShapeDtypeStruct((bsz, n_c, 8, chunk * n), f32),
                   jax.ShapeDtypeStruct((bsz, n_d, n, lanes), f32)],
        scratch_shapes=[pltpu.VMEM((n_d, n, lanes), f32),
                        pltpu.VMEM(((chunk + 1) * n, lanes), f32),
                        pltpu.VMEM((chunk * n, lanes), f32)]
        + [pltpu.VMEM((chunk, lanes), f32)] * 5
        + [pltpu.VMEM((chunk * n, 128), f32)] * 4,
        interpret=interpret,
    )(xp, dtp, bp, cp, at, dk, states, gyp)

    def rows(t, like):  # (batch, chunks, 8, chunk * state) -> like's
        return t[:, :, 0].reshape(bsz, n_c * chunk, n)[:, :s].astype(
            like.dtype)

    g_a = ga.sum(0).transpose(1, 0, 2).reshape(n, dch).T.astype(a.dtype)
    g_d = (cot.astype(f32) * x.astype(f32)).sum((0, 1)).astype(d.dtype)
    return (gx[:, :s], gdt[:, :s].astype(dt.dtype), g_a, rows(gb, b),
            rows(gc, c), g_d)


@_functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, dt, a, b, c, d, interpret):
    return selective_scan_forward(x, dt, a, b, c, d, interpret)[0]


def _scan_fwd(x, dt, a, b, c, d, interpret):
    y, states = selective_scan_forward(x, dt, a, b, c, d, interpret)
    return y, (x, dt, a, b, c, d, states)


def _scan_bwd(interpret, res, cot):
    """The backward is a dispatch of its own (``selective_scan_bwd``), as
    the flash backward is. A forward that ran in the interpreter asks for
    the same; on the chip the table, or the family's default, decides."""
    from . import dispatch

    return dispatch("selective_scan_bwd", *res, cot,
                    interpret=True if interpret else None)


_scan.defvjp(_scan_fwd, _scan_bwd)


# ---- registry wiring -------------------------------------------------

def _kernel(x, dt, a, b, c, d, interpret=False):
    return _scan(x, dt, a, b, c, d, bool(interpret))


def _pow2(n):
    p = 1
    while p < n:
        p *= 2
    return p


def _bucket(x, dt, a, b, c, d, *rest):
    """Batch and positions round UP to powers of two; channels, states and
    the type are exact (they set the tiling); the chunk and the channels a
    program are the ones the kernel runs with."""
    bsz, s, dch = x.shape
    return (f"b{_pow2(bsz)}_s{_pow2(s)}_d{dch}_n{a.shape[1]}_"
            f"{jnp.dtype(x.dtype).name}_t{CHUNK}l{_lanes_of(dch)}")


def _supports(x, dt, a, b, c, d, *rest):
    """Rank-3 inputs, channels in whole 128-lane blocks, the state in
    whole 8-sublane tiles, ``B`` and ``C`` as long as ``x``."""
    if x.ndim != 3 or a.ndim != 2 or b.ndim != 3:
        return False
    bsz, s, dch = x.shape
    n = a.shape[1]
    return (dt.shape == x.shape and a.shape[0] == dch and dch % 128 == 0
            and n % 8 == 0 and 0 < n <= 128
            and b.shape == (bsz, s, n) and c.shape == b.shape
            and d.shape == (dch,))


def _bwd_kernel(x, dt, a, b, c, d, states, cot, interpret=False):
    return selective_scan_backward_kernel(x, dt, a, b, c, d, states, cot,
                                          bool(interpret))


def _bwd_xla(x, dt, a, b, c, d, states, cot):
    """The gradient of the chunked XLA form: it makes its own chunk
    boundaries and recomputes inside them, and has no use for the
    kernel's saved states."""
    del states
    _, vjp = jax.vjp(selective_scan_chunked, x, dt, a, b, c, d)
    return vjp(cot)


def _register():
    from . import register_kernel

    register_kernel(
        "selective_scan", kernel=_kernel, xla=selective_scan_chunked,
        bucket=_bucket, supports=_supports, default_tpu=True,
        tolerance="f32 rtol=1e-5 atol=1e-5 vs a step-by-step loop (the "
                  "state is summed in another order); bf16 x, B, C are "
                  "widened on entry, dt, A and the state float32, the "
                  "output rounded once")
    register_kernel(
        "selective_scan_bwd", kernel=_bwd_kernel, xla=_bwd_xla,
        bucket=_bucket, supports=_supports, default_tpu=True,
        tolerance="f32 rtol=1e-4 atol=1e-5 vs the gradient of the "
                  "step-by-step loop: the states inside a chunk are "
                  "recomputed from the float32 state saved at its start")


_register()
