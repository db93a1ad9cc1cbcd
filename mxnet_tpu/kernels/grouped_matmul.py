"""Grouped matrix products over an expert-ordered row buffer (registry
family ``grouped_matmul``).

The buffer's first ``sizes[0]`` rows belong to group 0, the next
``sizes[1]`` to group 1, and so on; rows past ``sizes.sum()`` belong to no
group. Three forms, by ``mode``:

    "nn"  a (rows, K) @ w[g] (K, N), w (groups, K, N)    -> (rows, N)
    "nt"  a (rows, K) @ w[g]^T,      w (groups, N, K)    -> (rows, N)
    "tn"  a[g]^T (K, rows of g) @ b[g] (rows of g, N)    -> (groups, K, N)

"nn" is an expert layer's forward product, "nt" an input's cotangent (the
weights are read transposed where they lie, no copy), "tn" a weight's
cotangent. "nn" and "nt" leave the rows past the live count undefined
(nothing may read them); "tn" reads no such row, and an empty group's
result is zeros. Operands of one type, float32 accumulation, the result in
the operands' type, as ``jax.lax.ragged_dot`` gives it.

The kernels (``_gmm`` for "nn" / "nt", ``_tgmm`` for "tn", after the
megablox pair that ships with jax) walk a grid over the live row tiles
alone, group by group, from a schedule of a few integer vectors computed
before the call (``_schedule``), with a float32 accumulator in VMEM. Both
are jitted with the shapes and tiles static, so every call of one ``(mode,
rows, K, N)`` in a program shares one trace and one Mosaic kernel: a warm
start traces and lowers each distinct shape once, whatever the count of
calls. A start traces and lowers every Pallas kernel again, warm or cold
(the persistent cache's key is the lowered module), so the traced code is
written in ``jax.lax``: a ``jax.numpy`` call is a jit of its own, traced on
every start. The XLA side is ``jax.lax.ragged_dot`` (a ``jnp.swapaxes``
copy of the weights for "nt") and ``jax.lax.ragged_dot_general``, the forms
the expert layer ran before.

Tiles are a pure function of ``(mode, rows, K, N)``: ``TILES`` holds the
winners of ``benchmark/opperf.py --gmm-sweep`` on the chip at the expert
cells' widths; any other shape takes ``_rule``'s. Nothing is timed at start.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = ["grouped_matmul_xla", "tiles", "TILES", "MODES", "vmem_bytes"]

MODES = ("nn", "nt", "tn")

# (mode, K, N) -> (tm, tk, tn): the fastest tile of the sweep at the expert
# cells' widths (TPU v5 lite; PERF.md section 6 holds the rows)
TILES = {
    ("nn", 768, 2048): (512, 768, 1024),
    ("nn", 2048, 768): (512, 2048, 768),
    ("nt", 768, 2048): (512, 768, 1024),
    ("nt", 2048, 768): (512, 2048, 768),
    ("tn", 768, 2048): (512, 768, 1024),
    ("tn", 2048, 768): (512, 1024, 768),
    ("nn", 1792, 2048): (512, 1792, 1024),
    ("nn", 2048, 1792): (512, 2048, 896),
    ("nt", 1792, 2048): (512, 1792, 1024),
    ("nt", 2048, 1792): (512, 2048, 896),
    ("tn", 1792, 2048): (512, 896, 1024),
    ("tn", 2048, 1792): (512, 1024, 896),
    ("nn", 896, 2304): (512, 896, 1152),
    ("nn", 2304, 896): (512, 1152, 896),
    ("nt", 896, 2304): (512, 896, 1152),
    ("nt", 2304, 896): (512, 1152, 896),
    ("tn", 896, 2304): (512, 896, 768),
    ("tn", 2304, 896): (512, 768, 896),
}

_NO_MESH = jax.sharding.AbstractMesh((), ())

# what the rule lets a call hold in VMEM: the 16 MiB a v5e scopes for a
# call, less room for Mosaic's own
_VMEM_BUDGET = 12 * 2 ** 20


def _divisors(n, cap):
    """Multiples of 128 that divide ``n``, largest first, up to ``cap``."""
    return [d for d in range(min(n, cap) // 128 * 128, 0, -128)
            if n % d == 0]


def vmem_bytes(mode, tm, tk, tn, itemsize=2):
    """VMEM a tile holds: the two operands' blocks and the result's, each
    double-buffered, and the float32 accumulator; "tn" also widens both
    operand blocks to float32 to mask the rows of other groups."""
    if mode == "tn":
        return (2 * (tm * tk + tm * tn + tk * tn) * itemsize + tk * tn * 4
                + (tm * tk + tm * tn) * 4)
    return 2 * (tm * tk + tk * tn + tm * tn) * itemsize + tm * tn * 4


def _rule(mode, rows, k, n, itemsize):
    """Row tiles of 512 where the rows allow; the widest K and N tiles up to
    1,024 that fit the budget, the larger of the two cut first."""
    tm = next(t for t in (512, 256, 128) if rows % t == 0)
    tks, tns = _divisors(k, 1024), _divisors(n, 1024)
    while vmem_bytes(mode, tm, tks[0], tns[0], itemsize) > _VMEM_BUDGET:
        if tks[0] >= tns[0] and len(tks) > 1:
            tks.pop(0)
        elif len(tns) > 1:
            tns.pop(0)
        else:
            break
    return tm, tks[0], tns[0]


def tiles(mode, rows, k, n, itemsize=2):
    """``(tm, tk, tn)`` of one call: the sweep's where it measured the
    widths, with the row tile halved until it divides ``rows``."""
    found = TILES.get((mode, k, n))
    if found is None:
        return _rule(mode, rows, k, n, itemsize)
    tm, tk, tn = found
    while rows % tm:
        tm //= 2
    return tm, tk, tn


def _shape(a, b, mode):
    """(rows, K, N, groups) of one call."""
    if mode == "tn":
        return a.shape[0], a.shape[1], b.shape[1], None
    k, n = (b.shape[2], b.shape[1]) if mode == "nt" else b.shape[1:]
    return a.shape[0], k, n, b.shape[0]


def grouped_matmul_xla(a, b, sizes, mode="nn"):
    """The XLA side: ``jax.lax.ragged_dot`` and its general form."""
    if mode == "nn":
        return jax.lax.ragged_dot(a, b, sizes)
    if mode == "nt":
        return jax.lax.ragged_dot(a, jnp.swapaxes(b, 1, 2), sizes)
    return jax.lax.ragged_dot_general(
        a, b, sizes, jax.lax.RaggedDotDimensionNumbers(
            (((0,), (0,)), ((), ())), (0,), ()))


# the schedule's and the kernels' integer constants: numpy scalars, which
# lax takes as literals
_I32 = np.int32


def _schedule(sizes, rows, tm, visit_empty):
    """The row tiles a call visits, group by group: ``(offsets (groups +
    1,), group (slots,), tile (slots,), count)``. Slot ``i < count`` works
    on row tile ``tile[i]`` for group ``group[i]``, whose rows are
    ``offsets[g] .. offsets[g + 1]``; a tile two groups share is visited
    by both, one after the other. Only the live rows are covered; with
    ``visit_empty`` an empty group still takes one slot, so that its
    result is written (zeros)."""
    groups, tiles_m = sizes.shape[0], rows // tm
    slots = tiles_m + groups - 1
    ends = lax.cumsum(sizes)
    first = lax.div(lax.sub(ends, sizes), _I32(tm))
    spans = lax.select(
        lax.gt(sizes, _I32(0)),
        lax.sub(lax.div(lax.add(ends, _I32(tm - 1)), _I32(tm)), first),
        jnp.full((groups,), int(visit_empty), _I32))
    until = lax.cumsum(spans)

    def across(v):
        return lax.broadcast_in_dim(v, (slots, groups), (1,))

    slot = lax.broadcasted_iota(_I32, (slots, groups), 0)
    begin = across(lax.sub(until, spans))
    mine = lax.bitwise_and(lax.ge(slot, begin), lax.lt(slot, across(until)))

    def pick(v):
        # the value of the slot's group; 0 in a slot past the last, which
        # never runs
        return lax.reduce(lax.select(mine, v, jnp.zeros_like(v)), _I32(0),
                          lax.add, (1,))

    group = pick(lax.broadcasted_iota(_I32, (slots, groups), 1))
    tile = lax.clamp(_I32(0), pick(lax.add(across(first),
                                           lax.sub(slot, begin))),
                     _I32(tiles_m - 1))
    offsets = lax.concatenate([jnp.zeros((1,), _I32), ends], 0)
    count = lax.reduce(spans, _I32(0), lax.add, (0,))
    return offsets, group, tile, count


def _rows_of(offsets, group, tile, i, tm, width):
    """Which rows of slot ``i``'s tile belong to its group, (tm, width)."""
    g = group[i]
    row = lax.add(lax.broadcasted_iota(_I32, (tm, width), 0),
                  lax.mul(tile[i], _I32(tm)))
    return lax.bitwise_and(lax.ge(row, offsets[g]),
                           lax.lt(row, offsets[lax.add(g, _I32(1))]))


# jitted for the trace cache alone: inlined into the caller's program, never
# an executable of its own
@functools.partial(jax.jit,  # noqa: raw-jit
                   static_argnames=("tile", "transpose", "interpret"))
def _gmm(a, w, sizes, tile, transpose, interpret):
    """"nn" / "nt": grid (N tiles, live row tiles, K tiles), K innermost
    into a float32 accumulator; each row tile's result is written where
    its rows belong to the slot's group, so a tile two groups share holds
    both groups' rows when its block leaves VMEM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tm, tk, tn = tile
    rows, k = a.shape
    n = w.shape[1] if transpose else w.shape[2]
    steps = k // tk
    offsets, group, tiles, count = _schedule(sizes, rows, tm, False)

    def kernel(offsets, group, tiles, a_ref, w_ref, out_ref, acc_ref):
        i, kk = pl.program_id(1), pl.program_id(2)

        @pl.when(lax.eq(kk, 0))
        def _():
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        acc_ref[...] = lax.add(acc_ref[...], lax.dot_general(
            a_ref[...], w_ref[...],
            (((1,), (1 if transpose else 0,)), ((), ())),
            preferred_element_type=jnp.float32))

        @pl.when(lax.eq(kk, steps - 1))
        def _():
            mine = _rows_of(offsets, group, tiles, i, tm, tn)
            out_ref[...] = lax.select(
                mine, acc_ref[...],
                out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)

    w_block = ((None, tn, tk), lambda j, i, kk, o, g, t: (g[i], j, kk)) \
        if transpose else \
        ((None, tk, tn), lambda j, i, kk, o, g, t: (g[i], kk, j))
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(n // tn, count, steps),
        in_specs=[pl.BlockSpec((tm, tk), lambda j, i, kk, o, g, t: (t[i], kk)),
                  pl.BlockSpec(*w_block)],
        out_specs=pl.BlockSpec((tm, tn), lambda j, i, kk, o, g, t: (t[i], j)),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)])
    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((rows, n), a.dtype),
        grid_spec=grid, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n, transcendentals=0,
            bytes_accessed=(rows * k * (n // tn) + w.size + rows * n)
            * a.dtype.itemsize),
    )(offsets, group, tiles, a, w)


@functools.partial(jax.jit,  # noqa: raw-jit
                   static_argnames=("tile", "interpret"))
def _tgmm(a, b, sizes, tile, interpret):
    """"tn": grid (K tiles, N tiles, row tiles of every group), the rows
    innermost into a float32 accumulator that is reset where the group
    changes and written where it ends. Rows of other groups, and the
    undefined rows past the live count, are selected away in both
    operands before the product."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tm, tk, tn = tile
    rows, k = a.shape
    n = b.shape[1]
    groups = sizes.shape[0]
    offsets, group, tiles, count = _schedule(sizes, rows, tm, True)

    def kernel(offsets, group, tiles, a_ref, b_ref, out_ref, acc_ref):
        i, last = pl.program_id(2), lax.sub(pl.num_programs(2), 1)
        g = group[i]

        @pl.when(lax.bitwise_or(
            lax.eq(i, 0), lax.ne(group[lax.max(lax.sub(i, 1), 0)], g)))
        def _():
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        def live(ref, width):
            x = ref[...].astype(jnp.float32)
            return lax.select(_rows_of(offsets, group, tiles, i, tm, width),
                              x, jnp.zeros_like(x)).astype(ref.dtype)

        acc_ref[...] = lax.add(acc_ref[...], lax.dot(
            lax.transpose(live(a_ref, tk), (1, 0)), live(b_ref, tn),
            preferred_element_type=jnp.float32))

        @pl.when(lax.bitwise_or(
            lax.eq(i, last), lax.ne(group[lax.min(lax.add(i, 1), last)], g)))
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(k // tk, n // tn, count),
        in_specs=[pl.BlockSpec((tm, tk), lambda kk, j, i, o, g, t: (t[i], kk)),
                  pl.BlockSpec((tm, tn), lambda kk, j, i, o, g, t: (t[i], j))],
        out_specs=pl.BlockSpec((None, tk, tn),
                               lambda kk, j, i, o, g, t: (g[i], kk, j)),
        scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)])
    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((groups, k, n), a.dtype),
        grid_spec=grid, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n, transcendentals=0,
            bytes_accessed=(rows * k * (n // tn) + rows * n * (k // tk)
                            + groups * k * n) * a.dtype.itemsize),
    )(offsets, group, tiles, a, b)


def _kernel(a, b, sizes, mode="nn", interpret=False):
    rows, k, n, _ = _shape(a, b, mode)
    tile = tiles(mode, rows, k, n, jnp.dtype(a.dtype).itemsize)
    if mode == "tn":
        call = functools.partial(_tgmm, a, b, sizes, tile)
    else:
        call = functools.partial(_gmm, a, b, sizes, tile, mode == "nt")
    if not jax.sharding.get_abstract_mesh().empty:
        return call(interpret=bool(interpret))
    # jit's trace cache keys on the context's mesh, which a backward pass
    # sets to the empty mesh where the forward left none: one context for
    # both, so a forward and a backward product of one shape share one
    # trace and one Mosaic kernel
    with jax.sharding.use_abstract_mesh(_NO_MESH):
        return call(interpret=bool(interpret))


def _supports(a, b, sizes, mode="nn"):
    """Operands of one type, bfloat16 or float32; K and N in whole 128-lane
    tiles; rows in whole 128-row tiles; one size a group."""
    if mode not in MODES or a.ndim != 2 or sizes.ndim != 1 \
            or sizes.dtype != jnp.int32 or a.dtype != b.dtype \
            or a.dtype not in (jnp.bfloat16, jnp.float32):
        return False
    if b.ndim != (2 if mode == "tn" else 3):
        return False
    rows, k, n, groups = _shape(a, b, mode)
    if mode == "tn":
        ok = b.shape[0] == rows
    else:
        ok = groups == sizes.shape[0] and k == a.shape[1]
    return ok and rows % 128 == 0 and k % 128 == 0 and n % 128 == 0


def _bucket(a, b, sizes, mode="nn"):
    """Mode, the exact shape and type, and the tiles the call runs with."""
    rows, k, n, _ = _shape(a, b, mode)
    tm, tk, tn = tiles(mode, rows, k, n, jnp.dtype(a.dtype).itemsize)
    return (f"{mode}_m{rows}_k{k}_n{n}_g{sizes.shape[0]}_"
            f"{jnp.dtype(a.dtype).name}_t{tm}x{tk}x{tn}")


def _register():
    from . import register_kernel

    register_kernel(
        "grouped_matmul", kernel=_kernel, xla=grouped_matmul_xla,
        bucket=_bucket, supports=_supports, default_tpu=True,
        tolerance="float32 accumulation in another order than ragged_dot's "
                  "(K in tiles): bf16 results within one bf16 rounding of "
                  "the float32 reference of the same operands; rows past "
                  "the live count undefined on both sides")


_register()
