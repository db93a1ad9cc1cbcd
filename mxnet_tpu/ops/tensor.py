"""Tensor structure ops: reductions, linalg, indexing, shape manipulation.

Parity target: `src/operator/tensor/` in the reference — reduce
(`broadcast_reduce_op.h`), `dot` (`dot-inl.h`), indexing
(`indexing_op.cc`: take/gather_nd/scatter_nd/Embedding/one_hot), matrix ops
(`matrix_op.cc`: transpose/reshape/slice/concat/...), ordering
(`ordering_op.cc`: sort/argsort/topk), init ops (`init_op.cc`).

TPU-native notes: `dot`/`batch_dot` lower straight onto the MXU via
`lax.dot_general` with a bf16-friendly `preferred_element_type`; gathers and
scatters use XLA's native gather/scatter (no hand-written kernels).
"""
from __future__ import annotations

import functools as _functools
import math as _math

import jax
import jax.numpy as jnp
import numpy as _np

from .registry import register


def _norm_axis(axis):
    if axis is None or axis == ():
        return None
    if isinstance(axis, (list, tuple)):
        return tuple(int(a) for a in axis)
    return int(axis)


# ----------------------------------------------------------- reductions ----

def _make_reduce(jfn):
    def red(x, axis=None, keepdims=False, exclude=False):
        ax = _norm_axis(axis)
        if exclude and ax is not None:
            all_ax = set(range(x.ndim))
            keep = {a % x.ndim for a in (ax if isinstance(ax, tuple) else (ax,))}
            ax = tuple(sorted(all_ax - keep))
        return jfn(x, axis=ax, keepdims=keepdims)

    return red


for _name, _jfn in [("sum", jnp.sum), ("mean", jnp.mean), ("prod", jnp.prod),
                    ("nansum", jnp.nansum), ("nanprod", jnp.nanprod),
                    ("max", jnp.max), ("min", jnp.min)]:
    register(_name, aliases=(f"_np_{_name}",))(_make_reduce(_jfn))


@register("norm")
def _norm(x, ord=2, axis=None, keepdims=False):
    ax = _norm_axis(axis)
    if ord == 1:
        return jnp.sum(jnp.abs(x), axis=ax, keepdims=keepdims)
    return jnp.sqrt(jnp.sum(jnp.square(x), axis=ax, keepdims=keepdims))


@register("argmax", differentiable=False)
def _argmax(x, axis=None, keepdims=False):
    out = jnp.argmax(x, axis=_norm_axis(axis), keepdims=keepdims)
    return out.astype(jnp.float32)  # parity: MXNet argmax returns float


@register("argmin", differentiable=False)
def _argmin(x, axis=None, keepdims=False):
    return jnp.argmin(x, axis=_norm_axis(axis), keepdims=keepdims).astype(jnp.float32)


@register("argsort", differentiable=False)
def _argsort(x, axis=-1, is_ascend=True):
    idx = jnp.argsort(x, axis=axis)
    if not is_ascend:
        idx = jnp.flip(idx, axis=axis)
    return idx.astype(jnp.float32)


@register("sort")
def _sort(x, axis=-1, is_ascend=True):
    out = jnp.sort(x, axis=axis)
    if not is_ascend:
        out = jnp.flip(out, axis=axis)
    return out


@register("topk", differentiable=False)
def _topk(x, axis=-1, k=1, ret_typ="indices", is_ascend=False, dtype="float32"):
    from ..base import canonical_dtype

    axis = axis % x.ndim
    xm = jnp.moveaxis(x, axis, -1)
    vals, idx = jax.lax.top_k(-xm if is_ascend else xm, k)
    if is_ascend:
        vals = -vals
    vals = jnp.moveaxis(vals, -1, axis)
    idx = jnp.moveaxis(idx, -1, axis).astype(canonical_dtype(dtype))
    if ret_typ == "value":
        return vals
    if ret_typ == "both":
        return vals, idx
    return idx


# --------------------------------------------------------------- linalg ----

@register("dot")
def _dot(lhs, rhs, transpose_a=False, transpose_b=False):
    if transpose_a:
        lhs = jnp.swapaxes(lhs, -1, -2) if lhs.ndim > 1 else lhs
    if transpose_b:
        rhs = jnp.swapaxes(rhs, -1, -2) if rhs.ndim > 1 else rhs
    if lhs.ndim == 1 and rhs.ndim == 1:
        return jnp.dot(lhs, rhs)
    # MXNet dot: contract last axis of lhs with first axis of rhs
    return jnp.tensordot(lhs, rhs, axes=([lhs.ndim - 1], [0]))


@register("batch_dot")
def _batch_dot(lhs, rhs, transpose_a=False, transpose_b=False):
    if transpose_a:
        lhs = jnp.swapaxes(lhs, -1, -2)
    if transpose_b:
        rhs = jnp.swapaxes(rhs, -1, -2)
    return jnp.matmul(lhs, rhs)


@register("linalg_gemm2")
def _linalg_gemm2(a, b, transpose_a=False, transpose_b=False, alpha=1.0):
    if transpose_a:
        a = jnp.swapaxes(a, -1, -2)
    if transpose_b:
        b = jnp.swapaxes(b, -1, -2)
    return alpha * jnp.matmul(a, b)


@register("linalg_potrf")
def _potrf(a):
    return jnp.linalg.cholesky(a)


@register("linalg_syrk")
def _syrk(a, transpose=False, alpha=1.0):
    at = jnp.swapaxes(a, -1, -2)
    return alpha * (jnp.matmul(at, a) if transpose else jnp.matmul(a, at))


@register("khatri_rao")
def _khatri_rao(*mats):
    out = mats[0]
    for m in mats[1:]:
        out = jnp.einsum("i...,j...->ij...", out, m).reshape(-1, out.shape[-1])
    return out


# ------------------------------------------------------------- indexing ----

@register("take")
def _take(a, indices, axis=0, mode="clip"):
    return jnp.take(a, indices.astype(jnp.int32), axis=axis,
                    mode="clip" if mode == "clip" else "wrap")


@register("pick")
def _pick(data, index, axis=-1, keepdims=False, mode="clip"):
    idx = jnp.clip(index.astype(jnp.int32), 0, data.shape[axis] - 1)
    out = jnp.take_along_axis(data, jnp.expand_dims(idx, axis), axis=axis)
    return out if keepdims else jnp.squeeze(out, axis=axis)


@register("gather_nd")
def _gather_nd(data, indices):
    idx = tuple(indices.astype(jnp.int32))
    return data[idx]


@register("scatter_nd")
def _scatter_nd(data, indices, shape=()):
    out = jnp.zeros(tuple(shape), dtype=data.dtype)
    idx = tuple(indices.astype(jnp.int32))
    return out.at[idx].add(data)


def embedding_grad_columns(rows, vocab, width):
    """How many columns of the table ``Embedding``'s backward scatters at a
    time, from the shape alone: ``rows`` ids into a ``vocab`` x ``width``
    table. 512 where XLA would sort the ids and walk a table wider than
    1,024; the whole width elsewhere (one scatter, as ``jnp.take``'s
    transpose is written).

    Why (a TPU v5e, libtpu 0.0.34; device ms of the table's cotangent
    alone in a jit, Zipf(1) ids; my chip runs, PR 32; ``benchmark/opperf.py
    --embedding-grad-sweep``). XLA has two scatter emitters and picks by
    ``vocab < 8 * rows`` (found by compiling; 3,200 rows into 25,008 walk,
    2,048 do not). Under it it sorts the ids and WALKS the table: the time
    follows the table's rows, not the ids (4,096 rows into 25,008 x 2,560:
    9.79; unique ids 9.83; into 12,504 rows 5.07; float32 9.93; added to a
    tied head's dW 10.15), and the width decides the rate: 4,096 rows into
    25,008 x 256 / 512 / 768 / 1,024 / 1,280 / 1,536 / 2,048 / 2,304 /
    **2,560** / 3,072 / 4,096 / **5,120**: 0.10 / 0.17 / 0.27 / 0.40 / 0.59 /
    1.02 / 1.38 / 2.53 / **9.79** / 2.05 / 1.52 / **43.7**. In blocks of 512
    columns the same tables take 0.27 (768) / 0.36 / 0.46 / 0.53 / 0.71 /
    0.81 / **0.89** / 1.06 / 1.41 / **1.77**: never slower from 1,024 on;
    blocks of 256 / 1,024 at 2,560: 1.01 / 1.11. The cells: the hybrid
    decoder (4,096 into 25,008 x 2,560) 9.79 whole, **0.89** in blocks; the
    language model (8,192 into 16,032 x 2,048) 1.27, **0.92**; BERT's words
    (12,288 into 30,522 x 768) **0.55**, 0.61. Otherwise XLA updates the
    table ROW BY ROW, in place, for 0.12-0.25 us a row, and blocks only
    repeat that: 4,096 into 200,064 x 2,560 **2.60** whole (1.55 of it the
    zeros), 6.80 in blocks; 8,192 into 128,256 x 2,048 **2.65**, 5.71; 1,024 /
    2,048 rows into 25,008 x 2,560 **0.46** / **0.72**, 0.53 / 0.82; BERT's
    type table (12,288 into 2 x 768) 0.25, 0.32. A batch of 32,768 tokens
    makes XLA walk the published table: 78.7 whole, **10.2** in blocks.

    The values are the scatter's own either way (a block sums the same
    rows in the same order). The walking emitter sums a run of duplicates
    in float32 (error at id 0's row 0.21 against a float64 sum, the same
    as a float32 product's); the row-by-row emitter accumulates in the
    table's dtype (2.6 at 200,064 rows, PERF.md section 7).

    What lost at the hybrid decoder's shape: ``one_hot(ids)^T @ cot`` 2.73
    (2.84 and 2.98 at the other two cells, 22.6 at 200,064 rows, bfloat16
    products on a float32 table); ids sorted and duplicates summed by an
    (N, N) product before a unique scatter 10.36 (the walk remains); the
    table in 2 / 4 blocks of ROWS 10.41 / 11.05 (each block is walked at the
    same rate); that dedup and 8 scatters of 512 ids each, which XLA runs
    row by row, 1.68."""
    return 512 if width > 1024 and vocab < 8 * rows else width


@_functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _embedding_lookup(weight, ids, vocab):
    return jnp.take(weight, ids, axis=0)


def _embedding_lookup_fwd(weight, ids, vocab):
    return jnp.take(weight, ids, axis=0), ids


def _embedding_grad(ids, cot, vocab, columns):
    """The cotangent of the table under ``jnp.take(table, ids, axis=0)``:
    the transpose jax writes (one XLA scatter-add into zeros), taken over
    blocks of ``columns`` columns, each scattered on its own. Every
    element sums the same rows in the same order whatever ``columns`` is."""
    row = cot.shape[ids.ndim:]
    flat = cot.reshape(ids.shape + (-1,))

    def scatter(block):
        table = jax.ShapeDtypeStruct((vocab, block.shape[-1]), cot.dtype)
        return jax.linear_transpose(
            lambda w: jnp.take(w, ids, axis=0), table)(block)[0]

    if columns >= flat.shape[-1]:
        return scatter(flat).reshape((vocab,) + row)
    parts = [scatter(flat[..., lo:lo + columns])
             for lo in range(0, flat.shape[-1], columns)]
    return jnp.concatenate(parts, axis=1).reshape((vocab,) + row)


def _embedding_lookup_bwd(vocab, ids, cot):
    width = _math.prod(cot.shape[ids.ndim:])
    columns = embedding_grad_columns(ids.size, vocab, width)
    return _embedding_grad(ids, cot, vocab, columns), None


_embedding_lookup.defvjp(_embedding_lookup_fwd, _embedding_lookup_bwd)


@register("Embedding")
def _embedding(data, weight, input_dim=None, output_dim=None, dtype="float32",
               sparse_grad=False):
    return _embedding_lookup(weight, data.astype(jnp.int32), weight.shape[0])


@register("one_hot")
def _one_hot(indices, depth=1, on_value=1.0, off_value=0.0, dtype="float32"):
    from ..base import canonical_dtype

    oh = jax.nn.one_hot(indices.astype(jnp.int32), depth)
    return (oh * (on_value - off_value) + off_value).astype(canonical_dtype(dtype))


@register("where")
def _where(condition, x, y):
    return jnp.where(condition.astype(bool), x, y)


@register("boolean_mask", differentiable=False, eager=True)
def _boolean_mask(data, index, axis=0):
    # dynamic-shape op: output size depends on the mask VALUES, so it
    # must run eagerly, never under jit (parity: test_dynamic_shape.py);
    # inside traces use `where`.
    return jnp.compress(_np.asarray(index).astype(bool), data, axis=axis)


# -------------------------------------------------------- shape manip ------

@register("reshape", aliases=("Reshape",))
def _reshape(x, shape=()):
    # Supports MXNet special codes 0 (copy dim) and -1 (infer)
    tgt = []
    for i, s in enumerate(shape):
        if s == 0:
            tgt.append(x.shape[i])
        elif s == -2:
            tgt.extend(x.shape[i:])
        else:
            tgt.append(int(s))
    return jnp.reshape(x, tuple(tgt))


@register("reshape_like")
def _reshape_like(x, like):
    return jnp.reshape(x, like.shape)


@register("shape_array", differentiable=False)
def _shape_array(x):
    return jnp.asarray(x.shape, dtype=jnp.int64)


@register("size_array", differentiable=False)
def _size_array(x):
    return jnp.asarray([x.size], dtype=jnp.int64)


@register("transpose")
def _transpose(x, axes=()):
    return jnp.transpose(x, tuple(axes) if axes else None)


@register("expand_dims")
def _expand_dims(x, axis=0):
    return jnp.expand_dims(x, axis)


@register("squeeze")
def _squeeze(x, axis=None):
    return jnp.squeeze(x, axis=_norm_axis(axis))


@register("Flatten", aliases=("flatten",))
def _flatten(x):
    return jnp.reshape(x, (x.shape[0], -1))


@register("Concat", aliases=("concat",))
def _concat(*args, dim=1, num_args=None):
    return jnp.concatenate(args, axis=dim)


@register("stack")
def _stack(*args, axis=0, num_args=None):
    return jnp.stack(args, axis=axis)


def _split_impl(x, num_outputs=1, axis=1, squeeze_axis=False):
    parts = jnp.split(x, num_outputs, axis=axis)
    if squeeze_axis:
        parts = [jnp.squeeze(p, axis=axis) for p in parts]
    return tuple(parts) if num_outputs > 1 else parts[0]


register("SliceChannel", aliases=("split", "slice_channel"),
         num_outputs=lambda n_in, kw: int(kw.get("num_outputs", 1)))(_split_impl)


@register("slice")
def _slice(x, begin=(), end=(), step=()):
    slices = []
    for i in range(x.ndim):
        b = begin[i] if i < len(begin) else None
        e = end[i] if i < len(end) else None
        s = step[i] if step and i < len(step) and step[i] else None
        slices.append(slice(b, e, s))
    return x[tuple(slices)]


@register("slice_axis")
def _slice_axis(x, axis=0, begin=0, end=None):
    sl = [slice(None)] * x.ndim
    sl[axis] = slice(begin, end)
    return x[tuple(sl)]


@register("slice_like")
def _slice_like(x, like, axes=()):
    axes = tuple(axes) if axes else tuple(range(x.ndim))
    sl = [slice(None)] * x.ndim
    for a in axes:
        sl[a] = slice(0, like.shape[a])
    return x[tuple(sl)]


@register("flip", aliases=("reverse",))
def _flip(x, axis=0):
    return jnp.flip(x, axis=_norm_axis(axis))


@register("tile")
def _tile(x, reps=()):
    return jnp.tile(x, tuple(reps))


@register("repeat")
def _repeat(x, repeats=1, axis=None):
    return jnp.repeat(x, repeats, axis=axis)


@register("pad", aliases=("Pad",))
def _pad(x, mode="constant", pad_width=(), constant_value=0.0):
    pw = [(pad_width[2 * i], pad_width[2 * i + 1]) for i in range(len(pad_width) // 2)]
    jmode = {"constant": "constant", "edge": "edge", "reflect": "reflect"}[mode]
    if jmode == "constant":
        return jnp.pad(x, pw, mode=jmode, constant_values=constant_value)
    return jnp.pad(x, pw, mode=jmode)


@register("swapaxes", aliases=("SwapAxis",))
def _swapaxes(x, dim1=0, dim2=0):
    return jnp.swapaxes(x, dim1, dim2)


@register("depth_to_space")
def _depth_to_space(x, block_size=1):
    b, c, h, w = x.shape
    bs = block_size
    x = x.reshape(b, bs, bs, c // (bs * bs), h, w)
    x = x.transpose(0, 3, 4, 1, 5, 2)
    return x.reshape(b, c // (bs * bs), h * bs, w * bs)


@register("space_to_depth")
def _space_to_depth(x, block_size=1):
    b, c, h, w = x.shape
    bs = block_size
    x = x.reshape(b, c, h // bs, bs, w // bs, bs)
    x = x.transpose(0, 3, 5, 1, 2, 4)
    return x.reshape(b, c * bs * bs, h // bs, w // bs)


# -------------------------------------------------------------- sequence ---

@register("SequenceMask", aliases=("sequence_mask",))
def _sequence_mask(data, sequence_length=None, use_sequence_length=False, value=0.0,
                   axis=0):
    if not use_sequence_length or sequence_length is None:
        return data
    maxlen = data.shape[axis]
    steps = jnp.arange(maxlen)
    mask = steps[:, None] < sequence_length[None, :].astype(steps.dtype)  # (T, B)
    if axis == 1:
        mask = mask.T
    mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    return jnp.where(mask, data, jnp.asarray(value, dtype=data.dtype))


@register("SequenceLast", aliases=("sequence_last",))
def _sequence_last(data, sequence_length=None, use_sequence_length=False, axis=0):
    if not use_sequence_length or sequence_length is None:
        idx = data.shape[axis] - 1
        return jnp.take(data, idx, axis=axis)
    last = (sequence_length.astype(jnp.int32) - 1)  # (B,)
    moved = jnp.moveaxis(data, axis, 0)  # (T, B, ...)
    return jnp.take_along_axis(
        moved, last.reshape((1, -1) + (1,) * (moved.ndim - 2)), axis=0
    )[0]


@register("SequenceReverse", aliases=("sequence_reverse",))
def _sequence_reverse(data, sequence_length=None, use_sequence_length=False, axis=0):
    if not use_sequence_length or sequence_length is None:
        return jnp.flip(data, axis=axis)
    moved = jnp.moveaxis(data, axis, 0)
    T = moved.shape[0]
    steps = jnp.arange(T)[:, None]
    slen = sequence_length.astype(jnp.int32)[None, :]
    idx = jnp.where(steps < slen, slen - 1 - steps, steps)
    out = jnp.take_along_axis(moved, idx.reshape(idx.shape + (1,) * (moved.ndim - 2)),
                              axis=0)
    return jnp.moveaxis(out, 0, axis)


# ------------------------------------------------------- legacy tail ops ---

@register("batch_take")
def _batch_take(a, indices):
    """parity: src/operator/tensor/indexing_op.cc batch_take — pick one
    element per row."""
    return a[jnp.arange(a.shape[0]), indices.astype(jnp.int32)]


@register("diag")
def _diag(data, k=0, axis1=0, axis2=1):
    """parity: src/operator/tensor/diag_op.cc."""
    if data.ndim == 1:
        return jnp.diag(data, k=k)
    return jnp.diagonal(data, offset=k, axis1=axis1, axis2=axis2)


def _split_v2_indices(indices):
    """The reference python wrapper stores ``[0] + indices`` in the op attr
    (ndarray.py split_v2); accept both that convention (reference-produced
    symbol.json) and bare user indices."""
    idx = list(indices)
    if idx and idx[0] == 0:
        idx = idx[1:]
    return idx


@register("split_v2", num_outputs=lambda n_in, kw:
          int(kw["sections"]) if kw.get("sections")
          else len(_split_v2_indices(kw.get("indices", ()))) + 1)
def _split_v2(data, indices=(), axis=0, squeeze_axis=False, sections=0):
    """parity: matrix_op.cc split_v2 — split at explicit indices or into
    equal sections."""
    if sections:
        parts = jnp.split(data, sections, axis=axis)
    else:
        parts = jnp.split(data, _split_v2_indices(indices), axis=axis)
    if squeeze_axis:
        parts = [jnp.squeeze(p, axis=axis) for p in parts]
    return tuple(parts)


@register("digamma")
def _digamma(data):
    return jax.scipy.special.digamma(data)


@register("multi_sum_sq")
def _multi_sum_sq(*arrays, num_arrays=1):
    """parity: contrib/multi_sum_sq.cc — ONE output vector holding each
    array's sum of squares (used by LANS/LAMB aggregated updates)."""
    return jnp.stack([jnp.sum(jnp.square(a)) for a in arrays])


@register("unravel_index")
def _unravel_index(data, shape=()):
    """parity: tensor/ravel.cc — flat index -> coordinates (ndim, N)."""
    coords = jnp.unravel_index(data.astype(jnp.int32), tuple(shape))
    return jnp.stack(coords, axis=0)


@register("ravel_multi_index")
def _ravel_multi_index(data, shape=()):
    """parity: tensor/ravel.cc — coordinates (ndim, N) -> flat index."""
    return jnp.ravel_multi_index(
        tuple(data[i].astype(jnp.int32) for i in range(data.shape[0])),
        tuple(shape), mode="clip")


@register("choose_element_0index")
def _choose_element_0index(lhs, rhs):
    """parity: legacy choose_element_0index == batch_take."""
    return lhs[jnp.arange(lhs.shape[0]), rhs.astype(jnp.int32)]


@register("fill_element_0index")
def _fill_element_0index(lhs, mhs, rhs):
    """parity: legacy fill_element_0index — set lhs[i, rhs[i]] = mhs[i]."""
    return lhs.at[jnp.arange(lhs.shape[0]), rhs.astype(jnp.int32)].set(mhs)


@register("argmax_channel", differentiable=False)
def _argmax_channel(data):
    """parity: broadcast_reduce_op_index.cc argmax_channel."""
    return jnp.argmax(data, axis=1).astype(jnp.float32)


# ------------------------------------------------------ legacy tail 2 ------

@register("add_n", aliases=("ElementWiseSum", "_sum"))
def _add_n(*args, num_args=None):
    """parity: tensor/elemwise_sum.cc — sum of N tensors in one op."""
    out = args[0]
    for a in args[1:]:
        out = out + a
    return out


@register("moments", num_outputs=2)
def _moments(data, axes=(), keepdims=False):
    """parity: nn/moments.cc — (mean, variance) over `axes` in one pass."""
    ax = tuple(axes) if axes else None
    mean = jnp.mean(data, axis=ax, keepdims=keepdims)
    mk = mean if keepdims or ax is None else jnp.expand_dims(mean, ax)
    var = jnp.mean(jnp.square(data - jnp.reshape(mk, mk.shape)), axis=ax,
                   keepdims=keepdims)
    return mean, var


@register("softmax_cross_entropy")
def _softmax_cross_entropy(data, label):
    """parity: loss_binary_op.cc softmax_cross_entropy — summed CE of
    softmax(data) against integer labels."""
    logp = jax.nn.log_softmax(data, axis=-1)
    picked = jnp.take_along_axis(
        logp, label.astype(jnp.int32)[..., None], axis=-1)
    return -jnp.sum(picked)


@register("_histogram", num_outputs=2, differentiable=False,
          aliases=("histogram",))
def _histogram_op(data, bins=None, bin_cnt=10, range=None):
    """parity: tensor/histogram.cc — counts + bin edges. `bins` may be an
    explicit edge tensor (second input in the reference); otherwise
    `bin_cnt` uniform bins over `range` (defaults to data min/max)."""
    if bins is not None:
        edges = bins
        hist = jnp.histogram(data.reshape(-1), bins=edges)[0]
        return hist, edges
    lo, hi = (range if range is not None
              else (jnp.min(data), jnp.max(data)))
    hist, edges = jnp.histogram(data.reshape(-1), bins=int(bin_cnt),
                                range=(lo, hi))
    return hist, edges


@register("col2im")
def _col2im(data, output_size=(), kernel=(), stride=(1, 1), dilate=(1, 1),
            pad=(0, 0)):
    """parity: nn/im2col.cc col2im — fold sliding-window columns back into
    the image by summing overlaps (the transpose of im2col)."""
    n, ckk, l = data.shape
    kh, kw = kernel
    c = ckk // (kh * kw)
    oh, ow = output_size
    sh, sw = stride
    dh, dw = dilate
    ph, pw = pad
    hpad, wpad = oh + 2 * ph, ow + 2 * pw
    out_h = (hpad - (dh * (kh - 1) + 1)) // sh + 1
    out_w = (wpad - (dw * (kw - 1) + 1)) // sw + 1
    cols = data.reshape(n, c, kh, kw, out_h, out_w)
    img = jnp.zeros((n, c, hpad, wpad), data.dtype)
    for i in range(kh):
        for j in range(kw):
            img = img.at[:, :, i * dh:i * dh + sh * out_h:sh,
                         j * dw:j * dw + sw * out_w:sw].add(
                cols[:, :, i, j])
    return img[:, :, ph:ph + oh, pw:pw + ow]


@register("_slice_assign")
def _slice_assign(lhs, rhs, begin=(), end=(), step=()):
    """parity: matrix_op.cc _slice_assign — functional slice write (the
    NDArray setitem fast path)."""
    idx = tuple(slice(b if b is not None else None,
                      e if e is not None else None,
                      s if s else None)
                for b, e, s in zip(begin, end,
                                   step or (None,) * len(begin)))
    return lhs.at[idx].set(rhs)


@register("_slice_assign_scalar")
def _slice_assign_scalar(lhs, scalar=0.0, begin=(), end=(), step=()):
    idx = tuple(slice(b if b is not None else None,
                      e if e is not None else None,
                      s if s else None)
                for b, e, s in zip(begin, end,
                                   step or (None,) * len(begin)))
    return lhs.at[idx].set(scalar)


@register("_scatter_set_nd")
def _scatter_set_nd(lhs, rhs, indices):
    """parity: indexing_op.cc _scatter_set_nd — advanced-index write."""
    return lhs.at[tuple(indices.astype(jnp.int32))].set(rhs)


@register("_rnn_param_concat")
def _rnn_param_concat(*args, dim=0, num_args=None):
    """parity: rnn.cc _rnn_param_concat — flat fused-parameter pack."""
    return jnp.concatenate([a.reshape(-1) if dim == 0 else a for a in args],
                           axis=0 if dim == 0 else dim)


@register("_identity_with_attr_like_rhs")
def _identity_with_attr_like_rhs(lhs, rhs):
    """parity: elemwise_unary_op_basic.cc — identity of lhs, shape/stype
    attrs borrowed from rhs during inference (shapes already agree here)."""
    return lhs


@register("cast_storage", eager=True)
def _cast_storage(data, stype="default"):
    """parity: tensor/cast_storage.cc. Dense XLA buffers back every
    storage type here (ndarray/sparse.py wraps them in the row_sparse/csr
    view classes at the NDArray layer); the op is the dense identity."""
    return data


# legacy internal creation-op names (init_op.cc registrations; the public
# nd.zeros/ones/arange route here too)

@register("_zeros", differentiable=False,
          aliases=("_zeros_without_dtype",))
def _zeros_op(shape=(), dtype="float32", ctx=None):
    from ..base import canonical_dtype

    return jnp.zeros(tuple(shape), canonical_dtype(dtype or "float32"))


@register("_ones", differentiable=False)
def _ones_op(shape=(), dtype="float32", ctx=None):
    from ..base import canonical_dtype

    return jnp.ones(tuple(shape), canonical_dtype(dtype or "float32"))


@register("_full", differentiable=False)
def _full_op(shape=(), value=0.0, dtype="float32", ctx=None):
    from ..base import canonical_dtype

    return jnp.full(tuple(shape), value, canonical_dtype(dtype or "float32"))


@register("_arange", differentiable=False)
def _arange_op(start=0.0, stop=None, step=1.0, repeat=1, infer_range=False,
               dtype="float32", ctx=None):
    from ..base import canonical_dtype

    out = jnp.arange(start, stop, step, canonical_dtype(dtype))
    if repeat > 1:
        out = jnp.repeat(out, repeat)
    return out


@register("_linspace", differentiable=False)
def _linspace_op(start=0.0, stop=1.0, num=50, endpoint=True,
                 dtype="float32", ctx=None):
    from ..base import canonical_dtype

    return jnp.linspace(start, stop, int(num),
                        endpoint=endpoint).astype(canonical_dtype(dtype))


@register("_sparse_retain")
def _sparse_retain_op(data, indices):
    """parity: sparse_retain.cc — keep only the listed rows (dense
    emitter; the NDArray layer keeps row_sparse structure)."""
    mask = jnp.zeros((data.shape[0],), bool).at[
        indices.astype(jnp.int32)].set(True)
    return jnp.where(mask.reshape((-1,) + (1,) * (data.ndim - 1)), data,
                     jnp.zeros_like(data))
