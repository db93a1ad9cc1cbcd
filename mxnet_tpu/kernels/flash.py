"""Flash attention — blocked online-softmax attention (registry family
``flash_attention``).

Migrated from ``ops/pallas_ops.py`` (PR 8); that module is now the
op-registration shim calling :func:`mxnet_tpu.kernels.dispatch`.
Forward runs the Pallas kernel (VMEM-blocked, MXU matmuls per tile, the
(S, S) score matrix never materializes in HBM) and also writes each
query row's log-sum-exp. A head whose sequence fits one tile (up to 1024
positions a side) is one plain softmax, several such heads to a program;
a longer one carries the online softmax from k block to k block. The
backward is a family of its own, ``flash_attention_bwd``: one fused
Pallas call (two, dK/dV then dQ, where a head's dQ does not fit VMEM)
that recomputes the probabilities tile by tile from ``(q, k, v, out, lse,
d_out)``; its XLA side is the gradient of the dense reference. Forward and
backward alike feed the MXU operands in the inputs' dtype and keep scores,
statistics and sums float32, and when causal neither compute nor fetch a
block pair above the diagonal. Training memory stays O(S*block) end to
end wherever the kernels run. Both families pick their blocks from the
shape, here and nowhere else (:func:`default_blocks`,
:func:`backward_blocks`); a caller names a pair only to force a tile.

Tolerance vs the XLA baseline (dense softmax reference): f32 inputs
agree to rtol=2e-5/atol=2e-5 — the kernel accumulates in f32 exactly
like the reference but reassociates the softmax normalizer across k
blocks, so parity is close-but-not-bitwise. bf16 inputs: ``p`` is rounded
to bf16 before ``p @ v`` (as the reference rounds it), the output within
1e-2 of the largest |out| of the float32 dense softmax of the same
rounded inputs, the log-sum-exp at 2e-5 (tests/test_pallas.py,
tests/test_kernels.py and tests/test_text_model.py assert these bounds).
"""
from __future__ import annotations

import functools as _functools

import jax
import jax.numpy as jnp

__all__ = ["flash_attention_reference", "flash_forward",
           "flash_forward_lse", "flash_backward_kernel", "row_log_sum_exp",
           "default_blocks", "backward_blocks"]


def _dense_keep(qlen, klen, window):
    """The causal mask, and inside it the band of ``window`` keys a query
    sees (its own position and the ``window - 1`` before it)."""
    keep = jnp.tril(jnp.ones((qlen, klen), bool))
    if window is not None:
        keep &= ~jnp.tril(jnp.ones((qlen, klen), bool), -int(window))
    return keep


def _per_query_head(q, t):
    """Keys or values ``t`` with fewer heads than ``q``, one copy a query
    head (the dense oracle's way; the kernels read a shared head through
    their index maps instead)."""
    group = q.shape[1] // t.shape[1]
    return t if group == 1 else jnp.repeat(t, group, axis=1)


def flash_attention_reference(q, k, v, scale, causal, window=None):
    """Dense attention oracle (and the XLA dispatch baseline)."""
    k, v = _per_query_head(q, k), _per_query_head(q, v)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        s = jnp.where(_dense_keep(s.shape[-2], s.shape[-1], window), s,
                      -jnp.inf)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def row_log_sum_exp(q, k, scale, causal, window=None):
    """The dense oracle of the forward's second result: float32
    log-sum-exp of each query row's scaled, masked scores."""
    k = _per_query_head(q, k)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        s = jnp.where(_dense_keep(s.shape[-2], s.shape[-1], window), s,
                      -jnp.inf)
    return jax.nn.logsumexp(s, axis=-1)


_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _dot(a, b, dims):
    """An MXU product with a float32 sum. Operands narrower than float32
    are multiplied as they are: a precision asked of float32 matmuls
    (``jax.default_matmul_precision``) means nothing for them, and Mosaic
    refuses a bf16 product at ``highest``."""
    return jax.lax.dot_general(
        a, b, dims, preferred_element_type=jnp.float32,
        precision=None if a.dtype == jnp.float32
        else jax.lax.Precision.DEFAULT)


def _causal_keep(qi, ki, block_q, block_k, q_axis, window=None):
    """The mask of one tile, queries along ``q_axis``: a query sees the
    keys at or before its own position, and with a ``window`` only the
    last ``window`` of them."""
    shape = (block_q, block_k) if q_axis == 0 else (block_k, block_q)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                    1 - q_axis)
    if window is None:
        return q_pos >= k_pos
    return jnp.logical_and(q_pos >= k_pos, q_pos - k_pos < window)


def _on_causal_tiles(tile, causal, qi, ki, block_q, block_k, window=None):
    """Run ``tile(masked)`` for this (q block, k block) pair: not at all
    where the whole pair lies above the diagonal (or, with a ``window``,
    wholly before the band), with the mask only where the diagonal (or
    the band's far edge) crosses it."""
    from jax.experimental import pallas as pl

    if not causal:
        tile(False)
        return
    below = qi * block_q >= (ki + 1) * block_k - 1
    reached = (qi + 1) * block_q > ki * block_k
    if window is not None:
        # every pair of the tile inside the band / some pair inside it
        below = jnp.logical_and(
            below, (qi + 1) * block_q - 1 - ki * block_k < window)
        reached = jnp.logical_and(
            reached, qi * block_q - ((ki + 1) * block_k - 1) < window)
    pl.when(below)(lambda: tile(False))
    pl.when(jnp.logical_and(reached, jnp.logical_not(below)))(
        lambda: tile(True))


def _causal_maps(causal, block_q, block_k, window=None, n_qb=None):
    """``(q_of, k_of)``, the block a grid step ``(q block i, k block j)``
    names: when causal, the first q block that reaches k block j and the
    last k block that q block i reaches, so that a step above the
    diagonal keeps the block index of the nearest pair that is not, and an
    unchanged index moves nothing. A ``window`` clamps the other side the
    same way: to the last of the ``n_qb`` q blocks that still sees k block
    j, and the first k block inside q block i's band."""
    if not causal:
        return (lambda i, j: i), (lambda i, j: j)
    if window is None:
        return (lambda i, j: jnp.maximum(i, (j * block_k) // block_q),
                lambda i, j: jnp.minimum(
                    j, ((i + 1) * block_q - 1) // block_k))
    return (lambda i, j: jnp.clip(
                i, (j * block_k) // block_q,
                jnp.minimum(((j + 1) * block_k + window - 2) // block_q,
                            n_qb - 1)),
            lambda i, j: jnp.clip(
                j, jnp.maximum(i * block_q - (window - 1), 0) // block_k,
                ((i + 1) * block_q - 1) // block_k))


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *carry, scale, causal,
                  block_q, block_k, n_kb, window=None):
    """One (heads, q-block, k-block) program over ``q_ref.shape[0]`` heads
    of the ``batch*head`` axis. Both matmuls take their operands in the
    inputs' dtype (``_dot``); scores, running max, normaliser, ``exp`` and
    the sum are float32, and ``p`` is rounded to the values' dtype before
    ``p @ v``. A sequence that is one k block (``n_kb == 1``) is a plain
    softmax and carries nothing. A longer one is the FlashAttention
    recurrence: the TPU grid iterates its LAST dimension sequentially, so
    the online-softmax state ``carry`` = (m, l, acc) passes from k block
    to k block in VMEM scratch and only (block, d) tiles ever live there,
    whatever the sequence length. When causal, a tile above the diagonal
    is not computed (nor fetched: the caller clamps its index maps) and
    only a tile the diagonal crosses is masked; with a ``window`` the
    same holds at the band's far edge. The last k block writes
    the output and the rows' log-sum-exp ``m + log l``, one lane-major row
    of ``block_q`` values a head, which is all the backward needs to
    recompute a probability."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    heads = range(q_ref.shape[0])

    def finish(g, m, l, acc):
        l = jnp.maximum(l, 1e-30)
        o_ref[g] = (acc / l).astype(o_ref.dtype)
        lse_ref[g, 0] = _column_to_row(m + jnp.log(l))

    if carry:
        m_ref, l_ref, acc_ref = carry

        @pl.when(ki == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(masked):
        keep = _causal_keep(qi, ki, block_q, block_k, 0, window) \
            if masked else None
        for g in heads:
            v = v_ref[g]
            s = _dot(q_ref[g], k_ref[g], _NT) * scale
            if masked:
                s = jnp.where(keep, s, -jnp.inf)
            m_new = s.max(axis=-1, keepdims=True)
            if not carry:
                p = jnp.exp(s - m_new)
                finish(g, m_new, p.sum(axis=-1, keepdims=True),
                       _dot(p.astype(v.dtype), v, _NN))
                continue
            m = m_ref[g]
            m_new = jnp.maximum(m, m_new)
            shift = m_new
            if window is not None:
                # a row may see no key of the first tiles its block runs
                # (the band starts inside them): its maximum is still
                # -inf, and -inf - -inf is no number
                shift = jnp.where(m_new == -jnp.inf, 0.0, m_new)
            alpha = jnp.exp(m - shift)
            p = jnp.exp(s - shift)
            m_ref[g] = m_new
            l_ref[g] = l_ref[g] * alpha + p.sum(axis=-1, keepdims=True)
            acc_ref[g] = acc_ref[g] * alpha + _dot(p.astype(v.dtype), v, _NN)

    _on_causal_tiles(tile, causal, qi, ki, block_q, block_k, window)

    if carry:
        @pl.when(ki == n_kb - 1)
        def _finish():
            for g in heads:
                finish(g, m_ref[g], l_ref[g], acc_ref[g])


def _column_to_row(col):
    """``(n, 1)`` -> ``(1, n)``: one value a sublane to one value a lane."""
    return col.reshape(1, col.shape[0])


def _row_to_column(row_ref):
    """A ``(1, 1, 1, n)`` block of row statistics as an ``(n, 1)``
    column."""
    return jnp.expand_dims(row_ref[0, 0, 0], -1)


def _rows(stat, block):
    """Row statistics ``(bh, s)`` in the layout the kernels read and write
    them in: ``(bh, s // block, 1, block)``, so that a block's last two
    dimensions are the array's own and any block size is a legal tile."""
    bh, s = stat.shape
    return stat.reshape(bh, s // block, 1, block)


def flash_forward(q, k, v, scale, causal, block_q, block_k,
                  interpret=False, window=None):
    return flash_forward_lse(q, k, v, scale, causal, block_q, block_k,
                             interpret, window=window)[0]


def _group(q, k):
    """Query heads a key (and value) head: 1, or the grouped keys'."""
    return q.shape[1] // k.shape[1]


def _kv_row(group):
    """The row of the ``batch * key head`` axis that row ``i`` of the
    ``batch * query head`` axis reads: with ``h = hk * group`` heads,
    ``b * h + head`` -> ``b * hk + head // group`` is ``i // group``."""
    return (lambda i: i) if group == 1 else (lambda i: i // group)


def flash_forward_lse(q, k, v, scale, causal, block_q, block_k,
                      interpret=False, heads=None, window=None):
    """``(out, lse)``: the attention output ``(b, h, sq, dv)`` and the
    float32 log-sum-exp of each query row's scaled, masked scores
    ``(b, h, sq)``, both results of one ``pallas_call`` (the output
    first: a trace names a call after its first result). ``heads`` of the
    ``b * h`` axis go to one program: :func:`heads_a_program`'s, unless
    the caller forces a number (the sweep). Keys and values may have
    fewer heads than the queries (grouped keys): a program then takes one
    query head and reads its group's key head through the index map, and
    nothing is repeated in HBM. ``window`` (causal only) is the number of
    keys a query sees, its own position included."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[3]
    bh, group = b * h, _group(q, k)
    heads = heads or (heads_a_program(bh, sq, sk, block_q, block_k)
                      if group == 1 else 1)
    q3 = q.reshape(bh, sq, d)
    k3 = k.reshape(bh // group, sk, d)
    v3 = v.reshape(bh // group, sk, dv)
    n_kb = sk // block_k
    _, k_of = _causal_maps(causal, block_q, block_k, window,
                           sq // block_q)
    kv = _kv_row(group)
    f32 = jnp.float32
    out, lse = pl.pallas_call(
        _functools.partial(_flash_kernel, scale=scale, causal=causal,
                           block_q=block_q, block_k=block_k, n_kb=n_kb,
                           window=window),
        grid=(bh // heads, sq // block_q, n_kb),
        in_specs=[
            pl.BlockSpec((heads, block_q, d), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((heads, block_k, d),
                         lambda i, j, kk: (kv(i), k_of(j, kk), 0)),
            pl.BlockSpec((heads, block_k, dv),
                         lambda i, j, kk: (kv(i), k_of(j, kk), 0)),
        ],
        out_specs=[
            pl.BlockSpec((heads, block_q, dv), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((heads, 1, 1, block_q),
                         lambda i, j, kk: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, sq // block_q, 1, block_q), f32),
        ],
        # the online softmax's state, where there is a next k block
        scratch_shapes=[pltpu.VMEM((heads, block_q, 1), f32),
                        pltpu.VMEM((heads, block_q, 1), f32),
                        pltpu.VMEM((heads, block_q, dv), f32)] * (n_kb > 1),
        interpret=interpret,
    )(q3, k3, v3)
    return out.reshape(b, h, sq, dv), lse.reshape(b, h, sq)


# ---- the backward as kernels -----------------------------------------

# A head's dQ may stay in VMEM while its k blocks pass (the fused call) up
# to this many bytes of float32 sum and double-buffered result (8 a bf16
# value): 4,096 positions x 192 take 6 MiB beside ~8 MiB of tiles at 512 x
# 512, inside the 16 MiB of scoped VMEM.
_FUSED_DQ_BYTES = 8 * 2 ** 20


def _tile_p_ds(x, y, dx, dy, lse, dvec, scale, keep):
    """Probabilities and score gradients of one tile, in either
    orientation: ``p = exp(x y^T * scale - lse)`` and ``ds = p * (dx dy^T
    - D)`` (the factor ``scale`` of ``ds`` is applied once, to the
    accumulated product). Matmul operands keep the inputs' dtype, the
    tile is float32; ``keep`` is the causal mask of a tile the diagonal
    crosses, None elsewhere. ``lse`` is finite (every row sees a key), so
    a masked score gives exactly 0."""
    s = _dot(x, y, _NT) * scale
    if keep is not None:
        s = jnp.where(keep, s, -jnp.inf)
    p = jnp.exp(s - lse)
    return p, p * (_dot(dx, dy, _NT) - dvec)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dvec_ref,
                      dk_ref, dv_ref, *rest, scale, causal, block_q, block_k,
                      n_qb, n_kb, window=None):
    """One (batch*head, k-block, q-block) program of dK and dV; q blocks
    are the sequential dimension, the two sums live in VMEM scratch. The
    tile is held TRANSPOSED, ``(block_k, block_q)``: the rows' statistics
    then broadcast along sublanes as the lane-major rows they are stored
    as, and the matmuls are plain ``a @ b`` / ``a @ b.T``.

    FUSED (``rest`` = dq_ref, dk_acc, dv_acc, dq_acc; else dk_acc, dv_acc):
    the same tile also gives its rows of dQ, ``dS^T^T k``, summed over the
    k blocks in a float32 scratch that holds the head's WHOLE sequence and
    written out, through a block as long, while the last k block passes.
    Scores and their gradient are then computed once, not once a call."""
    from jax.experimental import pallas as pl

    dq_ref, dk_acc, dv_acc, dq_acc = \
        rest if len(rest) == 4 else (None,) + rest + (None,)
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if dq_acc is not None:
        @pl.when(ki == 0)
        def _init_dq():
            dq_acc[rows, :] = jnp.zeros((block_q, dq_acc.shape[1]),
                                        dq_acc.dtype)

    def tile(masked):
        q, do, k = q_ref[0], do_ref[0], k_ref[0]
        keep = _causal_keep(qi, ki, block_q, block_k, 1, window) \
            if masked else None
        p, ds = _tile_p_ds(k, q, v_ref[0], do, lse_ref[0, 0],
                           dvec_ref[0, 0], scale, keep)
        ds = ds.astype(q.dtype)
        dv_acc[...] += _dot(p.astype(do.dtype), do, _NN)
        dk_acc[...] += _dot(ds, q, _NN)
        if dq_acc is not None:
            dq_acc[rows, :] += _dot(ds, k, _TN)

    _on_causal_tiles(tile, causal, qi, ki, block_q, block_k, window)

    @pl.when(qi == n_qb - 1)
    def _finish():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    if dq_acc is not None:
        @pl.when(ki == n_kb - 1)
        def _finish_dq():
            dq_ref[0, rows, :] = (dq_acc[rows, :] * scale).astype(
                dq_ref.dtype)


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dvec_ref,
                     dq_ref, dq_acc, *, scale, causal, block_q, block_k,
                     n_kb, window=None):
    """One (batch*head, q-block, k-block) program of dQ; k blocks are the
    sequential dimension. The tile is ``(block_q, block_k)`` as in the
    forward, so the rows' statistics turn into columns here."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def tile(masked):
        k = k_ref[0]
        keep = _causal_keep(qi, ki, block_q, block_k, 0, window) \
            if masked else None
        _, ds = _tile_p_ds(q_ref[0], k, do_ref[0], v_ref[0],
                           _row_to_column(lse_ref),
                           _row_to_column(dvec_ref), scale, keep)
        dq_acc[...] += _dot(ds.astype(k.dtype), k, _NN)

    _on_causal_tiles(tile, causal, qi, ki, block_q, block_k, window)

    @pl.when(ki == n_kb - 1)
    def _finish():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def flash_backward_kernel(q, k, v, out, lse, cot, scale, causal, block_q,
                          block_k, interpret=False, fused=None, window=None):
    """``(dq, dk, dv)`` from the forward's operands, output and row
    log-sum-exp: ``D = rowsum(d_out * out)`` in plain JAX, then ONE Pallas
    call for all three where a head's dQ fits VMEM beside the tiles
    (``_FUSED_DQ_BYTES``; ``fused`` overrides the rule, for the tests and
    the sweep), else one for dK and dV and one for dQ, which computes the
    scores and their gradient a second time but holds one block of dQ.
    The first result of either call is dK or dQ, as wide as the keys: a
    trace names a call after its first result, and only the forward's is
    the attention output's shape. When causal, a block pair above the
    diagonal is neither computed nor fetched: its grid step keeps the
    block index of the nearest pair that is, and an unchanged index moves
    nothing; a ``window`` bounds the pairs on the other side in the same
    way. With grouped keys every query head reads its group's key head
    through the index map and writes its own float32 dK and dV, which are
    summed over the group afterwards: nothing is repeated on the way in.

    The fused call measured 26 % under the two at 4,096 positions and
    14 % at 384 (``backward_blocks`` has the sweep)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[3]
    bh, n_qb, n_kb = b * h, sq // block_q, sk // block_k
    group = _group(q, k)
    q3, k3 = q.reshape(bh, sq, d), k.reshape(bh // group, sk, d)
    v3, do3 = v.reshape(bh // group, sk, dv), cot.reshape(bh, sq, dv)
    f32 = jnp.float32
    dvec = (cot.astype(f32) * out.astype(f32)).sum(-1)
    lse4 = _rows(lse.reshape(bh, sq).astype(f32), block_q)
    dvec4 = _rows(dvec.reshape(bh, sq), block_q)
    if fused is None:
        fused = sq * d * (4 + 2 * q.dtype.itemsize) <= _FUSED_DQ_BYTES
    static = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, n_kb=n_kb, window=window)

    q_of, k_of = _causal_maps(causal, block_q, block_k, window, n_qb)
    kv = _kv_row(group)

    def specs(q_at, k_at):
        return [
            pl.BlockSpec((1, block_q, d), lambda *g: (g[0], q_at(*g), 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda *g: (kv(g[0]), k_at(*g), 0)),
            pl.BlockSpec((1, block_k, dv),
                         lambda *g: (kv(g[0]), k_at(*g), 0)),
            pl.BlockSpec((1, block_q, dv), lambda *g: (g[0], q_at(*g), 0)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda *g: (g[0], q_at(*g), 0, 0)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda *g: (g[0], q_at(*g), 0, 0)),
        ]

    operands = (q3, k3, v3, do3, lse4, dvec4)
    dq_shape = jax.ShapeDtypeStruct((bh, sq, d), q.dtype)
    # grid (batch*head, k block, q block); fused, dQ is its third result
    dk, dv_, *dq = pl.pallas_call(
        _functools.partial(_flash_dkv_kernel, n_qb=n_qb, **static),
        grid=(bh, n_kb, n_qb),
        in_specs=specs(lambda i, j, qq: q_of(qq, j), lambda i, j, qq: j),
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, j, qq: (i, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda i, j, qq: (i, j, 0)),
        ] + [pl.BlockSpec((1, sq, d), lambda i, j, qq: (i, 0, 0))] * fused,
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype if group == 1 else f32),
            jax.ShapeDtypeStruct((bh, sk, dv),
                                 v.dtype if group == 1 else f32)]
        + [dq_shape] * fused,
        scratch_shapes=[pltpu.VMEM((block_k, d), f32),
                        pltpu.VMEM((block_k, dv), f32)]
        + [pltpu.VMEM((sq, d), f32)] * fused,
        interpret=interpret,
    )(*operands)
    if not fused:
        # grid (batch*head, q block, k block)
        dq = [pl.pallas_call(
            _functools.partial(_flash_dq_kernel, **static),
            grid=(bh, n_qb, n_kb),
            in_specs=specs(lambda i, j, kk: j,
                           lambda i, j, kk: k_of(j, kk)),
            out_specs=pl.BlockSpec((1, block_q, d),
                                   lambda i, j, kk: (i, j, 0)),
            out_shape=dq_shape,
            scratch_shapes=[pltpu.VMEM((block_q, d), f32)],
            interpret=interpret,
        )(*operands)]
    if group > 1:
        dk, dv_ = (t.reshape(bh // group, group, sk, -1).sum(1).astype(
            like.dtype) for t, like in ((dk, k), (dv_, v)))
    return (dq[0].reshape(q.shape), dk.reshape(k.shape),
            dv_.reshape(v.shape))


@_functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret, window):
    return flash_forward(q, k, v, scale, causal, block_q, block_k,
                         interpret, window)


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret, window):
    out, lse = flash_forward_lse(q, k, v, scale, causal, block_q, block_k,
                                 interpret, window=window)
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, window, res, cot):
    """The backward is a dispatch of its own (``flash_attention_bwd``), at
    blocks of its own. A forward that ran in the interpreter asks for the
    same; on the chip the table, or the family's default, decides."""
    from . import dispatch

    del block_q, block_k  # the forward's
    return dispatch("flash_attention_bwd", *res, cot, scale, causal=causal,
                    window=window, interpret=True if interpret else None)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---- the blocks, from the shape ---------------------------------------
#
# Queries and keys share one head width ``d``; values (and so the output)
# may have their own, ``dv`` (latent attention: 192 | 128).

# The longest side of a forward tile, and what a Mosaic call may take of
# the 16 MiB of scoped VMEM a v5e gives it, by ``_forward_vmem_bytes``.
_BLOCK_CAP = 1024
_VMEM_BUDGET = 15 * 2 ** 20


def _forward_vmem_bytes(block_q, block_k, d, dv, itemsize):
    """VMEM the forward call takes at a tile, fitted to what Mosaic
    allocated at the shapes compiled for a v5e (PR 29; never under the
    least limit a compile passed with, at most 2 MiB over): the q, out, k
    and v tiles double-buffered, a row padded to whole lanes; 4.25 bytes
    a score (one float32 tile live, not two); the float32 sum and five
    (block_q, 1) columns of statistics, a column padded to 128 lanes; and
    6 bytes an element of q where float32 operands are split into bf16
    parts for the MXU."""
    def lanes(width):
        return -(-width // 128) * 128

    tiles = 2 * itemsize * (block_q + block_k) * (lanes(d) + lanes(dv))
    scores = 17 * block_q * block_k // 4
    state = 4 * block_q * (lanes(dv) + 5 * 128)
    split = 6 * block_q * lanes(d) * (itemsize == 4)
    return tiles + scores + state + split


def default_blocks(sq, sk, d, dv, itemsize=2):
    """``(block_q, block_k)`` of the forward kernel, from the shape alone
    (``itemsize``: bytes of an operand's element; bf16 unless said). A
    side of up to 1024 positions is ONE block (BERT's 384 x 384: K and V
    read once, no online-softmax state carried from program to program);
    a longer one takes the largest power of two up to 1024 that divides
    it; while the tile does not fit VMEM (``_forward_vmem_bytes``: wide
    heads, float32 operands) the longer side steps down, to 128 at the
    least. A side no multiple of 128 divides gets 128, which
    ``_supports`` refuses. The backward kernels take their own
    (``backward_blocks``).

    Measured on a v5e, bf16, the forward alone in a jit, ms of the Mosaic
    call in a device trace (my chip run, PR 29; ``benchmark/opperf.py
    --flash-sweep``; in brackets PR 28's kernel, float32 operands, every
    executed tile masked, at the same tile). 32 x 12 heads x 384, d64, no
    mask: 128 x 128 1.85 [1.86], 384 x 128 1.49, 128 x 384 0.64, 384 x 384
    0.51 [0.72], and with 2 / **4** / 8 heads a program 0.41 / **0.36** /
    0.36 (``heads_a_program``); dense XLA 0.57. 2 x 32 heads x 4096,
    192 | 128, causal: 512 x 512 5.65 [6.31], 512 x 1024 4.31, 1024 x 512
    6.33, **1024 x 1024 3.81** [4.05], 1024 x 2048 4.53, 2048 x 1024 out
    of VMEM; dense XLA 9.56. The same with keys and values 128 wide (no
    cell has it): 128 x 128 23.8 [27.8], 512 x 512 4.72, 512 x 1024 3.25,
    **1024 x 1024 2.94** [3.12], 1024 x 2048 3.55; dense XLA 9.42."""
    def sizes(s):  # largest first
        if s % 128:
            return [128]
        top = min(s & -s, _BLOCK_CAP)
        whole = [s] if top < s <= _BLOCK_CAP else []
        return whole + [top >> i for i in range(top.bit_length() - 7)]

    qs, ks = sizes(sq), sizes(sk)
    while (len(qs) + len(ks) > 2 and _forward_vmem_bytes(
            qs[0], ks[0], d, dv, itemsize) > _VMEM_BUDGET):
        (ks if len(qs) == 1 or (len(ks) > 1 and ks[0] >= qs[0])
         else qs).pop(0)
    return qs[0], ks[0]


def heads_a_program(bh, sq, sk, block_q, block_k):
    """How many heads of the ``batch*head`` axis one program of the
    forward takes, from the shape alone: one, unless a head is a single
    tile too small to hide a grid step's fixed cost; then the largest
    power of two up to 4 that divides ``bh`` and keeps the program at the
    1024 x 1024 scores the longest tile has (BERT's 384 x 384: four)."""
    heads = 1
    while ((block_q, block_k) == (sq, sk) and heads < 4
           and bh % (2 * heads) == 0
           and 2 * heads * sq * sk <= _BLOCK_CAP ** 2):
        heads *= 2
    return heads


def _inside_band(blocks, sides, window):
    """A band of ``window`` keys crosses every tile it touches, and a tile
    longer than the band is mostly outside it: each block steps down to
    the largest power of two the band holds, 128 at the least (at window
    512 and 4,096 positions 512 x 512, two tiles a q block, half of each
    inside the band; 1024 x 1024 would run two for a quarter). A side the
    smaller block does not divide keeps the one it has. Measured on a
    v5e, bf16, 20 query heads over 10 key heads, 4,096 positions, 64 |
    128 wide, window 512, device ms of the call alone in a jit, forward /
    fused backward (my chip run, PR 30; ``opperf.py --flash-sweep``): 128
    x 128 3.27 / 5.35, 256 x 256 1.44 / 1.72, 256 x 512 0.95 / 1.17, 512
    x 256 1.36 / 1.20, **512 x 512 0.76 / 0.88**, 1024 x 1024 (the shape's
    own without a window) 0.71 / 1.10. Tiles under the band's width lose
    to the per-tile overhead; the forward's 1024 x 1024 is 0.05 ms a call
    ahead and the backward's 0.22 behind, so one rule serves both."""
    if window is None:
        return blocks
    cap = max(128, 1 << max(int(window).bit_length() - 1, 0))
    return tuple(b if b <= cap or s % cap else cap
                 for b, s in zip(blocks, sides))


def _blocks(q, k, v, block_q=None, block_k=None, window=None):
    """The forward's blocks: the shape's, unless the caller forced a
    tile."""
    sides = (q.shape[2], k.shape[2])
    bq, bk = _inside_band(
        default_blocks(*sides, q.shape[3], v.shape[3],
                       jnp.dtype(q.dtype).itemsize), sides, window)
    return int(block_q or bq), int(block_k or bk)


def backward_blocks(sq, sk, d, dv):
    """``(block_q, block_k)`` of the backward kernels, from the shape alone
    and independent of the forward's: a backward tile keeps four
    (block_q x block_k) float32 arrays live (scores, probabilities, their
    two gradients), so the forward's 1024 x 1024 does not fit the 16 MiB of
    scoped VMEM beside a head's dQ. A sequence of up to 512 is one block
    (BERT's 384: one program a head, nothing re-read); a longer one takes
    the largest power of two up to 512 that divides it. Measured on a v5e,
    bf16, ms a layer alone in a jit, fused call / two calls (my chip run,
    PR 27): 2 x 32 heads x 4096, 192 | 128, causal: 128 x 128 31.6 / 51.4,
    256 x 256 13.6 / 19.3, 256 x 512 11.8 / 16.4, 512 x 256 11.8 / 16.0,
    **512 x 512 10.5 / 14.2**, 1024 x 512 10.5 / 14.0, 1024 x 256 11.2 /
    14.9, 256 x 1024 11.2 / 15.5, 512 x 1024 out of VMEM / 14.3. 32 x 12
    heads x 384, d64, no mask: 128 x 128 3.36 / 4.53, 384 x 128 2.47 /
    2.95, 128 x 384 2.35 / 2.98, **384 x 384 2.04 / 2.37**. Inside the
    cells' steps the fused call reads 7.7 and 0.53 ms."""
    del d, dv  # every width measured takes the same blocks

    def one(s):
        return s if s <= 512 else min(s & -s, 512)

    return one(sq), one(sk)


def _blocks_for(q, k, v, window=None):
    sides = (q.shape[2], k.shape[2])
    return _inside_band(backward_blocks(*sides, q.shape[3], v.shape[3]),
                        sides, window)


# ---- registry wiring -------------------------------------------------

def _kernel(q, k, v, scale, causal=False, block_q=None, block_k=None,
            interpret=False, window=None):
    return _flash(q, k, v, float(scale), bool(causal),
                  *_blocks(q, k, v, block_q, block_k, window),
                  bool(interpret), None if window is None else int(window))


def _xla(q, k, v, scale, causal=False, block_q=None, block_k=None,
         window=None):
    del block_q, block_k  # dense path has no blocking
    return flash_attention_reference(q, k, v, scale, causal, window)


def _pow2(n):
    p = 1
    while p < n:
        p *= 2
    return p


def _bucket(q, k, v, scale, causal=False, block_q=None, block_k=None,
            window=None):
    """Sequence lengths and batch*heads round UP to powers of two (one
    table row covers the whole bucket); head dims and dtype are exact —
    they change the kernel's tiling, not just its trip count. A value
    width of its own is named after the query/key width (``d192v128``);
    equal widths keep the key they always had. The blocks are the ones
    the kernel will run with. Grouped keys and a window are named after
    them (``..._q512k512_g2_w512``); without either the key is what it
    always was."""
    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[3]
    block_q, block_k = _blocks(q, k, v, block_q, block_k, window)
    width = f"d{d}" if dv == d else f"d{d}v{dv}"
    group = _group(q, k)
    return (f"bh{_pow2(b * h)}_sq{_pow2(sq)}_sk{_pow2(sk)}_{width}_"
            f"{jnp.dtype(q.dtype).name}_c{int(bool(causal))}_"
            f"q{block_q}k{block_k}"
            + (f"_g{group}" if group > 1 else "")
            + (f"_w{int(window)}" if window is not None else ""))


def _supports(q, k, v, scale, causal=False, block_q=None, block_k=None,
              window=None):
    """The statically checkable Mosaic constraints, of the forward and of
    the backward it will ask for: rank-4 inputs, keys as wide as the
    queries and as many as the values, both head widths a multiple of 8
    up to 512, S divisible by the forward's blocks, and each of the
    backward's blocks the whole sequence or a multiple of the 128 lanes a
    row of statistics is tiled by. No length the default blocks divide
    fails the last; a forced pair under 128 can (64 on 576 positions),
    and the shape then takes dense XLA forward and backward. Keys and
    values have as many heads as each other, a number that divides the
    queries'; a window is at least one key, of a causal square."""
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        return False
    sq, sk, d, dv = q.shape[2], k.shape[2], q.shape[3], v.shape[3]
    block_q, block_k = _blocks(q, k, v, block_q, block_k, window)
    return (sq % block_q == 0 and sk % block_k == 0
            and k.shape[3] == d and v.shape[2] == sk
            and d % 8 == 0 and 0 < d <= 512
            and dv % 8 == 0 and 0 < dv <= 512
            and k.shape[:2] == v.shape[:2] and k.shape[0] == q.shape[0]
            and q.shape[1] % k.shape[1] == 0
            and (window is None
                 or (bool(causal) and sq == sk and int(window) >= 1))
            and all(b == s or b % 128 == 0 for b, s in zip(
                _blocks_for(q, k, v, window), (sq, sk))))


def _bwd_kernel(q, k, v, out, lse, cot, scale, causal=False,
                interpret=False, window=None):
    return flash_backward_kernel(q, k, v, out, lse, cot, float(scale),
                                 bool(causal), *_blocks_for(q, k, v, window),
                                 bool(interpret), window=window)


def _bwd_xla(q, k, v, out, lse, cot, scale, causal=False, window=None):
    """The gradient of the dense reference: the XLA side of the pair is
    one function, forward and backward. It holds the (S, S) probabilities
    the kernels exist to avoid (2.1 GB a layer at the language model's
    shape), and no default decision takes it: only a table row or
    ``MXNET_TPU_KERNELS=0`` sends a bucket here."""
    del out, lse
    _, vjp = jax.vjp(lambda a, b, c: flash_attention_reference(
        a, b, c, scale, causal, window), q, k, v)
    return vjp(cot)


def _bwd_bucket(q, k, v, out, lse, cot, scale, causal=False, window=None):
    """The forward's key with the backward's own blocks."""
    return _bucket(q, k, v, scale, causal, *_blocks_for(q, k, v, window),
                   window=window)


def _bwd_supports(q, k, v, out, lse, cot, scale, causal=False, window=None):
    """The forward's condition, at the backward's blocks."""
    return _supports(q, k, v, scale, causal, *_blocks_for(q, k, v, window),
                     window=window)


def _register():
    from . import register_kernel

    register_kernel(
        "flash_attention", kernel=_kernel, xla=_xla, bucket=_bucket,
        supports=_supports, default_tpu=True,
        tolerance="f32 rtol=2e-5 atol=2e-5 vs dense softmax (softmax "
                  "normalizer reassociated across k blocks); bf16 operands "
                  "(p rounded to bf16 before p @ v; scores, statistics and "
                  "sums float32): within 1e-2 of the largest |out| of the "
                  "float32 dense softmax of the same rounded inputs, the "
                  "rows' log-sum-exp at 2e-5")
    register_kernel(
        "flash_attention_bwd", kernel=_bwd_kernel, xla=_bwd_xla,
        bucket=_bwd_bucket, supports=_bwd_supports, default_tpu=True,
        tolerance="f32 rtol=2e-4 atol=2e-5 vs the dense gradient in the "
                  "interpreter (256 positions); compiled, at 1024 positions "
                  "and highest precision, within 1e-4 of the largest "
                  "|gradient|; bf16 operands (p and dS "
                  "rounded to bf16 before their matmuls, float32 sums): "
                  "within 2e-2 of the largest |gradient| of the float32 "
                  "dense gradient of the same rounded inputs")


_register()
