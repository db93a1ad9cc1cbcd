"""Flash attention — blocked online-softmax attention (registry family
``flash_attention``).

Migrated verbatim from ``ops/pallas_ops.py`` (PR 8); that module is now
the op-registration shim calling :func:`mxnet_tpu.kernels.dispatch`.
Forward runs the Pallas kernel (VMEM-blocked, MXU matmuls per tile, the
(S, S) score matrix never materializes in HBM); backward is the blocked
flash recurrence in pure JAX (custom_vjp recomputing probabilities
tile-by-tile), so training memory stays O(S*block) end to end.

Tolerance vs the XLA baseline (dense softmax reference): f32 inputs
agree to rtol=2e-5/atol=2e-5 — the kernel accumulates in f32 exactly
like the reference but reassociates the softmax normalizer across k
blocks, so parity is close-but-not-bitwise (tests/test_pallas.py and
tests/test_kernels.py assert these bounds).
"""
from __future__ import annotations

import functools as _functools

import jax
import jax.numpy as jnp

__all__ = ["flash_attention_reference", "flash_forward"]


def flash_attention_reference(q, k, v, scale, causal):
    """Dense attention oracle (and the XLA dispatch baseline)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        qlen, klen = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((qlen, klen), bool))
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale, causal, block_q, block_k, n_kb):
    """One (batch*head, q-block, k-block) program. The TPU grid iterates
    its LAST dimension sequentially, so the online-softmax state (m, l,
    acc) carries across k blocks in VMEM scratch — only (block, d) tiles
    ever live in VMEM, whatever the sequence length (the FlashAttention
    recurrence)."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def compute():
        q = q_ref[0].astype(jnp.float32)  # (block_q, d)
        k_blk = k_ref[0].astype(jnp.float32)  # (block_k, d)
        v_blk = v_ref[0].astype(jnp.float32)  # (block_k, dv)
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        m = m_ref[...]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # blocks entirely above the diagonal contribute nothing
        @pl.when(ki * block_k < (qi + 1) * block_q)
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == n_kb - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_forward(q, k, v, scale, causal, block_q, block_k,
                  interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[3]
    bh = b * h
    q3 = q.reshape(bh, sq, d)
    k3 = k.reshape(bh, sk, d)
    v3 = v.reshape(bh, sk, dv)
    n_kb = sk // block_k
    grid = (bh, sq // block_q, n_kb)
    kernel = _functools.partial(_flash_kernel, scale=scale, causal=causal,
                                block_q=block_q, block_k=block_k,
                                n_kb=n_kb)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, kk, 0)),
            pl.BlockSpec((1, block_k, dv), lambda i, j, kk: (i, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dv), lambda i, j, kk: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        interpret=interpret,
    )(q3, k3, v3)
    return out.reshape(b, h, sq, dv)


def _causal_mask(s, qi, ci, bq, bk):
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = ci * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(q_pos >= k_pos, s, -jnp.inf)


def _flash_backward(q, k, v, out, cot, scale, causal, bq, bk):
    """Blocked flash backward (FlashAttention eq. 13-16) in pure JAX:
    probabilities are recomputed per (q-block, k-block) tile, so live
    memory stays O(S * block) — no (S, S) tensor ever exists, matching
    the forward kernel's memory contract for training too."""
    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[3]
    nbq, nbk = sq // bq, sk // bk
    f32 = jnp.float32

    def per_head(q2, k2, v2, o2, do2):
        qb = q2.reshape(nbq, bq, d).astype(f32)
        kb = k2.reshape(nbk, bk, d).astype(f32)
        vb = v2.reshape(nbk, bk, dv).astype(f32)
        dob = do2.reshape(nbq, bq, dv).astype(f32)
        Dvec = (do2.astype(f32) * o2.astype(f32)).sum(-1).reshape(nbq, bq)

        # pass 1: per-row max and normalizer (scan over k blocks)
        def ml_one(qi, qblk):
            def step(carry, kc):
                m, l = carry
                kcblk, ci = kc
                s = qblk @ kcblk.T * scale
                if causal:
                    s = _causal_mask(s, qi, ci, bq, bk)
                m_new = jnp.maximum(m, s.max(-1))
                l = l * jnp.exp(m - m_new) + \
                    jnp.exp(s - m_new[:, None]).sum(-1)
                return (m_new, l), None

            init = (jnp.full((bq,), -jnp.inf, f32), jnp.zeros((bq,), f32))
            (m, l), _ = jax.lax.scan(step, init,
                                     (kb, jnp.arange(nbk)))
            return m, jnp.maximum(l, 1e-30)

        m, l = jax.vmap(ml_one)(jnp.arange(nbq), qb)

        # dq: per q block, accumulate over k blocks
        def dq_one(qi, qblk, doblk, mrow, lrow, Drow):
            def step(acc, kc):
                kcblk, vcblk, ci = kc
                s = qblk @ kcblk.T * scale
                if causal:
                    s = _causal_mask(s, qi, ci, bq, bk)
                p = jnp.exp(s - mrow[:, None]) / lrow[:, None]
                dp = doblk @ vcblk.T
                ds = p * (dp - Drow[:, None])
                return acc + ds @ kcblk * scale, None

            acc, _ = jax.lax.scan(step, jnp.zeros((bq, d), f32),
                                  (kb, vb, jnp.arange(nbk)))
            return acc

        dq = jax.vmap(dq_one)(jnp.arange(nbq), qb, dob, m, l, Dvec)

        # dk, dv: per k block, accumulate over q blocks
        def dkv_one(ci, kcblk, vcblk):
            def step(carry, qc):
                dk_acc, dv_acc = carry
                qblk, doblk, mrow, lrow, Drow, qi = qc
                s = qblk @ kcblk.T * scale
                if causal:
                    s = _causal_mask(s, qi, ci, bq, bk)
                p = jnp.exp(s - mrow[:, None]) / lrow[:, None]
                dp = doblk @ vcblk.T
                ds = p * (dp - Drow[:, None])
                return (dk_acc + ds.T @ qblk * scale,
                        dv_acc + p.T @ doblk), None

            init = (jnp.zeros((bk, d), f32), jnp.zeros((bk, dv), f32))
            (dk_acc, dv_acc), _ = jax.lax.scan(
                step, init, (qb, dob, m, l, Dvec, jnp.arange(nbq)))
            return dk_acc, dv_acc

        dk, dv_ = jax.vmap(dkv_one)(jnp.arange(nbk), kb, vb)
        return dq.reshape(sq, d), dk.reshape(sk, d), dv_.reshape(sk, dv)

    flat = lambda x: x.reshape((b * h,) + x.shape[2:])  # noqa: E731
    dq, dk, dv_ = jax.vmap(per_head)(flat(q), flat(k), flat(v), flat(out),
                                     flat(cot))
    return (dq.reshape(q.shape).astype(q.dtype),
            dk.reshape(k.shape).astype(k.dtype),
            dv_.reshape(v.shape).astype(v.dtype))


@_functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret):
    return flash_forward(q, k, v, scale, causal, block_q, block_k,
                         interpret)


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    out = flash_forward(q, k, v, scale, causal, block_q, block_k,
                        interpret)
    return out, (q, k, v, out)


def _flash_bwd(scale, causal, block_q, block_k, interpret, res, cot):
    q, k, v, out = res
    return _flash_backward(q, k, v, out, cot, scale, causal, block_q,
                           block_k)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---- registry wiring -------------------------------------------------

def _kernel(q, k, v, scale, causal=False, block_q=128, block_k=128,
            interpret=False):
    return _flash(q, k, v, float(scale), bool(causal), int(block_q),
                  int(block_k), bool(interpret))


def _xla(q, k, v, scale, causal=False, block_q=128, block_k=128):
    del block_q, block_k  # dense path has no blocking
    return flash_attention_reference(q, k, v, scale, causal)


# Queries and keys share one head width ``d``; values (and so the output)
# may have their own, ``dv`` (latent attention: 192 | 128). Where the two
# are equal the traced program is what it was before ``dv`` existed, and so
# is every line number above this one: a Mosaic call's payload carries the
# source lines of its callers, and a moved line is a new executable.

def _pow2(n):
    p = 1
    while p < n:
        p *= 2
    return p


def _bucket(q, k, v, scale, causal=False, block_q=128, block_k=128):
    """Sequence lengths and batch*heads round UP to powers of two (one
    table row covers the whole bucket); head dims and dtype are exact —
    they change the kernel's tiling, not just its trip count. A value
    width of its own is named after the query/key width (``d192v128``);
    equal widths keep the key they always had."""
    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[3]
    width = f"d{d}" if dv == d else f"d{d}v{dv}"
    return (f"bh{_pow2(b * h)}_sq{_pow2(sq)}_sk{_pow2(sk)}_{width}_"
            f"{jnp.dtype(q.dtype).name}_c{int(bool(causal))}_"
            f"q{block_q}k{block_k}")


def _supports(q, k, v, scale, causal=False, block_q=128, block_k=128):
    """The statically checkable Mosaic constraints: S divisible by the
    block sizes, both head widths a multiple of 8 up to 512, rank-4
    inputs, keys as wide as the queries and as many as the values."""
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        return False
    sq, sk, d, dv = q.shape[2], k.shape[2], q.shape[3], v.shape[3]
    return (sq % block_q == 0 and sk % block_k == 0
            and k.shape[3] == d and v.shape[2] == sk
            and d % 8 == 0 and 0 < d <= 512
            and dv % 8 == 0 and 0 < dv <= 512)


def default_blocks(sq, sk, d, dv):
    """``(block_q, block_k)`` for a caller with no preference of its own.
    Equal widths keep the 128 x 128 they always had (the only size
    measured at d64). With a value width of its own, up to 256 wide: the
    largest power of two up to 1024 that divides the length. Measured on
    a v5e at 2 x 32 heads x 4096, 192 | 128, causal, forward + backward:
    155 ms at 128 x 128, 73 at 512, 65 at 1024 (the scanned backward
    computes every block pair, so fewer and larger blocks win twice);
    2048 x 1024 does not fit VMEM (PERF.md, PR 26)."""
    if d == dv or max(d, dv) > 256:
        return 128, 128
    # s & -s: the largest power of two that divides s
    return (max(128, min(sq & -sq, 1024)), max(128, min(sk & -sk, 1024)))


def _register():
    from . import register_kernel

    register_kernel(
        "flash_attention", kernel=_kernel, xla=_xla, bucket=_bucket,
        supports=_supports, default_tpu=True,
        tolerance="f32 rtol=2e-5 atol=2e-5 vs dense softmax (softmax "
                  "normalizer reassociated across k blocks)")


_register()
