"""Ring attention: sequence/context parallelism over the mesh 'sp' axis.

The reference (MXNet 1.x) predates long-context tech — SURVEY §5.7 documents
its absence and directs the rebuild to make SP first-class. This module
implements blockwise ring attention (Liu et al.'s ring schedule with
flash-style online-softmax accumulation):

  * sequence is sharded over the 'sp' mesh axis; each device holds a
    (B, H, S/n, D) block of q, k, v;
  * n ring steps: attend q-block against the resident k/v block, then
    `ppermute` k/v to the next neighbour over ICI — compute and transfer
    overlap, and no device ever materialises the full S x S score matrix;
  * numerically exact: running max/denominator accumulation is the fp-safe
    flash-attention recurrence.

Also exports `attention()` — the single-device fused softmax(qk)v used as
the reference implementation and as the building block for transformer
layers (parity role: contrib/transformer.cc interleaved selfatt ops).
"""
from __future__ import annotations

import functools
import math

__all__ = ["attention", "ring_attention", "ring_attention_sharded"]


def attention(q, k, v, causal=False, scale=None):
    """Plain fused attention on one device. q,k,v: (B, H, S, D) jax arrays."""
    import jax.numpy as jnp

    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask, s, -jnp.inf)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _ring_attention_local(q, k, v, axis_name, causal, scale):
    """Per-shard body (runs under shard_map): flash accumulation over the
    ring of k/v blocks."""
    import jax
    import jax.numpy as jnp

    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    s_loc = q.shape[2]
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    perm = [(i, (i + 1) % n) for i in range(n)]

    q_pos = my_idx * s_loc + jnp.arange(s_loc)  # global query positions

    def step(i, carry):
        o, m, l, k_cur, v_cur = carry
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k_cur) * scale
        src = (my_idx - i) % n  # which shard this k/v block came from
        if causal:
            k_pos = src * s_loc + jnp.arange(k_cur.shape[2])
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask, s, -jnp.inf)
        block_max = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, block_max)
        # guard fully-masked blocks: exp(-inf - -inf) -> use safe m
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        corr = jnp.exp(jnp.where(jnp.isneginf(m), 0.0, m) - m_safe)
        corr = jnp.where(jnp.isneginf(m), 0.0, corr)
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v_cur)
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return o_new, m_new, l_new, k_next, v_next

    # initial carries must carry the sp-varying type (shard_map type system)
    pcast = jax.lax.pcast
    o = pcast(jnp.zeros(q.shape, jnp.float32), axis_name, to="varying")
    m = pcast(jnp.full(q.shape[:-1], -jnp.inf, jnp.float32),
              axis_name, to="varying")
    l = pcast(jnp.zeros(q.shape[:-1], jnp.float32), axis_name,
              to="varying")
    o, m, l, _, _ = jax.lax.fori_loop(0, n, step, (o, m, l,
                                                   k.astype(jnp.float32),
                                                   v.astype(jnp.float32)))
    return (o / jnp.maximum(l[..., None], 1e-30)).astype(q.dtype)


def ring_attention_sharded(mesh, axis="sp", causal=False, scale=None):
    """Build a shard_map'ed ring-attention callable over `mesh`.

    Returns fn(q, k, v) where inputs are (B, H, S, D) with S divisible by
    the sp axis size; inputs may be unsharded (they will be laid out).
    """
    import jax
    from jax.sharding import PartitionSpec as P

    jmesh = mesh.jax_mesh
    spec = P(None, None, axis, None)
    local = functools.partial(_ring_attention_local, axis_name=axis,
                              causal=causal, scale=scale)
    fn = jax.shard_map(lambda q, k, v: local(q, k, v), mesh=jmesh,
                   in_specs=(spec, spec, spec), out_specs=spec)
    return jax.jit(fn)


def ring_attention(q, k, v, mesh, axis="sp", causal=False, scale=None):
    """One-shot ring attention over NDArrays or jax arrays."""
    from ..ndarray import NDArray

    raw = lambda x: x._data if isinstance(x, NDArray) else x
    fn = ring_attention_sharded(mesh, axis=axis, causal=causal, scale=scale)
    out = fn(raw(q), raw(k), raw(v))
    return NDArray(out) if isinstance(q, NDArray) else out
