"""Ops of decoder language models: RMS and layer norm, rotary positions,
the gated SiLU product, the sparse expert layer, the token cross-entropy,
and a hybrid decoder's mixers: the causal depthwise convolution and the
selective scan of a state-space layer, and differential attention.

Beyond the reference (MXNet 1.x stops at post-LN encoders and fused
RNNs). Each is a pure JAX function like every other op; the gluon blocks
over them are in ``gluon/nn/text_layers.py`` and the first model built of
them in ``gluon/model_zoo/text``. Attention itself is
``_contrib_flash_attention`` (``ops/pallas_ops.py``), which takes a value
width of its own, grouped keys and a window; the scan is the kernel family
``selective_scan`` (``kernels/selective_scan.py``).
"""
from __future__ import annotations

from .registry import register


@register("_contrib_rms_norm")
def _contrib_rms_norm(x, gamma, eps=1e-6):
    """``gamma * x / sqrt(mean(x^2) + eps)`` over the last axis; the
    statistics in float32, the result in ``x``'s type."""
    import jax
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return gamma.astype(x.dtype) * (x32 * inv).astype(x.dtype)


@register("_contrib_rotary_embedding")
def _contrib_rotary_embedding(x, theta=10000.0, interleave=False):
    """Rotary position embedding over ``x`` (B, H, S, D), positions
    0..S-1, frequencies ``theta ** (-2i / D)``.

    The result is in rotate-half layout: with ``(a, b)`` the two halves of
    the last axis, ``[a cos - b sin, b cos + a sin]``. ``interleave`` says
    the input holds its pairs side by side (``x[2i], x[2i+1]``: they are
    de-interleaved first, DeepSeek's ``rope_interleave``); otherwise the
    pairs are the two halves already."""
    import jax.numpy as jnp

    s, d = x.shape[-2], x.shape[-1]
    half = d // 2
    freq = float(theta) ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    if interleave:
        pairs = x32.reshape(x.shape[:-1] + (half, 2))
        a, b = pairs[..., 0], pairs[..., 1]
    else:
        a, b = x32[..., :half], x32[..., half:]
    out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.astype(x.dtype)


@register("_contrib_gated_silu")
def _contrib_gated_silu(gate, up):
    """``silu(gate) * up``, the product inside a gated feed-forward."""
    import jax
    import jax.numpy as jnp

    return jax.nn.silu(gate.astype(jnp.float32)).astype(up.dtype) * up


@register("_contrib_sparse_moe",
          num_outputs=lambda _n, kw: 3 if kw.get("route_counts") else 2)
def _contrib_sparse_moe(x, router_weight, router_bias, gate_weight,
                        up_weight, down_weight, top_k=1, first_expert=0,
                        scale=1.0, norm_topk=True, norm_eps=1e-20,
                        route_counts=False):
    """The held experts' part of a sparse expert layer over ``x``
    (..., h): ``parallel.moe.routed_experts`` on the flattened tokens.
    Outputs ``(y like x, load (n_held,))`` and, with ``route_counts``,
    the pairs every expert of the router got, (n_experts,)."""
    from ..parallel.moe import routed_experts

    y, *counts = routed_experts(
        x.reshape(-1, x.shape[-1]), router_weight, router_bias,
        gate_weight, up_weight, down_weight, top_k=int(top_k),
        first_expert=int(first_expert), scale=float(scale),
        norm_topk=bool(norm_topk), norm_eps=float(norm_eps),
        route_counts=bool(route_counts))
    return (y.reshape(x.shape), *counts)


@register("_contrib_ring_write")
def _contrib_ring_write(ring, row, index):
    """``ring`` (R, ...) with ``row`` written at ``index[0] mod R``: a
    history of the last R values of a statistic, kept as auxiliary state
    (``SparseMoE``'s ``route_recent``)."""
    import jax.numpy as jnp

    at = index.reshape(-1)[0].astype(jnp.int32) % ring.shape[0]
    return ring.at[at].set(row.astype(ring.dtype))


@register("_contrib_lm_cross_entropy")
def _contrib_lm_cross_entropy(logits, labels):
    """Mean over a sequence's tokens of ``logsumexp(logits) -
    logits[label]``: logits (B, S, V), labels (B, S) -> (B,) float32."""
    import jax
    import jax.numpy as jnp

    z = logits.astype(jnp.float32)
    picked = jnp.take_along_axis(
        z, labels.astype(jnp.int32)[..., None], axis=-1)[..., 0]
    return (jax.nn.logsumexp(z, axis=-1) - picked).mean(axis=-1)


@register("_contrib_layer_norm")
def _contrib_layer_norm(x, gamma, beta, eps=1e-5):
    """``gamma * (x - mean) / sqrt(var + eps) + beta`` over the last axis;
    the statistics in float32, the result in ``x``'s type (``LayerNorm``
    takes them in ``x``'s)."""
    import jax
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    cen = x32 - mean
    inv = jax.lax.rsqrt(jnp.mean(cen * cen, axis=-1, keepdims=True) + eps)
    return (cen * inv * gamma.astype(jnp.float32)
            + beta.astype(jnp.float32)).astype(x.dtype)


@register("_contrib_causal_conv1d")
def _contrib_causal_conv1d(x, weight, bias, activation=None):
    """Depthwise causal convolution over positions: ``x`` (B, S, C),
    ``weight`` (C, K), ``bias`` (C,); ``y_t = bias + sum_k weight[:, k] *
    x_(t - K + 1 + k)``, positions before the first counted as zero (a
    ``conv1d`` with ``groups = C`` and ``padding = K - 1`` cut to S), then
    ``silu`` where ``activation="silu"``. Summed in float32."""
    import jax
    import jax.numpy as jnp

    s, taps = x.shape[1], weight.shape[1]
    x32 = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    w32 = weight.astype(jnp.float32)
    y = bias.astype(jnp.float32)
    for k in range(taps):
        y = y + x32[:, k:k + s, :] * w32[:, k]
    if activation is not None:
        if activation != "silu":
            raise ValueError(f"causal_conv1d has no activation {activation!r}")
        y = jax.nn.silu(y)
    return y.astype(x.dtype)


@register("_contrib_gated_short_conv")
def _contrib_gated_short_conv(bcx, weight):
    """The inside of LFM2's double-gated short convolution: ``bcx`` (B, S,
    3 C) holds three streams ``[b; c; x]`` side by side, ``weight`` (C, K)
    the taps of a depthwise causal convolution without bias. ``z = b * x``;
    ``v_t = sum_k weight[:, k] * z_(t - K + 1 + k)``, positions before the
    first counted as zero; the result is ``c * v`` (B, S, C). Products and
    sums in float32, the result in ``bcx``'s type.

    One pass over the streams each way: the backward keeps ``bcx`` as it
    came, recomputes the few products an element it needs and writes the
    three cotangents side by side. What jax would derive keeps ``z``,
    ``v`` and a float32 copy of ``bcx`` a layer and pads and concatenates
    its way back; and XLA undoes a recomputation it can see through, so
    both passes take ``bcx`` behind ``optimization_barrier``: without the
    forward's the projection before it writes float32 (XLA drops the
    rounding to ``bcx``'s type in between), without the backward's the
    forward's ``z`` and ``v`` are kept in float32 for it (read off the
    step compiled for a described v5e)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    s, taps = bcx.shape[1], weight.shape[1]

    def streams(bcx, weight):
        b, c, x = jnp.split(bcx.astype(f32), 3, axis=-1)
        z = jnp.pad(b * x, ((0, 0), (taps - 1, 0), (0, 0)))
        w32 = weight.astype(f32)
        v = sum(z[:, k:k + s, :] * w32[:, k] for k in range(taps))
        return b, c, x, z, v, w32

    @jax.custom_vjp
    def gated(bcx, weight):
        _, c, _, _, v, _ = streams(jax.lax.optimization_barrier(bcx), weight)
        return (c * v).astype(bcx.dtype)

    def gated_fwd(bcx, weight):
        return gated(bcx, weight), (bcx, weight)

    def gated_bwd(kept, g):
        kept = jax.lax.optimization_barrier(kept)
        b, c, x, z, v, w32 = streams(*kept)
        g = g.astype(f32)
        # v_t reads z_(t - K + 1 + k): z_u is read by v_(u + K - 1 - k)
        dv = jnp.pad(g * c, ((0, 0), (0, taps - 1), (0, 0)))
        dz = sum(dv[:, taps - 1 - k:taps - 1 - k + s, :] * w32[:, k]
                 for k in range(taps))
        dw = jnp.stack([(dv[:, :s, :] * z[:, k:k + s, :]).sum(axis=(0, 1))
                        for k in range(taps)], axis=-1)
        d_bcx = jnp.concatenate([dz * x, g * v, dz * b], axis=-1)
        return d_bcx.astype(kept[0].dtype), dw.astype(kept[1].dtype)

    gated.defvjp(gated_fwd, gated_bwd)
    return gated(bcx, weight)


@register("_contrib_selective_scan")
def _contrib_selective_scan(x, dt, a_log, b, c, d, dt_bias, interpret=False):
    """The recurrence of a Mamba-1 layer over ``x`` (B, S, C): ``D_t =
    softplus(dt + dt_bias)`` and ``A = -exp(a_log)`` (C, N) in float32,
    ``h_t = exp(D_t A) h_(t-1) + (D_t x_t) B_t^T``, ``y_t = h_t C_t + d *
    x_t`` with ``b``, ``c`` (B, S, N); the state float32, the result in
    ``x``'s type. Kernel family ``selective_scan``: a Pallas kernel with
    the state resident in VMEM where ``kernels.dispatch`` picks it, the
    chunked XLA recurrence elsewhere; ``interpret=True`` forces the kernel
    through the Pallas interpreter (CPU tests)."""
    import jax
    import jax.numpy as jnp

    from .. import kernels as _kernels

    f32 = jnp.float32
    step = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
    return _kernels.dispatch(
        "selective_scan", x, step, -jnp.exp(a_log.astype(f32)), b, c,
        d.astype(f32), interpret=bool(interpret) or None)


@register("_contrib_diff_attention")
def _contrib_diff_attention(q, k, v, lam_q1, lam_k1, lam_q2, lam_k2, gamma,
                            num_heads=2, num_kv_heads=2, lam_init=0.8,
                            window=None, eps=1e-5, interpret=False):
    """Causal differential attention (arXiv:2410.05258) with grouped keys
    over projected ``q`` (B, S, H * d) and ``k``, ``v`` (B, S, Hk * d).

    Heads are taken in pairs, even and odd side by side: query pair ``p``
    is heads ``(2p, 2p + 1)``, it reads key pair ``p // (H / Hk)`` and that
    pair's two value heads as one head ``2d`` wide. ``P = softmax(q1 k1^T
    / sqrt(d)) - lam * softmax(q2 k2^T / sqrt(d))`` with ``lam =
    exp(lam_q1 . lam_k1) - exp(lam_q2 . lam_k2) + lam_init``; the result is
    ``(1 - lam_init) * RMSNorm_2d(P [v1; v2])`` (``gamma`` (2d,), statistics
    float32), heads side by side: (B, S, H * d). Each softmax is one
    ``flash_attention`` dispatch over H / 2 query heads, Hk / 2 key heads
    and values ``2d`` wide; ``window`` is the number of keys a position
    sees, its own included (None: all before it)."""
    import jax
    import jax.numpy as jnp

    from .. import kernels as _kernels

    bsz, s = q.shape[:2]
    heads, kv_heads = int(num_heads), int(num_kv_heads)
    d = q.shape[-1] // heads
    f32 = jnp.float32

    def halves(t, n):  # (B, S, n * d) -> 2 x (B, n / 2, S, d)
        t = t.reshape(bsz, s, n // 2, 2, d).transpose(3, 0, 2, 1, 4)
        return t[0], t[1]

    q1, q2 = halves(q, heads)
    k1, k2 = halves(k, kv_heads)
    v2d = v.reshape(bsz, s, kv_heads // 2, 2 * d).transpose(0, 2, 1, 3)
    window = None if window is None else int(window)

    def softmax_v(q_, k_):
        return _kernels.dispatch(
            "flash_attention", q_, k_, v2d, float(d) ** -0.5, causal=True,
            window=window, interpret=bool(interpret) or None)

    lam = (jnp.exp(jnp.sum(lam_q1.astype(f32) * lam_k1.astype(f32)))
           - jnp.exp(jnp.sum(lam_q2.astype(f32) * lam_k2.astype(f32)))
           + lam_init)
    out = softmax_v(q1, k1).astype(f32) - lam * softmax_v(q2, k2).astype(f32)
    inv = jax.lax.rsqrt(jnp.mean(out * out, axis=-1, keepdims=True) + eps)
    out = (out * inv * gamma.astype(f32) * (1.0 - lam_init)).astype(q.dtype)
    return out.transpose(0, 2, 1, 3).reshape(bsz, s, heads * d)
