"""Ops of decoder language models: RMS norm, rotary positions, the gated
SiLU product, the sparse expert layer and the token cross-entropy.

Beyond the reference (MXNet 1.x stops at post-LN encoders and fused
RNNs). Each is a pure JAX function like every other op; the gluon blocks
over them are in ``gluon/nn/text_layers.py`` and the first model built of
them in ``gluon/model_zoo/text``. Attention itself is
``_contrib_flash_attention`` (``ops/pallas_ops.py``), which takes a value
width of its own.
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


@register("_contrib_sparse_moe", num_outputs=2)
def _contrib_sparse_moe(x, router_weight, router_bias, gate_weight,
                        up_weight, down_weight, top_k=1, first_expert=0,
                        scale=1.0, norm_topk=True):
    """The held experts' part of a sparse expert layer over ``x``
    (..., h): ``parallel.moe.routed_experts`` on the flattened tokens.
    Outputs ``(y like x, load (n_held,))``."""
    from ..parallel.moe import routed_experts

    y, load = routed_experts(
        x.reshape(-1, x.shape[-1]), router_weight, router_bias,
        gate_weight, up_weight, down_weight, top_k=int(top_k),
        first_expert=int(first_expert), scale=float(scale),
        norm_topk=bool(norm_topk))
    return y.reshape(x.shape), load


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
