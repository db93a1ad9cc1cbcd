"""Pallas flash-attention kernel tests (interpreter mode on CPU; the
same kernel lowers natively on TPU — driven on the real chip in
verification)."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.ops import pallas_ops


def _qkv(B=1, H=2, S=256, D=64, seed=0):
    rs = onp.random.RandomState(seed)
    return [mx.nd.array(rs.randn(B, H, S, D).astype("float32") * 0.3)
            for _ in range(3)]


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(causal):
    import jax.numpy as jnp

    q, k, v = _qkv()
    out = nd.contrib.flash_attention(q, k, v, causal=causal, interpret=True)
    ref = pallas_ops.flash_attention_reference(
        jnp.asarray(q.asnumpy()), jnp.asarray(k.asnumpy()),
        jnp.asarray(v.asnumpy()), 1.0 / 8.0, causal)
    onp.testing.assert_allclose(out.asnumpy(), onp.asarray(ref),
                                rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_op_in_bfloat16_at_berts_length(causal):
    """Through the op, bf16 in and out, a head's 384 positions one block:
    within 1e-2 of the largest |out| of the float32 dense softmax of the
    same rounded inputs (the bound the family registers)."""
    import jax.numpy as jnp

    q, k, v = (x.astype("bfloat16") for x in _qkv(S=384))
    out = nd.contrib.flash_attention(q, k, v, causal=causal, interpret=True)
    assert out.dtype == q.dtype and out.shape == q.shape
    want = onp.asarray(pallas_ops.flash_attention_reference(
        *(jnp.asarray(x.astype("float32").asnumpy()) for x in (q, k, v)),
        1.0 / 8.0, causal))
    err = onp.abs(out.astype("float32").asnumpy() - want).max()
    assert err <= 1e-2 * onp.abs(want).max(), err


def test_flash_kernel_path_taken():
    """The pallas kernel (not the dense fallback) runs for aligned
    shapes under interpret mode."""
    import jax.numpy as jnp

    q, k, v = _qkv(S=128)
    out = pallas_ops._flash_forward(
        jnp.asarray(q.asnumpy()), jnp.asarray(k.asnumpy()),
        jnp.asarray(v.asnumpy()), 0.125, False, 128, 128,
        interpret=True)
    ref = pallas_ops.flash_attention_reference(
        jnp.asarray(q.asnumpy()), jnp.asarray(k.asnumpy()),
        jnp.asarray(v.asnumpy()), 0.125, False)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=1e-4, atol=1e-5)


def test_flash_unaligned_falls_back():
    q, k, v = _qkv(S=100)  # not divisible by block
    out = nd.contrib.flash_attention(q, k, v)
    assert out.shape == q.shape


def test_flash_gradients():
    q, k, v = _qkv(S=128)
    for x in (q, k, v):
        x.attach_grad()
    with mx.autograd.record():
        out = nd.contrib.flash_attention(q, k, v, interpret=True)
        loss = (out * out).sum()
    loss.backward()
    # oracle: dense attention gradients
    import jax
    import jax.numpy as jnp

    def dense_loss(qr, kr, vr):
        o = pallas_ops.flash_attention_reference(qr, kr, vr, 0.125, False)
        return (o * o).sum()

    grads = jax.grad(dense_loss, argnums=(0, 1, 2))(
        jnp.asarray(q.asnumpy()), jnp.asarray(k.asnumpy()),
        jnp.asarray(v.asnumpy()))
    for x, g in zip((q, k, v), grads):
        onp.testing.assert_allclose(x.grad.asnumpy(), onp.asarray(g),
                                    rtol=1e-3, atol=1e-4)
