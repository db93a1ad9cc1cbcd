"""Kernels of the main path compiled at their real widths for a DESCRIBED
v5e (the TPU's compiler is installed here; nothing runs, nothing printed
is a measurement): what Mosaic or the chip's memory would refuse on the
chip fails here, at no chip time. Everything built from the topology is
built inside the fixtures, so every xdist worker collects the same tests
and only the one that runs this file loads the TPU's library."""
import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def quiet_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache; keep it out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _shape(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("blocks", [(128, 128), (1024, 1024)])
def test_flash_192_128_compiles_forward_and_backward(one_chip, quiet_cache,
                                                     blocks):
    """Latent attention at the benchmark's shape: 2 x 32 heads, 4096
    positions, keys 192 wide and values 128, causal, bfloat16."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.kernels import flash

    bq, bk = blocks
    q = _shape((2, 32, 4096, 192), jnp.bfloat16, one_chip)
    v = _shape((2, 32, 4096, 128), jnp.bfloat16, one_chip)

    def fwd_bwd(q_, k_, v_, cot):
        out, vjp = jax.vjp(
            lambda *t: flash._kernel(*t, 192 ** -0.5, causal=True,
                                     block_q=bq, block_k=bk), q_, k_, v_)
        return (out,) + vjp(cot)

    compiled = jax.jit(fwd_bwd).lower(q, q, v, v).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 1
    out, dq, dk, dv = jax.eval_shape(fwd_bwd, q, q, v, v)
    assert out.shape == v.shape and dq.shape == q.shape \
        and dk.shape == q.shape and dv.shape == v.shape


@pytest.mark.parametrize("shape,causal,forward_blocks", [
    ((2, 32, 4096, 192, 128), True, (1024, 1024)),   # the language model
    ((32, 12, 384, 64, 64), False, (384, 384)),      # BERT-base at 384
], ids=["bh64_s4096_d192v128_c1", "bh384_s384_d64_c0"])
def test_flash_backward_kernels_compile_at_the_cells_buckets(
        one_chip, quiet_cache, shape, causal, forward_blocks):
    """The forward with its second result, at the blocks (and heads a
    program) the shape picks, and the fused backward call, at the blocks
    ``backward_blocks`` picks, for both buckets the benchmark runs: two
    Mosaic calls, inside the scoped VMEM; and as the two calls a longer
    sequence takes: three."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.kernels import flash

    b, h, s, d, dv = shape
    q = _shape((b, h, s, d), jnp.bfloat16, one_chip)
    v = _shape((b, h, s, dv), jnp.bfloat16, one_chip)
    blocks = flash.backward_blocks(s, s, d, dv)
    assert blocks == ((512, 512) if s == 4096 else (s, s))
    assert flash.default_blocks(s, s, d, dv) == forward_blocks
    assert flash.heads_a_program(b * h, s, s, *forward_blocks) \
        == (1 if s == 4096 else 4)

    def fwd_bwd(q_, k_, v_, cot):
        out, lse = flash.flash_forward_lse(q_, k_, v_, d ** -0.5, causal,
                                           *forward_blocks)
        return (out, lse) + flash.flash_backward_kernel(
            q_, k_, v_, out, lse, cot, d ** -0.5, causal, *blocks)

    compiled = jax.jit(fwd_bwd).lower(q, q, v, v).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2
    two = jax.jit(lambda q_, k_, v_, o_, l_, c_: flash.flash_backward_kernel(
        q_, k_, v_, o_, l_, c_, d ** -0.5, causal, *blocks, fused=False))
    lse_ = _shape((b, h, s), jnp.float32, one_chip)
    assert two.lower(q, q, v, v, lse_, v).compile().as_text().count(
        "tpu_custom_call") == 2
    # bf16 operands stay bf16 products whatever precision float32 matmuls
    # are asked for (Mosaic refuses a bf16 product at "highest", which is
    # how chip_smoke.py traces its kernels)
    with jax.default_matmul_precision("highest"):
        assert two.lower(q, q, v, v, lse_, v).compile().as_text().count(
            "tpu_custom_call") == 2
    out, lse, dq, dk, dv_ = jax.eval_shape(fwd_bwd, q, q, v, v)
    assert lse.shape == (b, h, s) and lse.dtype == jnp.float32
    assert (dq.shape, dk.shape, dv_.shape) == (q.shape, q.shape, v.shape)
    assert dq.dtype == dk.dtype == dv_.dtype == jnp.bfloat16


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("sq,sk,d,dv", [
    (4096, 4096, 192, 128),   # float32 at the language model's widths
    (4096, 4096, 512, 512),   # the widest heads ``_supports`` lets in
    (1024, 4096, 512, 256),
    (896, 896, 512, 512),     # a whole sequence no power of two
    (1024, 1024, 64, 64),     # the longest whole sequence
    (512, 512, 64, 64),       # four heads a program of 512 x 512
])
def test_flash_forward_compiles_at_the_blocks_the_shape_picks(
        one_chip, quiet_cache, dtype, sq, sk, d, dv):
    """``default_blocks`` bounds a tile by ``_forward_vmem_bytes``, a fit
    to what Mosaic allocates: where the fit says a tile is inside the
    scoped VMEM, the compiler has to agree, masked or not."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.kernels import flash

    dt = jnp.dtype(dtype)
    blocks = flash.default_blocks(sq, sk, d, dv, dt.itemsize)
    assert flash._forward_vmem_bytes(*blocks, d, dv, dt.itemsize) \
        <= flash._VMEM_BUDGET
    q = _shape((1, 4, sq, d), dt, one_chip)
    k = _shape((1, 4, sk, d), dt, one_chip)
    v = _shape((1, 4, sk, dv), dt, one_chip)
    for causal in (False, True):
        text = jax.jit(lambda q_, k_, v_: flash._kernel(
            q_, k_, v_, d ** -0.5, causal=causal)).lower(
                q, k, v).compile().as_text()
        assert text.count("tpu_custom_call") == 1


def test_routed_experts_compile_to_grouped_kernels(one_chip, quiet_cache):
    """The expert layer at the benchmark's widths (8,192 tokens, 128-way
    router, top-6, 16 held experts of 2048 x 768): the three products of
    the forward pass and their transposes are Mosaic grouped-matmul
    calls, not 16 dense passes."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel.moe import routed_experts

    bf = jnp.bfloat16
    args = (_shape((8192, 2048), bf, one_chip),
            _shape((128, 2048), bf, one_chip),
            _shape((128,), jnp.float32, one_chip),
            _shape((16, 2048, 768), bf, one_chip),
            _shape((16, 2048, 768), bf, one_chip),
            _shape((16, 768, 2048), bf, one_chip))

    def loss(*a):
        y, load = routed_experts(*a, top_k=6, first_expert=0, scale=2.448)
        return y.astype(jnp.float32).sum(), load

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 3, 4, 5),
                                has_aux=True)).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 9
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 * 2 ** 30


def test_selective_scan_kernels_compile_at_the_cells_bucket(one_chip,
                                                            quiet_cache):
    """The scan at the hybrid decoder's shape: one sequence of 4,096
    positions, 5,120 channels of 16 states, bfloat16 x, B and C and a
    float32 step: the forward and the backward are one Mosaic call each,
    64 positions a chunk and 512 channels a program inside the scoped
    VMEM, and the states saved are the 21 MB of chunk boundaries."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.kernels import selective_scan as ss

    bf, f32 = jnp.bfloat16, jnp.float32
    wide = (1, 4096, 5120)
    args = (_shape(wide, bf, one_chip), _shape(wide, f32, one_chip),
            _shape((5120, 16), f32, one_chip),
            _shape((1, 4096, 16), bf, one_chip),
            _shape((1, 4096, 16), bf, one_chip),
            _shape((5120,), f32, one_chip))
    assert ss._supports(*args) and ss._lanes_of(5120) == 512
    assert ss._bucket(*args) == "b1_s4096_d5120_n16_bfloat16_t64l512"
    fwd = jax.jit(ss.selective_scan_forward)
    assert fwd.lower(*args).compile().as_text().count("tpu_custom_call") == 1
    y, states = jax.eval_shape(ss.selective_scan_forward, *args)
    assert (y.shape, y.dtype) == (wide, bf)
    assert (states.shape, states.dtype) == ((1, 64, 16, 5120), f32)
    bwd = jax.jit(ss.selective_scan_backward_kernel)
    cot = _shape(wide, bf, one_chip)
    states = _shape(states.shape, f32, one_chip)
    assert bwd.lower(*args, states, cot).compile().as_text().count(
        "tpu_custom_call") == 1
    grads = jax.eval_shape(ss.selective_scan_backward_kernel, *args, states,
                           cot)
    assert [(g.shape, g.dtype) for g in grads] \
        == [(a.shape, a.dtype) for a in args]
    # float32 inputs take the exact selector products at the highest
    # precision; Mosaic has to accept those too
    args32 = tuple(_shape(a.shape, f32, one_chip) for a in args)
    assert fwd.lower(*args32).compile().as_text().count(
        "tpu_custom_call") == 1


@pytest.mark.parametrize("window,forward_blocks", [(None, (1024, 1024)),
                                                   (512, (512, 512))],
                         ids=["full", "window512"])
def test_grouped_windowed_flash_compiles_at_the_cells_buckets(
        one_chip, quiet_cache, window, forward_blocks):
    """One softmax of differential attention at the hybrid decoder's
    shape: 20 query heads 64 wide read 10 key heads through the index map,
    values 128 wide, causal, with and without the window of 512: the
    forward and the fused backward are one Mosaic call each, dK and dV
    leave the kernel float32 a query head and are summed over the
    group."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.kernels import flash

    bf = jnp.bfloat16
    q = _shape((1, 20, 4096, 64), bf, one_chip)
    k = _shape((1, 10, 4096, 64), bf, one_chip)
    v = _shape((1, 10, 4096, 128), bf, one_chip)
    cot = _shape((1, 20, 4096, 128), bf, one_chip)
    assert flash._supports(q, k, v, 0.125, True, window=window)
    assert flash._blocks(q, k, v, window=window) == forward_blocks
    assert flash._blocks_for(q, k, v, window) == (512, 512)
    suffix = "_g2" + (f"_w{window}" if window else "")
    assert flash._bucket(q, k, v, 0.125, True, window=window) == (
        "bh32_sq4096_sk4096_d64v128_bfloat16_c1_q%dk%d" % forward_blocks
        + suffix)

    def fwd_bwd(q_, k_, v_, cot_):
        out, lse = flash.flash_forward_lse(q_, k_, v_, 0.125, True,
                                           *forward_blocks, window=window)
        return (out,) + flash.flash_backward_kernel(
            q_, k_, v_, out, lse, cot_, 0.125, True, 512, 512, window=window)

    text = jax.jit(fwd_bwd).lower(q, k, v, cot).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "f32[20,4096,64]" in text        # dK a query head, before the sum
    out, dq, dk, dv = jax.eval_shape(fwd_bwd, q, k, v, cot)
    assert [(t.shape, t.dtype) for t in (out, dq, dk, dv)] \
        == [(t.shape, t.dtype) for t in (cot, q, k, v)]
