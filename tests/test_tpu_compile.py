"""Kernels of the main path compiled at their real widths for a DESCRIBED
v5e (the TPU's compiler is installed here; nothing runs, nothing printed
is a measurement): what Mosaic or the chip's memory would refuse on the
chip fails here, at no chip time. Everything built from the topology is
built inside the fixtures, so every xdist worker collects the same tests
and only the one that runs this file loads the TPU's library."""
import functools
import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(v5e):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e.devices[0])


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


def test_expert_buffer_rungs_compile_to_their_rows(one_chip, quiet_cache):
    """An expert layer shaped like the short-convolution decoder's, small
    (2,048 tokens, top-4, 8 held of a 32-way router, 256 x 224): in each
    branch of the forward's and of the backward's switch every grouped
    product with a (rows, width) result has that rung's rows, and no float32
    tensor of every (token, expert) pair is left outside a fusion."""
    import re

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel.moe import MAX_RUNGS, buffer_rungs, routed_experts

    t, h, f, e, n, k = 2048, 256, 224, 32, 8, 4
    rungs = buffer_rungs(t, k, n, e)
    assert len(rungs) == MAX_RUNGS and rungs[-1] == t * k
    bf = jnp.bfloat16
    args = (_shape((t, h), bf, one_chip), _shape((e, h), bf, one_chip),
            _shape((e,), jnp.float32, one_chip),
            _shape((n, h, f), bf, one_chip), _shape((n, h, f), bf, one_chip),
            _shape((n, f, h), bf, one_chip))

    def loss(*a):
        y, load = routed_experts(*a, top_k=k, scale=1.0)
        return y.astype(jnp.float32).sum(), load

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 3, 4, 5),
                                      has_aux=True)).lower(*args) \
        .compile().as_text()
    bodies, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?(\S+) \(.*\{$", line)
        if head:
            name = head.group(1)
            bodies[name] = []
        elif name is not None and line.strip() not in ("", "}"):
            bodies[name].append(line)
    switches = [re.search(r"branch_computations=\{([^}]*)\}", line)
                .group(1).replace("%", "").split(", ")
                for lines in bodies.values() for line in lines
                if " conditional(" in line]
    assert len(switches) == 2
    for branches in switches:
        assert len(branches) == len(rungs)
        for rows, branch in zip(rungs, branches):
            products = [re.search(r"= bf16\[(\d+),\d+\]", line)
                        for line in bodies[branch] if "ragged-dot-none" in
                        line.split("=")[0]]
            seen = {int(m.group(1)) for m in products if m}
            assert seen == {rows}, (branch, seen)
    fused = {m.group(1) for m in re.finditer(r"calls=%([\w.\-]+)", text)}
    every_pair = (f"f32[{t * k},{h}]", f"f32[{t},{k},{h}]")
    for comp, lines in bodies.items():
        if comp in fused:
            continue
        for line in lines:
            result = line.split(" = ", 1)[-1].split("{", 1)[0]
            assert not result.startswith(every_pair), line[:200]


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


def _step_text(monkeypatch, devices, net, batch, optimizer, params, **kw):
    """The optimized HLO of ``_step_lowered``'s step."""
    return _step_lowered(monkeypatch, devices, net, batch, optimizer,
                         params, **kw).compile().as_text()


def _step_lowered(monkeypatch, devices, net, batch, optimizer, params, **kw):
    """A ``ShardedTrainer`` step over ``net`` in bfloat16 with float32
    masters under an L2 loss against a batch of its input's shape, lowered
    for the described ``devices`` (nothing can be put on those, so the
    trainer's own placement is skipped, as ``rehearse_compile.py`` does)."""
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer

    monkeypatch.setattr(ShardedTrainer, "_place_params", lambda self: None)
    net.initialize(mx.init.Zero())
    net.cast("bfloat16")
    trainer = ShardedTrainer(
        net, gloss.L2Loss(), optimizer, dict(params, multi_precision=True),
        mesh=DeviceMesh({"dp": len(devices)}, devices=devices), **kw)
    x = jax.ShapeDtypeStruct(batch, jnp.bfloat16)
    return trainer.aot_lower(x, x)


def _bf16_step_text(monkeypatch, devices, width, optimizer, params, **kw):
    """``_step_text`` of two bias-free ``width`` x ``width`` layers."""
    from mxnet_tpu.gluon import nn

    net = nn.HybridSequential()
    net.add(nn.Dense(width, in_units=width, use_bias=False),
            nn.Dense(width, in_units=width, use_bias=False))
    return _step_text(monkeypatch, devices, net, (8, width), optimizer,
                      params, **kw)


def _entry_fusions(text):
    """``(name, result dtypes, operand names)`` of the entry computation's
    fusions."""
    import re

    entry = text[text.index("ENTRY "):]
    out = []
    for m in re.finditer(
            r"^\s*(?:ROOT )?%(\S+) = (.+?) fusion\((.*?)\), kind=", entry,
            re.MULTILINE):
        out.append((m.group(1), re.findall(r"(\w+)\[[\d,]*\]", m.group(2)),
                    re.findall(r"%([\w.\-]+)", m.group(3))))
    return out


@pytest.mark.parametrize("optimizer,params,slots", [
    ("adam", {"learning_rate": 1e-3}, 3),
    ("sgd", {"learning_rate": 1e-3, "momentum": 0.9}, 2),
], ids=["adam", "sgd_momentum"])
def test_bf16_parameter_update_is_one_fusion(v5e, quiet_cache, monkeypatch,
                                             optimizer, params, slots):
    """The guarded update of a bfloat16 parameter with a float32 master is
    ONE pass over master, state and gradient: the fusion that writes the
    float32 state writes the bfloat16 weight too, and no fusion whose
    results are all low-precision reads optimizer state. (Where the
    parameter's select had the old weight as an operand of its own, XLA
    kept it out of the state's fusion and recomputed the whole rule in a
    ``convert_select_fusion`` over the same arrays: 44 bytes a parameter
    under Adam where 28 are needed.) At 4,096 x 4,096 no state array is
    prefetched, so the entry's parameter names are the operands."""
    fusions = _entry_fusions(_bf16_step_text(
        monkeypatch, v5e.devices[:1], 4096, optimizer, params))
    reads_state = [f for f in fusions
                   if any(o.startswith("opt_raws_") for o in f[2])]
    assert not [f for f in reads_state
                if set(f[1]) <= {"bf16", "f16"}], reads_state
    for i in range(2):
        mine = [f for f in reads_state
                if any(o.startswith(f"opt_raws_{i}__") for o in f[2])]
        assert len(mine) == 1, mine
        assert sorted(mine[0][1]) == ["bf16"] + ["f32"] * slots, mine
        assert {o.split(".")[0] for o in mine[0][2]
                if o.startswith("opt_raws_")} \
            == {f"opt_raws_{i}__{j}_" for j in range(slots)}, mine


def test_zero_gathers_the_bf16_parameter_not_its_master(v5e, quiet_cache,
                                                        monkeypatch):
    """Under ``zero`` the state is dp-sharded and the parameter is the cast
    of the selected master: the cast happens on the shard, so what crosses
    the chips is the bfloat16 weight, not the float32 master."""
    import re

    text = _bf16_step_text(monkeypatch, v5e.devices[:4], 1024, "adam",
                           {"learning_rate": 1e-3}, zero=True)
    gathered = re.findall(r"= (\w+)\[1024,1024\]\S* all-gather(?:-start)?\(",
                          text)
    assert gathered and set(gathered) == {"bf16"}, gathered


@pytest.mark.parametrize("rows,vocab,width,columns", [
    (4096, 25008, 2560, 512),
    (8192, 16032, 2048, 512),
    (12288, 30522, 768, 768),
    (4096, 200064, 2560, 2560),
], ids=["phi4_flash", "kanana2", "bert_words", "phi4_flash_published"])
def test_embedding_gradient_compiles_in_the_blocks_its_shape_picks(
        one_chip, quiet_cache, rows, vocab, width, columns):
    """The cotangent of ``Embedding``'s bfloat16 table at the three text
    cells' shapes and at the hybrid decoder's published vocabulary, added
    to a head's dW as under a tied table. Where the rule says blocks no
    scatter is left over the whole table (XLA's sort-and-walk emitter took
    9.8 ms over ``bf16[25008,2560]`` and takes 0.13 ms over each
    ``bf16[25008,512]``; my chip run, PR 32); where it says whole, XLA's one
    scatter stands, and at 200,064 rows that is the row-by-row emitter (no
    sorted ids), which does not walk the table; the program fits the
    chip."""
    import re

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import tensor

    assert tensor.embedding_grad_columns(rows, vocab, width) == columns

    def tied(dw, weight, ids, cot):
        _, pull = jax.vjp(lambda w: tensor._embedding(ids, w), weight)
        return dw + pull(cot)[0]

    table = _shape((vocab, width), jnp.bfloat16, one_chip)
    compiled = jax.jit(tied, donate_argnums=0).lower(
        table, table, _shape((rows,), jnp.int32, one_chip),
        _shape((rows, width), jnp.bfloat16, one_chip)).compile()
    text = compiled.as_text()
    scatters = re.findall(r"= bf16\[(\d+),(\d+)\]\S* scatter\(([^\n]*)",
                          text)
    assert [(int(v), int(w)) for v, w, _ in scatters] \
        == [(vocab, columns)] * (width // columns), scatters
    walks = ["indices_are_sorted=true" in rest for _, _, rest in scatters]
    assert walks == [vocab < 8 * rows] * len(scatters), walks
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes + memory.argument_size_in_bytes \
        + memory.output_size_in_bytes - memory.alias_size_in_bytes \
        < 15.75 * 2 ** 30


def _dropout_block_step_text(monkeypatch, devices, batch, width, hidden):
    """``_step_text`` under Adam of a BERT-shaped block: twice ``Dense``
    (GELU), ``Dense``, ``Dropout(0.1)``, the residual add, ``LayerNorm``."""
    from mxnet_tpu.gluon import nn

    class Block(nn.HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                for i in range(2):
                    setattr(self, f"ffn1_{i}", nn.Dense(
                        hidden, in_units=width, flatten=False))
                    setattr(self, f"ffn2_{i}", nn.Dense(
                        width, in_units=hidden, flatten=False))
                    setattr(self, f"drop_{i}", nn.Dropout(0.1))
                    setattr(self, f"norm_{i}", nn.LayerNorm(
                        in_channels=width))

        def hybrid_forward(self, F, x):
            for i in range(2):
                h = F.invoke("LeakyReLU", getattr(self, f"ffn1_{i}")(x),
                             act_type="gelu")
                h = getattr(self, f"drop_{i}")(getattr(self, f"ffn2_{i}")(h))
                x = getattr(self, f"norm_{i}")(x + h)
            return x

    return _step_text(monkeypatch, devices, Block(), batch + (width,),
                      "adam", {"learning_rate": 1e-3})


def _opperf():
    """``benchmark/opperf.py``, whose counters read a compiled text."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "benchmark"))
    import opperf

    return opperf


def test_dropout_mask_is_drawn_once_by_the_bit_generator(v5e, quiet_cache,
                                                         monkeypatch):
    """The mechanism's counter: generator ops a step = Dropout calls a step
    (2 here, 25 in ``bert_base``'s). The mask's words come from XLA's
    ``rng-bit-generator``, which the TPU compiler cannot copy into its
    consumers; with ``jax.random.bernoulli`` the threefry hash over the
    activation's ``u32`` shape (126 integer vector operations an element)
    was evaluated inside every fusion that wanted the mask, the products'
    among them: 104 times a step in BERT's, 2,184 ``xor`` ops (PR 33)."""
    text = _dropout_block_step_text(monkeypatch, v5e.devices[:1], (32, 384),
                                    768, 3072)
    counts = _opperf().dropout_program_counts(text, (32, 384, 768))
    assert counts == {"generator_ops": 2, "hashes": 0,
                      "hash_in_product": False}, counts


def test_exact_gelu_is_evaluated_once_a_layer(v5e, quiet_cache, monkeypatch):
    """The mechanism's counter: ``exponential`` instructions over the
    activation's float32 shape in the compiled Adam step = two a GELU call
    (the forward's ``erfc`` in ``ffn1``'s product epilogue, the density's
    ``exp`` in the dX fusion): 4 in this two-layer block, 24 in
    ``bert_base``'s step. With ``jax.nn.gelu(h, approximate=False)`` alone
    (until PR 35; a ``custom_vjp`` without the barrier too) XLA copied the
    ``erfc`` expansion, ~140 vector operations an element, into ``ffn2``'s
    product, ``ffn2.weight``'s dW product and the dX fusion as well: 8
    here, 48 in BERT's."""
    text = _dropout_block_step_text(monkeypatch, v5e.devices[:1], (32, 384),
                                    768, 3072)
    counts = _opperf().gelu_program_counts(text, (32, 384, 3072))
    assert counts["exponentials"] == 4, counts
    # ffn1's product with h and erfc as results, and the dX fusion with
    # ffn1's bias gradient: no dW and no ffn2 product among them
    assert counts["holders"] == [
        "bf16[3072], bf16[32,384,3072]"] * 2 + [
        "bf16[32,384,3072], bf16[32,384,3072]"] * 2, counts


def _named_instructions(text):
    """``[(name, result dtypes, opcode, op_name)]`` of every instruction of
    ``text`` that carries metadata."""
    import re

    out = []
    for m in re.finditer(
            r"^\s*(?:ROOT )?%(\S+) = (.+?) ([a-z][a-z\-]*)\(.*?"
            r'metadata=\{op_name="([^"]*)"', text, re.MULTILINE):
        out.append((m.group(1), re.findall(r"(\w+)\[[\d,]*\]", m.group(2)),
                    m.group(3), m.group(4)))
    return out


def test_step_text_names_update_and_backward(v5e, quiet_cache, monkeypatch):
    """What ``chipbench/harness/step_phases.py`` joins a trace to: in the
    compiled bf16 step the update fusion of each parameter, results
    ``(bf16, f32, f32, f32)``, carries ``trainer.update`` in its OWN
    ``op_name``, each dW product ``transpose(jvp(`` and each forward
    product ``jvp(`` without it; the guard's name is on no product."""
    from mxnet_tpu.parallel import sharded_trainer

    text = _bf16_step_text(monkeypatch, v5e.devices[:1], 4096, "adam",
                           {"learning_rate": 1e-3})
    entry = _named_instructions(text[text.index("ENTRY "):])
    fusions = [i for i in entry if i[2] == "fusion"]
    updates = [f for f in fusions
               if sorted(f[1]) == ["bf16", "f32", "f32", "f32"]]
    assert len(updates) == 2, updates
    assert all(sharded_trainer.UPDATE_SCOPE in f[3] for f in updates)
    products = [f for f in fusions if f[3].endswith("/dot_general")]
    # two layers: a dW product each, the guard's reduction fused in (a
    # ``pred`` beside the gradient), and a forward product each
    gradients = [f for f in products if "pred" in f[1]]
    forward = [f for f in products if "pred" not in f[1]]
    assert len(gradients) == 2 and len(forward) == 2, products
    assert all("transpose(jvp(" in f[3] for f in gradients), gradients
    assert all("jvp(" in f[3] and "transpose(" not in f[3]
               for f in forward), forward
    assert not [f for f in products if "trainer." in f[3]], products
    guards = [i for i in _named_instructions(text)
              if i[2] == "is-finite"]
    assert guards and all(sharded_trainer.GUARD_SCOPE in g[3]
                          for g in guards)


def test_step_scopes_leave_the_mosaic_calls_their_names(v5e, quiet_cache,
                                                        monkeypatch):
    """A Mosaic call's payload holds the kernel's name, the name comes from
    the scope stack, and jax's cache key does not strip it: a scope AROUND
    the forward would rename every Pallas call and compile the attention
    cells cold. The step's two scopes sit after the gradient: the calls of
    a latent-attention block are still ``jvp_mla.attention_*`` and
    ``transpose_jvp_mla.attention_*`` with no ``trainer.`` in them, and the
    lowered module, payloads and all, is the same without the two."""
    import contextlib
    import re

    import jax

    from mxnet_tpu import kernels
    from mxnet_tpu.gluon import nn

    def lowered():
        net = nn.MLAttention(256, num_heads=2, kv_lora_rank=128,
                             qk_nope_head_dim=128, qk_rope_head_dim=64,
                             v_head_dim=128)
        return _step_lowered(monkeypatch, v5e.devices[:1], net,
                             (1, 1024, 256), "adam", {"learning_rate": 1e-3})

    monkeypatch.setattr(kernels, "on_tpu", lambda: True)
    named = lowered()
    text = named.compile().as_text()
    calls = re.findall(r"%(\S+) = [^\n]*? custom-call\([^\n]*"
                       r'custom_call_target="tpu_custom_call"', text)
    assert len(calls) >= 2, calls
    assert all(re.match(r"(transpose_)?jvp_mla\.attention_", c)
               for c in calls), calls
    assert any(c.startswith("transpose_jvp_") for c in calls), calls
    assert not [c for c in calls if "trainer" in c], calls
    assert "trainer.update" in text
    scope = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope", lambda name: contextlib.nullcontext()
        if name.startswith("trainer.") else scope(name))
    assert lowered().as_text() == named.as_text()


# the lowered step of ``_deepseek_block`` on the tree BEFORE ``SparseMoE``
# learned ``bias_update_rate`` (PR 35's; jax 0.9.0): sha256 of the module's
# text, which holds no source location. A PR that changes the deepseek_v3
# step on purpose replaces it and says so.
_DEEPSEEK_BLOCK_STEP = \
    "6933eae19b934814e651f5e000cc07e66aa9277e8922ae7437ac276809500494"


def _deepseek_block(**moe):
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.model_zoo.text.deepseek_v3 import DeepseekV3Block

    cfg = {"hidden_size": 256, "rms_norm_eps": 1e-6,
           "num_attention_heads": 2, "kv_lora_rank": 64,
           "qk_nope_head_dim": 64, "qk_rope_head_dim": 64,
           "v_head_dim": 128, "rope_theta": 10000.0,
           "rope_interleave": True, "moe_intermediate_size": 128,
           "n_routed_experts": 8, "num_experts_per_tok": 2,
           "n_shared_experts": 1, "routed_scaling_factor": 2.5,
           "norm_topk_prob": True}
    net = DeepseekV3Block(cfg, True, (2, 4))
    if moe:
        with net.name_scope():
            net.ffn = nn.SparseMoE(256, 128, 8, 2, num_shared=1,
                                   routed_scaling_factor=2.5,
                                   experts_held=(2, 4), **moe)
    return net


def test_a_still_bias_leaves_the_deepseek_step_as_it_was(v5e, quiet_cache,
                                                         monkeypatch):
    """``bias_update_rate``'s default builds the expert layer without the
    rule, its counters or the router's third output: the lowered step of a
    ``deepseek_v3`` block is byte for byte the parent's. With a rate the
    same block counts the whole router under ``moe.balance``."""
    import hashlib

    def lowered(**moe):
        return _step_lowered(monkeypatch, v5e.devices[:1],
                             _deepseek_block(**moe), (2, 256, 256), "adam",
                             {"learning_rate": 1e-3})

    still = lowered().as_text()
    assert hashlib.sha256(still.encode()).hexdigest() == _DEEPSEEK_BLOCK_STEP
    assert "moe.balance" not in lowered().as_text(debug_info=True)
    moving = lowered(bias_update_rate=1e-3)
    assert moving.as_text() != still
    assert "moe.balance" in moving.as_text(debug_info=True)


def test_lfm2_kernels_compile_at_the_cells_shapes(one_chip, quiet_cache):
    """The new cell's kernels at its widths, one sequence of 8,192: the
    flash family with four query heads a key head (32 over 8 heads of 64)
    at the blocks the shape picks, forward and backward, and the gated
    short convolution's two passes over three (8192, 2048) streams."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.kernels import flash
    from mxnet_tpu.ops import registry

    bf = jnp.bfloat16
    q = _shape((1, 32, 8192, 64), bf, one_chip)
    kv = _shape((1, 8, 8192, 64), bf, one_chip)
    assert flash.default_blocks(8192, 8192, 64, 64) == (1024, 1024)
    assert flash.backward_blocks(8192, 8192, 64, 64) == (512, 512)
    assert flash._bucket(q, kv, kv, 0.125, True) \
        == "bh32_sq8192_sk8192_d64_bfloat16_c1_q1024k1024_g4"

    assert flash._supports(q, kv, kv, 0.125, True)
    assert flash._blocks_for(q, kv, kv) == (512, 512)

    def fwd_bwd(q_, k_, v_, cot_):
        out, lse = flash.flash_forward_lse(q_, k_, v_, 0.125, True, 1024,
                                           1024)
        return (out,) + flash.flash_backward_kernel(
            q_, k_, v_, out, lse, cot_, 0.125, True, 512, 512)

    text = jax.jit(fwd_bwd).lower(q, kv, kv, q).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    out, dq, dk, dv = jax.eval_shape(fwd_bwd, q, kv, kv, q)
    assert [(t.shape, t.dtype) for t in (out, dq, dk, dv)] \
        == [(t.shape, t.dtype) for t in (q, q, kv, kv)]

    gated = registry.get("_contrib_gated_short_conv").fn
    bcx = _shape((1, 8192, 6144), bf, one_chip)
    taps = _shape((2048, 3), bf, one_chip)
    cot = _shape((1, 8192, 2048), bf, one_chip)

    def conv(bcx, taps, cot):
        out, vjp = jax.vjp(gated, bcx, taps)
        return (out,) + vjp(cot)

    compiled = jax.jit(conv).lower(bcx, taps, cot).compile()
    # beside the results: the gated product z and the convolution's
    # cotangent in float32 (64 MiB each, what a shifted read wants
    # materialised) and the three cotangents before they are put side by
    # side; no float32 copy of bcx (192 MiB)
    assert compiled.memory_analysis().temp_size_in_bytes < 288 * 2 ** 20


# the lowered step of ``_lfm2_block`` on the tree BEFORE ``GQAttention``
# learned ``head_dim`` / ``window`` / ``rope_scaling`` and ``SparseMoE`` a
# softmax router (jax 0.9.0): sha256 of the module's text, which
# holds no source location. A PR that changes the lfm2_moe step on purpose
# replaces it and says so.
_LFM2_BLOCK_STEP = \
    "4511ab056871815657c72870541266c39f0a6c0830361345591d754f46f2188a"


def _lfm2_block():
    from mxnet_tpu.gluon.model_zoo.text.lfm2_moe import Lfm2MoeBlock

    cfg = {"hidden_size": 256, "norm_eps": 1e-5,
           "layer_types": ["conv", "full_attention"], "conv_L_cache": 3,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "rope_theta": 1e6, "num_dense_layers": 1,
           "intermediate_size": 512, "moe_intermediate_size": 128,
           "num_experts": 8, "num_experts_per_tok": 2,
           "routed_scaling_factor": 1.0, "norm_topk_prob": True}
    return Lfm2MoeBlock(cfg, 1, (2, 4), bias_update_rate=1e-3)


def test_the_default_paths_leave_the_lfm2_step_as_it_was(v5e, quiet_cache,
                                                         monkeypatch):
    """The defaults of the rotary op (no YaRN), of ``GQAttention`` (width
    from the heads, no window, q/k norms) and of ``SparseMoE`` (the sigmoid
    router and its bias's rule) build an ``lfm2_moe`` attention block
    whose lowered step is byte for byte the parent's; the ``deepseek_v3``
    block's is held by ``test_a_still_bias_leaves_the_deepseek_step_as_it_was``."""
    import hashlib

    lowered = _step_lowered(monkeypatch, v5e.devices[:1], _lfm2_block(),
                            (2, 256, 256), "adam", {"learning_rate": 1e-3})
    text = lowered.as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == _LFM2_BLOCK_STEP


@pytest.mark.parametrize("window,forward_blocks", [(None, (1024, 1024)),
                                                   (1024, (1024, 1024))],
                         ids=["full", "window1024"])
def test_mellum_flash_compiles_at_the_cells_buckets(one_chip, quiet_cache,
                                                    window, forward_blocks):
    """The mellum cell's attention, one sequence of 8,192: 32 query heads
    128 wide read 4 key heads (g8), causal, with and without the window of
    1,024: the forward and the fused backward are one Mosaic call each,
    at the blocks the shape picks."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.kernels import flash

    bf = jnp.bfloat16
    q = _shape((1, 32, 8192, 128), bf, one_chip)
    kv = _shape((1, 4, 8192, 128), bf, one_chip)
    scale = 128 ** -0.5
    assert flash._supports(q, kv, kv, scale, True, window=window)
    assert flash._blocks(q, kv, kv, window=window) == forward_blocks
    assert flash._blocks_for(q, kv, kv, window) == (512, 512)
    assert flash._bucket(q, kv, kv, scale, True, window=window) == (
        "bh32_sq8192_sk8192_d128_bfloat16_c1_q1024k1024_g8"
        + (f"_w{window}" if window else ""))

    def fwd_bwd(q_, k_, v_, cot_):
        out, lse = flash.flash_forward_lse(q_, k_, v_, scale, True,
                                           *forward_blocks, window=window)
        return (out,) + flash.flash_backward_kernel(
            q_, k_, v_, out, lse, cot_, scale, True, 512, 512, window=window)

    text = jax.jit(fwd_bwd).lower(q, kv, kv, q).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    out, dq, dk, dv = jax.eval_shape(fwd_bwd, q, kv, kv, q)
    assert [(t.shape, t.dtype) for t in (out, dq, dk, dv)] \
        == [(t.shape, t.dtype) for t in (q, q, kv, kv)]


@pytest.mark.parametrize("rows,held,h,f", [
    (17408, 16, 2304, 896), (9216, 8, 2048, 1792), (7168, 16, 2048, 768)],
    ids=["mellum", "lfm2", "kanana"])
def test_grouped_matmul_compiles_at_the_first_rungs(one_chip, quiet_cache,
                                                    rows, held, h, f):
    """The six grouped products of an expert cell's first rung (``nn``,
    ``nt`` and ``tn`` with K x N = h x f and f x h), each one Mosaic call
    at the tiles ``grouped_matmul.tiles`` gives the shape, inside the
    scoped VMEM a v5e gives a call, results in the operands' type."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.kernels import grouped_matmul as gm

    bf = jnp.bfloat16
    sizes = _shape((held,), jnp.int32, one_chip)
    for k, n in ((h, f), (f, h)):
        a = _shape((rows, k), bf, one_chip)
        for mode, b, out in (
                ("nn", (held, k, n), (rows, n)),
                ("nt", (held, n, k), (rows, n)),
                ("tn", (rows, n), (held, k, n))):
            b = _shape(b, bf, one_chip)
            assert gm._supports(a, b, sizes, mode)
            tile = gm.tiles(mode, rows, k, n)
            if mode == "tn":
                fn = functools.partial(gm._tgmm, tile=tile, interpret=False)
            else:
                fn = functools.partial(gm._gmm, tile=tile,
                                       transpose=mode == "nt",
                                       interpret=False)
            text = jax.jit(fn).lower(a, b, sizes).compile().as_text()
            assert text.count('custom_call_target="tpu_custom_call"') == 1, \
                (mode, k, n, tile)
            got = jax.eval_shape(fn, a, b, sizes)
            assert (got.shape, got.dtype) == (out, bf)
