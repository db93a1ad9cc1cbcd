"""The deepseek_v3 text model (``gluon.model_zoo.text``) and the blocks and
ops under it against the plain float32 reference of the benchmark's
``kanana2_30b_a3b_ep8`` configuration, at a small size on the CPU, all on
seeded weights: hidden 64, 8 experts of which 2 are held, 1 dense + 2
expert layers, 4 heads of 24|8 / 16, vocabulary 64.

Ops exercised by name: _contrib_rms_norm, _contrib_rotary_embedding,
_contrib_gated_silu, _contrib_sparse_moe, _contrib_lm_cross_entropy,
_contrib_flash_attention.
"""
import copy
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, kernels
from mxnet_tpu.gluon import loss as gloss
from mxnet_tpu.gluon import nn
from mxnet_tpu.kernels import flash
from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer
from mxnet_tpu.parallel import moe as pmoe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL = {"hidden_size": 64, "intermediate_size": 96,
         "moe_intermediate_size": 32, "num_attention_heads": 4,
         "kv_lora_rank": 32, "qk_nope_head_dim": 24, "qk_rope_head_dim": 8,
         "v_head_dim": 16, "num_hidden_layers": 3, "num_experts_per_tok": 3,
         "n_routed_experts": 2, "vocab_size": 64, "dtype": "float32"}
S = 128


@pytest.fixture(scope="module")
def model():
    from chipbench.harness import bench as hbench

    return hbench.load_module(os.path.join(
        REPO, "chipbench", "configs", "kanana2_30b_a3b_ep8", "model.py"))


def _cfg(first=0, count=2, **changes):
    from chipbench.harness import bench as hbench

    cfg = hbench.load_json(os.path.join(
        REPO, "chipbench", "configs", "kanana2_30b_a3b_ep8", "config.json"))
    cfg = copy.deepcopy(dict(cfg, **SMALL, **changes))
    cfg["deployment"].update(router_width=8, experts_held=[first, count])
    return cfg


def _build(model, cfg, seed):
    """``model.build`` with the attention kernel in the Pallas interpreter
    (off the TPU ``dispatch`` takes the dense side on its own)."""
    from mxnet_tpu.gluon.model_zoo import text

    published, held = model.model_config(cfg)
    net = text.get_model("deepseek_v3", experts_held=held, interpret=True,
                         **published)
    net.cast(cfg["dtype"])
    net.initialize(mx.init.Zero(), ctx=mx.cpu())
    model._set_params(net, cfg, model.make_params(cfg, seed))
    return net


def _batch(cfg, seed=3, b=2):
    key = jax.random.PRNGKey(seed)
    ids = jax.random.randint(key, (b, S + 1), 0, cfg["vocab_size"])
    return np.asarray(ids[:, :-1], np.int32), np.asarray(ids[:, 1:], np.int32)


def _close(got, want, rtol=2e-4, name=""):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = max(float(np.abs(want).max()), 1e-12)
    assert got.shape == want.shape, name
    assert float(np.abs(got - want).max()) <= rtol * scale, \
        (name, float(np.abs(got - want).max()), scale)


# ------------------------------------------- (a) system against reference -

def test_logits_loss_and_every_gradient_match_the_reference(model):
    cfg = _cfg()
    x, y = _batch(cfg)
    net = _build(model, cfg, 5)
    loss_fn = model.loss(cfg)
    params = model.export_params(net, cfg)
    names = [n for n, _, init in model.layout(cfg)
             if init in ("normal", "ones")]

    def ref_loss(p):
        return model.reference(cfg, dict(params, **p), (x, y))["loss"]

    want = model.reference(cfg, params, (x, y))
    want_grads = jax.grad(ref_loss)({n: params[n] for n in names})

    trainable = [p for p in net.collect_params().values()
                 if p.grad_req != "null"]
    assert len(trainable) == len(names)
    with autograd.record():
        out = net(mx.nd.array(x, dtype="int32"))
        loss = loss_fn(out, mx.nd.array(y, dtype="int32")).mean()
    loss.backward()
    _close(out.asnumpy(), want["logits"], name="logits")
    assert float(loss.asscalar()) == pytest.approx(float(want["loss"]),
                                                   rel=1e-5)
    for name, p in zip(names, trainable):
        _close(p.grad().asnumpy(), want_grads[name], rtol=2e-3, name=name)
        assert float(np.abs(want_grads[name]).max()) > 0, name


def test_trains_through_sharded_trainer_and_predicts(model):
    cfg = _cfg()
    x, y = _batch(cfg)
    net = _build(model, cfg, 5)
    trainer = ShardedTrainer(
        net, model.loss(cfg), "adam",
        {"learning_rate": 1e-3, "multi_precision": True},
        mesh=DeviceMesh({"dp": 1}))
    losses = [float(trainer.step(x, y).asscalar()) for _ in range(4)]
    assert losses[-1] < losses[0] and np.isfinite(losses).all()
    assert trainer.skipped_steps == 0
    # the counters were updated inside the step: 4 calls a layer, and
    # every pair that a held expert got
    load = net.expert_load()
    assert sorted(load) == [1, 2]
    for rec in load.values():
        assert rec["calls"] == 4 and len(rec["pairs"]) == 2
        assert 0 < sum(rec["pairs"]) <= 4 * 2 * S * 2
        assert max(rec["pairs"]) <= rec["peak"] <= sum(rec["pairs"])
    from mxnet_tpu.telemetry import registry

    gauge = registry.get("mxtpu_moe_expert_pairs")
    assert gauge is not None and gauge.snapshot()
    # inference leaves the counters alone and agrees with the reference
    got = trainer.predict(x).asnumpy()
    assert net.expert_load() == load
    want = model.reference(cfg, model.export_params(net, cfg), (x, None))
    _close(got, want["logits"], name="predict")


# --------------------------------------------------- (b) the share test ---

def test_shares_add_up_to_the_uncut_layer(model):
    """The routed parts of all 4 shares plus the shared experts counted
    once equal the uncut reference's layer output."""
    cfg = _cfg(0, 8)
    h, f, e = cfg["hidden_size"], cfg["moe_intermediate_size"], 8
    rng = np.random.RandomState(0)
    x = rng.randn(2, S, h).astype(np.float32)
    w = {"router.weight": rng.randn(e, h) * 0.3,
         "router.bias": rng.randn(e) * 0.1,
         "experts.gate": rng.randn(e, h, f) * 0.1,
         "experts.up": rng.randn(e, h, f) * 0.1,
         "experts.down": rng.randn(e, f, h) * 0.1,
         "shared.gate.weight": rng.randn(2 * f, h) * 0.1,
         "shared.up.weight": rng.randn(2 * f, h) * 0.1,
         "shared.down.weight": rng.randn(h, 2 * f) * 0.1}
    w = {k: v.astype(np.float32) for k, v in w.items()}

    def share(first, count):
        blk = nn.SparseMoE(h, f, e, 3, num_shared=2,
                           routed_scaling_factor=2.448,
                           experts_held=(first, count))
        blk.initialize()
        sl = slice(first, first + count)
        for p, v in zip(blk.collect_params().values(), [
                w["router.weight"], w["router.bias"],
                w["experts.gate"][sl], w["experts.up"][sl],
                w["experts.down"][sl], np.zeros(count), np.zeros(1),
                np.zeros(1), w["shared.gate.weight"],
                w["shared.up.weight"], w["shared.down.weight"]]):
            p.set_data(mx.nd.array(v.astype(np.float32)))
        return blk, blk(mx.nd.array(x)).asnumpy()

    shared_blk = nn.GatedMLP(h, 2 * f)
    shared_blk.initialize()
    for p, k in zip(shared_blk.collect_params().values(),
                    ("gate", "up", "down")):
        p.set_data(mx.nd.array(w[f"shared.{k}.weight"]))
    shared = shared_blk(mx.nd.array(x)).asnumpy()

    def ref(held):
        sl = slice(held[0], held[0] + held[1])   # the weights held there
        p = {f"m.{k}": jnp.asarray(v[sl] if k.startswith("experts.") else v)
             for k, v in w.items()}
        with jax.default_matmul_precision("highest"):
            out = model.expert_layer(cfg, p, "m",
                                     jnp.asarray(x.reshape(-1, h)), held)
        return np.asarray(out).reshape(x.shape)

    whole = ref((0, 8))                  # the uncut reference's layer
    _close(share(0, 8)[1], whole, name="whole layer")
    parts = [share(first, 2)[1] - shared for first in (0, 2, 4, 6)]
    assert all(np.abs(part).max() > 1e-3 for part in parts)
    _close(sum(parts) + shared, whole, name="sum of the shares")
    # and each share is the reference's share
    for first in (0, 6):
        _close(share(first, 2)[1], ref((first, 2)), name=f"share {first}")


def test_reference_share_is_the_models_share(model):
    """``reference(experts_held=...)`` of the whole model: the logits of
    a share differ from the uncut model's, and the system built with the
    same share agrees with it."""
    cfg = _cfg(2, 4)
    x, _ = _batch(cfg)
    net = _build(model, cfg, 9)
    params = model.export_params(net, cfg)
    got = net(mx.nd.array(x, dtype="int32")).asnumpy()
    _close(got, model.reference(cfg, params, (x, None))["logits"])
    other = model.reference(cfg, params, (x, None),
                            experts_held=(2, 2))["logits"]
    assert np.abs(np.asarray(other) - got).max() > 1e-3


# ------------------------------------------------ (c) no dropped token ----

def test_every_token_on_one_held_expert_is_not_dropped(model):
    """A router forced to send every token to one held expert (and its
    other choices to absent ones): 256 rows in one group, none dropped."""
    cfg = _cfg(2, 2)
    x, y = _batch(cfg)
    net = _build(model, cfg, 4)
    bias = np.zeros(8, np.float32)
    bias[3] = 4.0                      # held: every token's first choice
    bias[2] = -4.0                     # the other held expert: never
    for _i, moe in net.moe_layers():
        moe.router_bias.set_data(mx.nd.array(bias))
    params = model.export_params(net, cfg)
    with autograd.record(train_mode=True):
        out = net(mx.nd.array(x, dtype="int32"))
    want = model.reference(cfg, params, (x, None))["logits"]
    _close(out.asnumpy(), want, name="one expert takes all")
    for rec in net.expert_load().values():
        assert rec["pairs"] == [0.0, 2.0 * S] and rec["peak"] == 2.0 * S


# ------------------------------------- (d) the kernel with two widths -----

# the shape's own blocks (the whole 256, BERT's whole 384) and a forced
# 128 x 128, whose forward carries the online softmax over k blocks and,
# when causal, skips the block above the diagonal; the backward reads the
# forward's ``out`` and ``lse`` in all three
@pytest.mark.parametrize("d,dv", [(32, 16), (192, 128), (64, 64)])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s,blocks", [(256, {}), (384, {}),
                                      (256, {"block_q": 128, "block_k": 128})],
                         ids=["s256_whole", "s384_whole", "s256_q128k128"])
def test_flash_kernel_forward_and_backward(d, dv, causal, s, blocks):
    rng = np.random.RandomState(d + dv)
    q = jnp.asarray(rng.randn(1, 2, s, d), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, s, d), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, s, dv), jnp.float32)
    cot = jnp.asarray(rng.randn(1, 2, s, dv), jnp.float32)
    scale = d ** -0.5
    assert flash._blocks(q, k, v, **blocks) == (
        blocks.get("block_q", s), blocks.get("block_k", s))

    def run(fn):
        out, vjp = jax.vjp(lambda a, b, c: fn(a, b, c), q, k, v)
        return (out,) + vjp(cot)

    got = run(lambda a, b, c: flash._kernel(a, b, c, scale, causal=causal,
                                            interpret=True, **blocks))
    want = run(lambda a, b, c: flash.flash_attention_reference(
        a, b, c, scale, causal))
    assert got[0].shape == (1, 2, s, dv)
    for g, w, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-5, err_msg=name)


def _attention_case(d, dv, causal, sq=256, sk=256, dtype=jnp.float32):
    rng = np.random.RandomState(d + dv + sk)
    q = jnp.asarray(rng.randn(1, 2, sq, d), dtype)
    k = jnp.asarray(rng.randn(1, 2, sk, d), dtype)
    v = jnp.asarray(rng.randn(1, 2, sk, dv), dtype)
    cot = jnp.asarray(rng.randn(1, 2, sq, dv), dtype)
    return q, k, v, cot, d ** -0.5


# the forward alone: BERT's bucket (384 x 384 whole, d64), the same
# causal, and sequences of several blocks: square and both oblongs with
# block pairs above the diagonal (their index maps clamped), and more
# keys than queries with no mask
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("d,dv,sq,sk,causal,blocks", [
    (64, 64, 384, 384, False, (384, 384)),
    (64, 64, 384, 384, True, (384, 384)),
    (32, 16, 512, 512, True, (128, 128)),
    (32, 16, 512, 512, True, (128, 256)),
    (32, 16, 512, 512, True, (256, 128)),
    (192, 128, 256, 512, False, (128, 256)),
])
@pytest.mark.parametrize("heads", [None, 1],
                         ids=["heads_from_shape", "one_head"])
def test_flash_forward_in_the_input_dtype(dtype, d, dv, sq, sk, causal,
                                          blocks, heads):
    """``out`` against the dense reference and ``lse`` against
    ``row_log_sum_exp``, both float32 on the SAME (rounded) inputs:
    float32 at the 2e-5 the family registers; bf16 (operands of both
    matmuls bf16, ``p`` rounded to bf16 before ``p @ v``, everything else
    float32) within 1e-2 of the largest |out|, ``lse`` at 2e-5 still: a
    bf16 product is exact in the float32 sum."""
    tolerance = " ".join(kernels.entry("flash_attention").tolerance.split())
    assert "f32 rtol=2e-5 atol=2e-5" in tolerance
    assert "within 1e-2 of the largest |out|" in tolerance
    q, k, v, _, scale = _attention_case(d, dv, causal, sq=sq, sk=sk,
                                        dtype=dtype)
    # both heads of a whole-sequence tile go to one program, unless told
    per_program = heads or (2 if blocks == (sq, sk) else 1)
    assert flash.heads_a_program(2, sq, sk, *blocks) == \
        (2 if blocks == (sq, sk) else 1)
    out, lse = flash.flash_forward_lse(q, k, v, scale, causal, *blocks,
                                       interpret=True, heads=heads)
    assert out.dtype == dtype and out.shape == (1, 2, sq, dv)
    assert lse.dtype == jnp.float32 and lse.shape == (1, 2, sq)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    want = np.asarray(flash.flash_attention_reference(*f32, scale, causal))
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(flash.row_log_sum_exp(
            f32[0], f32[1], scale, causal)), rtol=2e-5, atol=2e-5)
    if dtype == jnp.float32:
        np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5,
                                   atol=2e-5)
    else:
        err = np.abs(np.asarray(out, np.float32) - want).max()
        assert err <= 1e-2 * np.abs(want).max(), err
    # both matmuls of every traced tile take the inputs' dtype and sum
    # in float32: one tile a head of the program, or a masked and an
    # unmasked one when causal
    dots = _dot_generals(jax.make_jaxpr(
        lambda *a: flash.flash_forward_lse(
            *a, scale, causal, *blocks, interpret=True,
            heads=heads))(q, k, v).jaxpr)
    assert len(dots) == (4 if causal else 2) * per_program
    for eqn in dots:
        assert [x.aval.dtype for x in eqn.invars] == [dtype] * 2
        assert eqn.outvars[0].aval.dtype == jnp.float32


@pytest.mark.parametrize("shape,heads", [
    # (batch*heads, sq, sk, block_q, block_k): BERT's bucket and the
    # benchmark's toy shape take four heads a program
    ((384, 384, 384, 384, 384), 4),
    ((8, 128, 128, 128, 128), 4),
    # as many as divide batch*heads, and as keep a program at 1024 x 1024
    # scores
    ((6, 384, 384, 384, 384), 2),
    ((7, 384, 384, 384, 384), 1),
    ((64, 640, 640, 640, 640), 2),
    ((64, 1024, 1024, 1024, 1024), 1),
    # a head of several tiles is its own program
    ((64, 4096, 4096, 1024, 1024), 1),
    ((384, 384, 384, 384, 128), 1),
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_heads_a_program_follow_the_shape(shape, heads):
    assert flash.heads_a_program(*shape) == heads


def test_flash_forward_takes_several_heads_a_program_with_a_carry():
    """Forced: two heads a program over several k blocks, each head with
    its own online-softmax state."""
    q, k, v, _, scale = _attention_case(32, 16, True, sq=512, sk=512)
    one = flash.flash_forward_lse(q, k, v, scale, True, 256, 128,
                                  interpret=True)
    two = flash.flash_forward_lse(q, k, v, scale, True, 256, 128,
                                  interpret=True, heads=2)
    for a, b in zip(one, two):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(
        np.asarray(two[0]), np.asarray(flash.flash_attention_reference(
            q, k, v, scale, True)), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("block_q,block_k", [(128, 128), (128, 256),
                                             (256, 128), (384, 128)])
def test_causal_maps_name_no_block_above_the_diagonal(block_q, block_k):
    """A grid step above the diagonal names the block of the nearest step
    that is not (so its fetch moves nothing); a step at or under the
    diagonal names its own."""
    q_of, k_of = flash._causal_maps(True, block_q, block_k)
    s = 3 * 256 * 2
    for i in range(s // block_q):
        for j in range(s // block_k):
            reached = (i + 1) * block_q > j * block_k
            if reached:
                assert (int(q_of(i, j)), int(k_of(i, j))) == (i, j)
                continue
            # the last k block q block i reaches / the first q block
            # that reaches k block j
            assert int(k_of(i, j)) == ((i + 1) * block_q - 1) // block_k < j
            assert int(q_of(i, j)) == (j * block_k) // block_q > i
    q_of, k_of = flash._causal_maps(False, block_q, block_k)
    assert (q_of(0, 3), k_of(0, 3)) == (0, 3)


def _dense_gradient(q, k, v, cot, scale, causal):
    _, vjp = jax.vjp(lambda a, b, c: flash.flash_attention_reference(
        a, b, c, scale, causal), q, k, v)
    return vjp(cot)


# the forward runs at 128 x 128; the backward at the same blocks, at
# unequal ones, and at the whole sequence in one block; and once with
# fewer keys than queries and no mask; each as one fused call and as two
@pytest.mark.parametrize("fused", [True, False], ids=["fused", "two_calls"])
@pytest.mark.parametrize("d,dv,sk,causal,blocks", [
    (d, dv, 256, causal, blocks)
    for d, dv in [(64, 64), (192, 128), (32, 16)]
    for causal in (False, True)
    for blocks in [(128, 128), (64, 128), (256, 256)]
] + [(32, 16, 128, False, (64, 128))])
def test_flash_backward_kernel_against_dense(d, dv, sk, causal, blocks,
                                             fused):
    q, k, v, cot, scale = _attention_case(d, dv, causal, sk=sk)
    out, lse = flash.flash_forward_lse(q, k, v, scale, causal, 128, 128,
                                       interpret=True)
    got = flash.flash_backward_kernel(q, k, v, out, lse, cot, scale, causal,
                                      *blocks, interpret=True, fused=fused)
    dense = _dense_gradient(q, k, v, cot, scale, causal)
    for g, w, name in zip(got, dense, ("dq", "dk", "dv")):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-5, err_msg=name)


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "two_calls"])
def test_flash_backward_kernel_in_bfloat16(fused):
    """MXU operands in the inputs' dtype: q, k, v, d_out, and p / dS where
    they feed a matmul, are bf16; everything else float32. Against the
    float32 dense gradient of the SAME rounded inputs, at the tolerance
    the family registers."""
    assert "within 2e-2 of the largest |gradient|" in " ".join(
        kernels.entry("flash_attention_bwd").tolerance.split())
    q, k, v, cot, scale = _attention_case(192, 128, True,
                                          dtype=jnp.bfloat16)
    out, lse = flash.flash_forward_lse(q, k, v, scale, True, 128, 128,
                                       interpret=True)
    assert out.dtype == jnp.bfloat16 and lse.dtype == jnp.float32
    got = flash.flash_backward_kernel(q, k, v, out, lse, cot, scale, True,
                                      128, 64, interpret=True, fused=fused)
    f32 = [x.astype(jnp.float32) for x in (q, k, v, cot)]
    want = _dense_gradient(*f32, scale, True)
    for g, w, name in zip(got, want, ("dq", "dk", "dv")):
        assert g.dtype == jnp.bfloat16
        err = np.abs(np.asarray(g, np.float32) - np.asarray(w)).max()
        assert err <= 2e-2 * np.abs(np.asarray(w)).max(), (name, err)
    # the operands of the five matmuls are bf16 in the traced kernels,
    # their sums float32
    dots = _dot_generals(jax.make_jaxpr(
        lambda *a: flash.flash_backward_kernel(
            *a, scale, True, 128, 64, interpret=True, fused=fused))(
                q, k, v, out, lse, cot).jaxpr)
    # a masked and an unmasked tile, each with S, dP, dV, dK, dQ; two
    # calls compute S and dP in both
    assert len(dots) == (10 if fused else 14)
    for eqn in dots:
        assert [x.aval.dtype for x in eqn.invars] == [jnp.bfloat16] * 2
        assert eqn.outvars[0].aval.dtype == jnp.float32


def _dot_generals(jaxpr):
    """Every ``dot_general`` equation of a jaxpr and of the jaxprs its
    equations hold (the kernel of a ``pallas_call``, a ``cond``'s
    branches)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found.extend(_dot_generals(inner))
    return found


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_writes_the_rows_log_sum_exp(causal):
    q, k, v, _, scale = _attention_case(32, 16, causal, sk=128)
    out, lse = flash.flash_forward_lse(q, k, v, scale, causal, 64, 32,
                                       interpret=True)
    # written out here and not through ``flash.row_log_sum_exp``, which
    # the smoke run and the autotuner use: that oracle is held to this too
    s = np.einsum("bhqd,bhkd->bhqk", np.asarray(q, np.float64),
                  np.asarray(k, np.float64)) * scale
    if causal:
        s = np.where(np.tril(np.ones(s.shape[-2:], bool)), s, -np.inf)
    want = np.log(np.exp(s - s.max(-1, keepdims=True)).sum(-1)) + s.max(-1)
    assert lse.shape == (1, 2, 256) and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(lse), want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(flash.row_log_sum_exp(q, k, scale, causal)), want,
        rtol=1e-5, atol=1e-5)
    # the output is still the call's first result, and the same array
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(flash.flash_forward(
            q, k, v, scale, causal, 64, 32, interpret=True)))


def test_flash_backward_is_a_counted_decision_of_its_own(monkeypatch):
    q, k, v, cot, scale = _attention_case(32, 16, True)

    def grads(**kw):
        _, vjp = jax.vjp(lambda a, b, c: kernels.dispatch(
            "flash_attention", a, b, c, scale, causal=True, **kw), q, k, v)
        return vjp(cot)

    kernels.reset_stats()
    got = grads(interpret=True)
    stats = kernels.dispatch_stats()
    # the backward's own blocks name its bucket: 256 positions, one block
    assert flash.backward_blocks(256, 256, 32, 16) == (256, 256)
    assert stats["flash_attention_bwd"] == {
        "kernel": 1, "xla": 0, "reasons": {"interpret_forced": 1},
        "buckets": {"bh2_sq256_sk256_d32v16_float32_c1_q256k256":
                    {"kernel": 1, "xla": 0}}}
    assert list(stats["flash_attention"]["buckets"]) \
        == ["bh2_sq256_sk256_d32v16_float32_c1_q256k256"]
    # the table decides it like any family: a row for the bucket that
    # names XLA sends the backward to the dense gradient, and the result
    # agrees
    e = kernels.entry("flash_attention_bwd")
    monkeypatch.setattr(
        kernels.table, "lookup", lambda family, bucket: {"winner": "xla"}
        if family == "flash_attention_bwd" else None)
    out, lse = flash.flash_forward_lse(q, k, v, scale, True, 128, 128,
                                       interpret=True)
    assert kernels.choice_for("flash_attention_bwd", q, k, v, out, lse, cot,
                              scale, causal=True) == ("xla", "tuned")
    dense = kernels.dispatch("flash_attention_bwd", q, k, v, out, lse, cot,
                             scale, causal=True)
    for g, w in zip(got, dense):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-5)
    monkeypatch.undo()
    # MXNET_TPU_KERNELS=0 covers it
    monkeypatch.setenv("MXNET_TPU_KERNELS", "0")
    assert kernels.choice_for("flash_attention_bwd", q, k, v, out, lse, cot,
                              scale, causal=True) == ("xla", "env_disabled")
    # one call where a head's dQ fits VMEM beside the tiles, two beyond
    def calls(sq, d):
        z = jnp.zeros((1, 1, sq, d), jnp.bfloat16)
        text = str(jax.make_jaxpr(lambda a, l: flash.flash_backward_kernel(
            a, a, a, a, l, a, 0.1, True, 512, 512, interpret=True))(
                z, z[..., 0].astype(jnp.float32)))
        return text.count("pallas_call")

    assert calls(4096, 192) == 1 and calls(8192, 192) == 2
    # blocks follow the shape: 512 at 4,096 positions, the whole sequence
    # at BERT's 384, and a length no block of 128s divides is refused
    assert flash.backward_blocks(4096, 4096, 192, 128) == (512, 512)
    assert flash.backward_blocks(384, 384, 64, 64) == (384, 384)
    assert flash.backward_blocks(1536, 640, 64, 64) == (512, 128)
    z = jnp.zeros((1, 1, 1000, 64))
    assert flash.backward_blocks(1000, 1000, 64, 64) == (8, 8)
    assert not e.supports(z, z, z, z, z[..., 0], z, 0.125)
    assert e.bucket(
        *(jnp.zeros((2, 32, 4096, w), jnp.bfloat16) for w in (192, 192, 128,
                                                               128)),
        jnp.zeros((2, 32, 4096)), jnp.zeros((2, 32, 4096, 128)), 0.07,
        causal=True) == "bh64_sq4096_sk4096_d192v128_bfloat16_c1_q512k512"


def test_flash_bucket_and_supports_know_both_widths():
    def arrays(d, dv, sk=256):
        return (jnp.zeros((2, 4, 256, d), jnp.bfloat16),
                jnp.zeros((2, 4, sk, d), jnp.bfloat16),
                jnp.zeros((2, 4, sk, dv), jnp.bfloat16))

    # equal widths keep the key's form from before values had their own
    assert flash._bucket(*arrays(64, 64), 0.125) \
        == "bh8_sq256_sk256_d64_bfloat16_c0_q256k256"
    assert flash._bucket(*arrays(192, 128), 0.1, causal=True) \
        == "bh8_sq256_sk256_d192v128_bfloat16_c1_q256k256"
    assert flash._supports(*arrays(192, 128), 0.1)
    assert not flash._supports(*arrays(192, 100), 0.1)     # dv % 8
    q, k, v = arrays(192, 128)
    assert not flash._supports(q, k[..., :128], v, 0.1)    # k narrower
    assert not flash._supports(q, k, v[:, :, :128], 0.1)   # fewer values
    kernels.reset_stats()
    kernels.dispatch("flash_attention", *arrays(32, 16), 0.2, causal=True,
                     interpret=True)
    stats = kernels.dispatch_stats()["flash_attention"]
    assert stats["buckets"] == {
        "bh8_sq256_sk256_d32v16_bfloat16_c1_q256k256":
            {"kernel": 1, "xla": 0}}


@pytest.mark.parametrize("shape,blocks", [
    # a side of up to 1024 positions is one block, whatever the widths:
    # BERT's bucket, the benchmark's toy shape, a length no power of two
    ((384, 384, 64, 64), (384, 384)),
    ((128, 128, 32, 16), (128, 128)),
    ((640, 1024, 64, 64), (640, 1024)),
    # a longer one: the largest power of two up to 1024 that divides it,
    # with a value width of its own (the language model's bucket) or not
    ((4096, 4096, 192, 128), (1024, 1024)),
    ((4096, 4096, 128, 128), (1024, 1024)),
    ((8192, 2048, 192, 128), (1024, 1024)),
    ((1536, 384, 192, 128), (512, 384)),
    ((1152, 4096, 64, 64), (128, 1024)),
    # no multiple of 128 divides it: 128, and ``_supports`` decides
    ((100, 100, 192, 128), (128, 128)),
    # heads too wide for the tile to fit VMEM: the longer side steps down
    ((4096, 4096, 512, 512), (1024, 512)),
    ((4096, 4096, 512, 256, 4), (512, 512)),
    # float32 operands at the language model's widths still fit
    ((4096, 4096, 192, 128, 4), (1024, 1024)),
], ids=lambda v: "x".join(map(str, v)))
def test_default_blocks_follow_the_shape(shape, blocks):
    assert flash.default_blocks(*shape) == blocks
    assert flash._forward_vmem_bytes(
        *blocks, *shape[2:4], *(shape[4:] or (2,))) <= flash._VMEM_BUDGET


@pytest.mark.parametrize("hybridize", [False, True],
                         ids=["imperative", "hybridized"])
def test_mlattention_names_no_block(hybridize):
    """The layer hands the op no block: the kernel family picks them from
    the shape, on the imperative path and through a traced Symbol alike."""
    attn = nn.MLAttention(64, 4, 32, 24, 8, 16, interpret=True)
    attn.initialize(mx.init.Xavier())
    if hybridize:
        attn.hybridize()
    kernels.reset_stats()
    out = attn(mx.nd.array(np.random.RandomState(0).randn(1, 256, 64)))
    assert out.shape == (1, 256, 64)
    assert list(kernels.dispatch_stats()["flash_attention"]["buckets"]) \
        == ["bh4_sq256_sk256_d32v16_float32_c1_q256k256"]


def test_equal_widths_trace_to_the_program_they_did():
    """Where dv == d the forward and the backward trace to the jaxpr of
    the one-width code (kept here, as it was, for this comparison)."""
    q = jnp.zeros((1, 2, 256, 64), jnp.bfloat16)

    def new(a, b, c):
        return jax.vjp(lambda *t: flash._kernel(*t, 0.125, interpret=True),
                       a, b, c)[1](a)

    text = str(jax.make_jaxpr(new)(q, q, q))
    # every array in the program is 64 wide or a (block, block) tile
    assert "192" not in text and "pallas_call" in text
    out = flash.flash_forward(q, q, q, 0.125, False, 128, 128,
                              interpret=True)
    assert out.shape == q.shape and out.dtype == q.dtype


# ------------------------------------------------- (e) the router ---------

def _route(x, w, b, **kw):
    args = dict(top_k=2, scale=1.0, norm_topk=True)
    args.update(kw)
    ids, wt = pmoe.route_topk(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b),
                              args["top_k"], args["scale"],
                              args["norm_topk"])
    return np.asarray(ids), np.asarray(wt)


def test_router_bias_moves_the_choice_and_not_the_weights():
    x = np.eye(4, dtype=np.float32)[:1] * 2.0            # one token
    w = np.array([[1.0, 0, 0, 0], [0.5, 0, 0, 0], [0.0, 0, 0, 0],
                  [-1.0, 0, 0, 0]], np.float32)          # logits 2, 1, 0, -2
    s = 1 / (1 + np.exp(-np.array([2.0, 1.0, 0.0, -2.0])))
    ids, wt = _route(x, w, np.zeros(4, np.float32), norm_topk=False)
    assert ids.tolist() == [[0, 1]]
    np.testing.assert_allclose(wt[0], s[:2], rtol=1e-6)
    # a bias lifts expert 3 into the choice; its weight is its own score
    ids, wt = _route(x, w, np.array([0, 0, 0, 2.0], np.float32),
                     norm_topk=False)
    assert ids.tolist() == [[3, 0]]
    np.testing.assert_allclose(wt[0], s[[3, 0]], rtol=1e-6)


def test_router_renormalises_then_scales():
    x = np.eye(4, dtype=np.float32)[:1] * 2.0
    w = np.array([[1.0, 0, 0, 0], [0.5, 0, 0, 0], [0.0, 0, 0, 0],
                  [-1.0, 0, 0, 0]], np.float32)
    s = 1 / (1 + np.exp(-np.array([2.0, 1.0])))
    _, wt = _route(x, w, np.zeros(4, np.float32))
    np.testing.assert_allclose(wt[0], s / s.sum(), rtol=1e-6)
    assert wt.sum() == pytest.approx(1.0)
    _, wt = _route(x, w, np.zeros(4, np.float32), scale=2.448)
    np.testing.assert_allclose(wt[0], s / s.sum() * 2.448, rtol=1e-6)
    _, wt = _route(x, w, np.zeros(4, np.float32), scale=2.448,
                   norm_topk=False)
    np.testing.assert_allclose(wt[0], s * 2.448, rtol=1e-6)


def test_router_scores_are_float32_whatever_the_input_type():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(16, 32), jnp.bfloat16)
    w = jnp.asarray(rng.randn(8, 32) * 0.2, jnp.bfloat16)
    ids, wt = pmoe.route_topk(x, w, jnp.zeros(8), 3, 1.0)
    assert wt.dtype == jnp.float32 and ids.dtype == jnp.int32
    s = jax.nn.sigmoid(x.astype(jnp.float32) @ w.astype(jnp.float32).T)
    want = jnp.sort(jax.lax.top_k(s, 3)[0], axis=-1)
    got = jnp.sort(jnp.take_along_axis(s, ids, axis=-1), axis=-1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_experts_held_outside_the_router_is_refused():
    with pytest.raises(ValueError, match="experts_held"):
        nn.SparseMoE(8, 4, 8, 2, experts_held=(6, 4))


# ------------------------------------------------- (f) rotary -------------

@pytest.mark.parametrize("interleave", [True, False])
def test_rotary_against_complex_multiplication(interleave):
    rng = np.random.RandomState(2)
    x = rng.randn(2, 3, 16, 8).astype(np.float32)
    theta = 1e6
    got = mx.nd.invoke("_contrib_rotary_embedding", mx.nd.array(x),
                       theta=theta, interleave=interleave).asnumpy()
    z = x[..., 0::2] + 1j * x[..., 1::2] if interleave \
        else x[..., :4] + 1j * x[..., 4:]
    rot = z * np.exp(1j * np.arange(16)[:, None]
                     * theta ** (-np.arange(4) / 4))
    want = np.concatenate([rot.real, rot.imag], axis=-1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # position 0 is the identity up to the de-interleave
    np.testing.assert_allclose(got[:, :, 0], np.concatenate(
        [z.real, z.imag], axis=-1)[:, :, 0], rtol=1e-6)


# ------------------------------------------------- the small blocks -------

def test_rms_norm_gated_silu_and_lm_loss_by_hand():
    rng = np.random.RandomState(3)
    x = rng.randn(4, 6).astype(np.float32)
    g = rng.rand(6).astype(np.float32) + 0.5
    got = mx.nd.invoke("_contrib_rms_norm", mx.nd.array(x), mx.nd.array(g),
                       eps=1e-6).asnumpy()
    want = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-6) * g
    np.testing.assert_allclose(got, want, rtol=1e-5)
    got = mx.nd.invoke("_contrib_gated_silu", mx.nd.array(x),
                       mx.nd.array(2 * x)).asnumpy()
    np.testing.assert_allclose(got, x / (1 + np.exp(-x)) * 2 * x, rtol=1e-5)
    logits = rng.randn(2, 5, 7).astype(np.float32)
    labels = rng.randint(0, 7, (2, 5))
    got = gloss.CausalLMLoss()(mx.nd.array(logits),
                               mx.nd.array(labels, dtype="int32")).asnumpy()
    logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    want = -np.take_along_axis(logp, labels[..., None], -1)[..., 0].mean(-1)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # the same through the ops the reference loss is built of
    old = gloss.SoftmaxCrossEntropyLoss(axis=-1)(
        mx.nd.array(logits), mx.nd.array(labels)).asnumpy()
    np.testing.assert_allclose(got, old, rtol=1e-5)


def test_model_is_built_from_the_published_keys_only(model):
    from mxnet_tpu.gluon.model_zoo import text

    with pytest.raises(NotImplementedError, match="q_lora_rank"):
        text.get_model("deepseek_v3", **dict(_cfg(), q_lora_rank=1536))
    with pytest.raises(ValueError, match="not supported"):
        text.get_model("no_such_model")
    published, _held = model.model_config(_cfg())
    assert published["n_routed_experts"] == 8      # the router's width
    net = text.get_model("deepseek_v3", **published)
    assert [i for i, _ in net.moe_layers()] == [1, 2]
    assert "held=(0, 8)" in repr(net)
