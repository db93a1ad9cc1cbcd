"""The phi4flash text model (``gluon.model_zoo.text``) and the blocks, ops
and kernels under it against the plain float32 reference of the
benchmark's ``phi4_mini_flash_l6`` configuration, at a small size on the
CPU, all on seeded weights: hidden 128, 4 query and 2 key heads of 32, a
window of 40, vocabulary 96, published layers 0, 1, 16, 17, 18, 19 (all
five mixers: Mamba, window and full differential attention, a gated
memory unit, cross-attention).

Ops exercised by name: _contrib_layer_norm, _contrib_causal_conv1d,
_contrib_selective_scan, _contrib_diff_attention, _contrib_gated_silu,
_contrib_lm_cross_entropy; kernel families flash_attention(_bwd) with a
window and grouped keys, selective_scan(_bwd).
"""
import copy
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, kernels
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo import text
from mxnet_tpu.kernels import flash
from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FOLDER = os.path.join(REPO, "chipbench", "configs", "phi4_mini_flash_l6")
SMALL = {"hidden_size": 128, "intermediate_size": 256,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "sliding_window": 40, "vocab_size": 96, "dtype": "float32"}
S = 128


@pytest.fixture(scope="module")
def model():
    from chipbench.harness import bench as hbench

    return hbench.load_module(os.path.join(FOLDER, "model.py"))


def _cfg(**changes):
    from chipbench.harness import bench as hbench

    cfg = hbench.load_json(os.path.join(FOLDER, "config.json"))
    return copy.deepcopy({**cfg, **SMALL, **changes})


def _build(model, cfg, seed, interpret=True, layers_kept=None):
    """``model.build`` with the attention and scan kernels in the Pallas
    interpreter (off the TPU ``dispatch`` takes the XLA side on its
    own)."""
    published, kept = model.model_config(cfg)
    net = text.get_model("phi4flash", interpret=interpret,
                         layers_kept=layers_kept or kept, **published)
    net.cast(cfg["dtype"])
    net.initialize(mx.init.Zero(), ctx=mx.cpu())
    if layers_kept is None:
        model._set_params(net, cfg, model.make_params(cfg, seed))
    return net


def _batch(cfg, seed=3, b=2, s=S):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (b, s + 1), 0,
                             cfg["vocab_size"])
    return np.asarray(ids[:, :-1], np.int32), np.asarray(ids[:, 1:], np.int32)


def _close(got, want, rtol=2e-4, name=""):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = max(float(np.abs(want).max()), 1e-12)
    assert got.shape == want.shape, name
    assert float(np.abs(got - want).max()) <= rtol * scale, \
        (name, float(np.abs(got - want).max()), scale)


# ------------------------------------------- (a) system against reference -

@pytest.mark.parametrize("interpret", [True, False],
                         ids=["kernels_interpreted", "xla_side"])
def test_logits_loss_and_every_gradient_match_the_reference(model,
                                                            interpret):
    cfg = _cfg()
    x, y = _batch(cfg)
    net = _build(model, cfg, 5, interpret)
    loss_fn = model.loss(cfg)
    params = model.export_params(net, cfg)
    names = [n for n, _, _ in model.layout(cfg)]

    def ref_loss(p):
        return model.reference(cfg, p, (x, y))["loss"]

    want = model.reference(cfg, params, (x, y))
    want_grads = jax.grad(ref_loss)(params)
    trainable = list(net.collect_params().values())
    assert all(p.grad_req != "null" for p in trainable)
    assert len(trainable) == len(names)
    kernels.reset_stats()
    with autograd.record():
        out = net(mx.nd.array(x, dtype="int32"))
        loss = loss_fn(out, mx.nd.array(y, dtype="int32")).mean()
    loss.backward()
    _close(out.asnumpy(), want["logits"], name="logits")
    assert float(loss.asscalar()) == pytest.approx(float(want["loss"]),
                                                   rel=1e-5)
    for name, p in zip(names, trainable):
        _close(p.grad().asnumpy(), want_grads[name], rtol=3e-3, name=name)
        assert float(np.abs(want_grads[name]).max()) > 0, name
    stats = kernels.dispatch_stats()
    side = "kernel" if interpret else "xla"
    for family in ("flash_attention", "selective_scan"):
        assert stats[family][side] > 0 and \
            stats[family]["kernel" if side == "xla" else "xla"] == 0


def test_every_fault_of_the_reference_shows_at_this_size(model):
    """What the chip's tolerance is read against (PERF.md): each fault
    moves the reference's logits; the two roundings least."""
    cfg = _cfg()
    x, _ = _batch(cfg, b=1)
    params = jax.tree_util.tree_map(
        np.asarray, model.make_params(cfg, 5))
    clean = np.asarray(model.reference(cfg, params, (x, None))["logits"])
    moved = {f: float(np.abs(np.asarray(model.reference(
        cfg, params, (x, None), fault=f)["logits"]) - clean).max()
        / np.abs(clean).max()) for f in model.FAULTS}
    for fault in ("window_off", "lam_dropped", "memory_after_gate",
                  "cross_own_kv", "weights_float8"):
        assert moved[fault] > 0.02, moved
    for fault in ("state_bf16", "dt_bf16"):
        assert 0 < moved[fault] < 0.02, moved


def test_trains_through_sharded_trainer_and_predicts(model):
    cfg = _cfg()
    x, y = _batch(cfg)
    net = _build(model, cfg, 5)
    trainer = ShardedTrainer(
        net, model.loss(cfg), "adam",
        {"learning_rate": 1e-3, "multi_precision": True},
        mesh=DeviceMesh({"dp": 1}))
    kernels.reset_stats()
    losses = [float(trainer.step(x, y).asscalar()) for _ in range(4)]
    assert losses[-1] < losses[0] and np.isfinite(losses).all()
    assert trainer.skipped_steps == 0
    # one step program: two softmaxes a layer over three attention layers
    # and two scans, each differentiated once
    stats = kernels.dispatch_stats()
    assert stats["flash_attention"]["kernel"] == 6
    assert stats["flash_attention_bwd"]["kernel"] == 6
    assert stats["selective_scan"]["kernel"] == 2
    assert stats["selective_scan_bwd"]["kernel"] == 2
    got = trainer.predict(x).asnumpy()
    want = model.reference(cfg, model.export_params(net, cfg), (x, None))
    _close(got, want["logits"], name="predict")


def test_trains_in_bfloat16_with_float32_scan_parameters(model):
    """Under ``cast('bfloat16')`` the decay, the skip, the lam vectors and
    the sub-norm's gain stay float32; the step runs and the loss falls."""
    cfg = _cfg(dtype="bfloat16")
    x, y = _batch(cfg)
    net = _build(model, cfg, 5, interpret=False)
    kinds = {p.name.rsplit("_", 2)[-2] + "_" + p.name.rsplit("_", 1)[-1]:
             np.dtype(p.dtype) for p in net.collect_params().values()}
    f32 = {n for n, d in kinds.items() if d == np.float32}
    assert {"a_log", "d_skip", "lam_q1", "lam_k2", "subln_gamma"} <= f32
    trainer = ShardedTrainer(
        net, model.loss(cfg), "adam",
        {"learning_rate": 1e-3, "multi_precision": True},
        mesh=DeviceMesh({"dp": 1}))
    losses = [float(trainer.step(x, y).asscalar()) for _ in range(4)]
    assert losses[-1] < losses[0] and np.isfinite(losses).all()


# ------------------------------------------------ (b) the cut in depth ----

def test_layers_kept_of_the_whole_model_is_the_cut_configuration(model):
    """The 32-layer model, and the same model built with ``layers_kept`` of
    the six: the six layers' weights, copied by name, give the cut
    configuration's logits; each kept layer keeps the kind, window and
    ``lam_init`` of its published index."""
    cfg = _cfg()
    published, kept = model.model_config(cfg)
    whole = text.get_model("phi4flash", **published)
    whole.initialize(mx.init.Normal(0.05), ctx=mx.cpu())
    assert whole.layers_kept == tuple(range(32))
    cut = _build(model, cfg, 5, interpret=False, layers_kept=kept)
    assert cut.layers_kept == (0, 1, 16, 17, 18, 19)
    x, _ = _batch(cfg, s=48)
    ids = mx.nd.array(x, dtype="int32")
    whole(ids)                              # resolves deferred shapes
    by_layer = dict(zip(whole.layers_kept, whole.layers))

    def named(block, params):     # parameter names without the block's own
        return {k[len(block.prefix):]: v for k, v in params.items()}

    pairs = [(named(whole, whole._reg_params), named(cut, cut._reg_params)),
             (named(whole.norm, whole.norm._reg_params),
              named(cut.norm, cut.norm._reg_params))]
    pairs += [(named(by_layer[i], by_layer[i].collect_params()),
               named(blk, blk.collect_params()))
              for i, blk in zip(kept, cut.layers)]
    for src, dst in pairs:
        assert sorted(src) == sorted(dst)
        for name, p in dst.items():
            assert p.shape == src[name].shape, name
            p.set_data(src[name].data())
    for i, blk in zip(kept, cut.layers):
        assert blk.kind == by_layer[i].kind == model.kind_of(cfg, i)
        if blk.kind in ("window", "full", "cross"):
            assert blk.mixer._kwargs == by_layer[i].mixer._kwargs
            assert blk.mixer._kwargs["lam_init"] == pytest.approx(
                0.8 - 0.6 * np.exp(-0.3 * i))
            assert blk.mixer._kwargs.get("window") == (
                40 if blk.kind == "window" else None)
    # weight for weight, logit for logit: the cut model IS the reference's
    want = model.reference(cfg, model.export_params(cut, cfg), (x, None))
    _close(cut(ids).asnumpy(), want["logits"], name="cut")
    # and the whole model is another function (26 more layers)
    assert float(np.abs(whole(ids).asnumpy() - cut(ids).asnumpy()).max()) \
        > 1e-3


@pytest.mark.parametrize("changes,error", [
    ({"mb_per_layer": 4}, NotImplementedError),
    ({"num_hidden_layers": 30}, NotImplementedError),
    ({"tie_word_embeddings": False}, NotImplementedError),
    ({"layers_kept": [0, 1, 18]}, ValueError),        # a GMU with no memory
    ({"layers_kept": [16, 19]}, ValueError),          # a cross layer, no K/V
    ({"layers_kept": [1, 0]}, ValueError),
    ({"layers_kept": [0, 32]}, ValueError),
])
def test_what_is_not_built_is_refused(model, changes, error):
    published, kept = model.model_config(_cfg())
    kept = changes.pop("layers_kept", kept)
    with pytest.raises(error):
        text.get_model("phi4flash", layers_kept=kept,
                       **dict(published, **changes))


def test_model_is_built_from_the_published_keys_only(model):
    cfg = _cfg()
    published, kept = model.model_config(cfg)
    net = text.get_model("phi4flash", layers_kept=kept, **published)
    kinds = [blk.kind for blk in net.layers]
    assert kinds == ["mamba", "window", "mamba_memory", "full", "gmu",
                     "cross"]
    assert isinstance(net.layers[0].mixer, nn.MambaMixer)
    assert isinstance(net.layers[1].mixer, nn.DiffAttention)
    assert isinstance(net.layers[4].mixer, nn.GatedMemoryUnit)
    assert net.layers[5].mixer.kv_proj is None
    assert "phi4flash" in str(pytest.raises(
        ValueError, text.get_model, "no_such_model").value)


# --------------------------- (c) windowed and grouped flash attention ----

def _attention_case(group, s=256, d=32, dv=64, heads=4, b=2,
                    dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (b, heads, s, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, heads // group, s, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, heads // group, s, dv)).astype(dtype)
    cot = jax.random.normal(ks[3], (b, heads, s, dv)).astype(dtype)
    return q, k, v, cot


def _masked_dense(q, k, v, scale, window):
    """Masked dense attention written out: a query at ``i`` sees keys
    ``i - window + 1 .. i``; a key head serves ``group`` query heads."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    i = jnp.arange(q.shape[2])
    keep = i[:, None] >= i[None, :]
    if window is not None:
        keep &= i[:, None] - i[None, :] < window
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "two_calls"])
@pytest.mark.parametrize("group", [1, 2, 4])
@pytest.mark.parametrize("window", [None, 4, 5, 128, 129])
def test_windowed_grouped_flash_forward_and_backward(window, group, fused):
    """Forward, log-sum-exp and the backward kernels (one fused call, and
    two) against masked dense attention, 128 x 128 blocks over 256
    positions: window 4 lies inside a block, 128 is a block, 129 crosses
    into a third; rows whose band does not reach the first tile a q block
    runs are the ones an online softmax must not turn into NaN."""
    q, k, v, cot = _attention_case(group)
    with jax.default_matmul_precision("highest"):
        out, lse = flash.flash_forward_lse(q, k, v, 0.2, True, 128, 128,
                                           True, window=window)
        want, vjp = jax.vjp(
            lambda *t: _masked_dense(*t, 0.2, window), q, k, v)
        _close(out, want, rtol=2e-5, name="out")
        _close(lse, flash.row_log_sum_exp(q, k, 0.2, True, window),
               rtol=2e-5, name="lse")
        _close(flash.flash_attention_reference(q, k, v, 0.2, True, window),
               want, rtol=2e-5, name="reference")
        got = flash.flash_backward_kernel(q, k, v, out, lse, cot, 0.2, True,
                                          128, 128, True, fused=fused,
                                          window=window)
        for name, g, w in zip("qkv", got, vjp(cot)):
            assert g.shape == w.shape and g.dtype == w.dtype
            _close(g, w, rtol=2e-4, name="d" + name)


@pytest.mark.parametrize("window", [4, 128])
def test_a_window_and_the_next_differ(window):
    """Window ``w`` and ``w + 1`` are different functions, by exactly the
    one key a row gains."""
    q, k, v, _ = _attention_case(2)
    with jax.default_matmul_precision("highest"):
        a, b = (flash.flash_forward(q, k, v, 0.2, True, 128, 128, True,
                                    window=w) for w in (window, window + 1))
    gap = np.abs(np.asarray(a - b)).max(axis=(0, 1, 3))      # by position
    assert (gap[:window] == 0).all() and (gap[window:] > 1e-6).all()


def test_windowed_grouped_flash_through_dispatch_and_its_vjp():
    """The registry's path: the forward's ``custom_vjp`` asks the backward
    family with the same window; buckets name the group and the window;
    without either the key is what it always was."""
    q, k, v, cot = _attention_case(2)
    kernels.reset_stats()

    def run(q_, k_, v_):
        return (kernels.dispatch("flash_attention", q_, k_, v_, 0.2,
                                 causal=True, interpret=True, window=40)
                * cot).sum()

    with jax.default_matmul_precision("highest"):
        got = jax.grad(run, (0, 1, 2))(q, k, v)
        want = jax.grad(lambda *t: (_masked_dense(*t, 0.2, 40) * cot).sum(),
                        (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        _close(g, w, rtol=2e-4)
    stats = kernels.dispatch_stats()
    key = "bh8_sq256_sk256_d32v64_float32_c1_q128k128_g2_w40"
    assert list(stats["flash_attention"]["buckets"]) == [key]
    assert list(stats["flash_attention_bwd"]["buckets"]) == [key]
    same = jnp.zeros((2, 4, 256, 32))
    assert flash._bucket(same, same, same, 0.2, True) \
        == "bh8_sq256_sk256_d32_float32_c1_q256k256"
    assert flash._bucket(q, k, v, 0.2, True) \
        == "bh8_sq256_sk256_d32v64_float32_c1_q256k256_g2"


@pytest.mark.parametrize("shape,window,fwd,bwd", [
    ((4096, 64, 128), None, (1024, 1024), (512, 512)),
    ((4096, 64, 128), 512, (512, 512), (512, 512)),
    ((4096, 64, 128), 600, (512, 512), (512, 512)),
    ((4096, 64, 128), 100, (128, 128), (128, 128)),
    ((384, 64, 64), 128, (128, 128), (128, 128)),
    ((384, 64, 64), 300, (384, 384), (384, 384)),     # 256 does not divide
    ((1024, 64, 64), 256, (256, 256), (256, 256)),
])
def test_blocks_step_inside_the_band(shape, window, fwd, bwd):
    s, d, dv = shape
    q = jax.ShapeDtypeStruct((1, 2, s, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 2, s, dv), jnp.bfloat16)
    assert flash._blocks(q, q, v, window=window) == fwd
    assert flash._blocks_for(q, q, v, window) == bwd


def test_flash_supports_refuses_what_the_kernel_cannot_group():
    q = jnp.zeros((2, 6, 256, 32))
    k4, k3, k2 = (jnp.zeros((2, n, 256, 32)) for n in (4, 3, 2))
    assert flash._supports(q, k3, k3, 0.2, True)
    assert flash._supports(q, k2, k2, 0.2, True, window=7)
    assert not flash._supports(q, k4, k4, 0.2, True)         # 6 % 4
    assert not flash._supports(q, k3, k2, 0.2, True)         # k, v differ
    assert not flash._supports(q, k3, k3, 0.2, False, window=7)
    assert not flash._supports(q, k3, k3, 0.2, True, window=0)
    assert not flash._supports(q, k3[:, :, :128], k3[:, :, :128], 0.2, True,
                               window=7)                     # no square


@pytest.mark.parametrize("window", [None, 512])
def test_causal_maps_stay_inside_the_band(window):
    """No grid step names a block outside the band or above the diagonal,
    and a step inside both names itself."""
    bq = bk = 128
    n = 4096 // 128
    q_of, k_of = flash._causal_maps(True, bq, bk, window, n)

    def live(i, j):   # some (query, key) pair of the tile is computed
        lo_q, hi_q, lo_k, hi_k = i * bq, (i + 1) * bq - 1, j * bk, \
            (j + 1) * bk - 1
        return hi_q >= lo_k and (window is None or lo_q - hi_k < window)

    for i in range(n):
        for j in range(n):
            kj, qi = int(k_of(i, j)), int(q_of(i, j))
            assert live(i, kj) and live(qi, j), (i, j, kj, qi)
            if live(i, j):
                assert (kj, qi) == (j, i)


# ---------------------------------------------- (d) the ops, by hand -----

def test_diff_attention_op_against_the_equations():
    """One pair of heads, written out: P = softmax(q1 k1^T / sqrt d) - lam
    softmax(q2 k2^T / sqrt d); out = (1 - lam_init) RMSNorm(P [v1; v2])."""
    from mxnet_tpu.ops import registry as opreg

    op = opreg.get("_contrib_diff_attention").fn
    s, d = 16, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    q, k, v = (jax.random.normal(ks[i], (1, s, 2 * d)) for i in range(3))
    lams = [jax.random.normal(ks[3 + i], (d,)) * 0.1 for i in range(4)]
    gamma = jax.random.normal(ks[7], (2 * d,)) + 1.0
    got = op(q, k, v, *lams, gamma, num_heads=2, num_kv_heads=2,
             lam_init=0.35, window=5, eps=1e-5)
    lam = jnp.exp(lams[0] @ lams[1]) - jnp.exp(lams[2] @ lams[3]) + 0.35
    i = jnp.arange(s)
    keep = (i[:, None] >= i[None, :]) & (i[:, None] - i[None, :] < 5)

    def soft(a, b):
        return jax.nn.softmax(jnp.where(keep, a @ b.T / np.sqrt(d), -jnp.inf),
                              axis=-1)

    p = soft(q[0, :, :d], k[0, :, :d]) - lam * soft(q[0, :, d:], k[0, :, d:])
    out = p @ v[0]
    out = out / jnp.sqrt((out * out).mean(-1, keepdims=True) + 1e-5) \
        * gamma * (1 - 0.35)
    _close(got[0], out, rtol=2e-5)


def test_layer_norm_statistics_are_float32():
    from mxnet_tpu.ops import registry as opreg

    op = opreg.get("_contrib_layer_norm").fn
    x = (jax.random.normal(jax.random.PRNGKey(1), (3, 256)) * 3 + 100)
    g = jnp.full((256,), 2.0)
    b = jnp.full((256,), 0.5)
    want = (x - x.mean(-1, keepdims=True)) / jnp.sqrt(
        x.var(-1, keepdims=True) + 1e-5) * 2.0 + 0.5
    _close(op(x, g, b, eps=1e-5), want, rtol=1e-5)
    # a bfloat16 input far from zero: the statistics do not round with it
    low = op(x.astype(jnp.bfloat16), g.astype(jnp.bfloat16),
             b.astype(jnp.bfloat16), eps=1e-5)
    x16 = x.astype(jnp.bfloat16).astype(jnp.float32)
    want16 = (x16 - x16.mean(-1, keepdims=True)) / jnp.sqrt(
        x16.var(-1, keepdims=True) + 1e-5) * 2.0 + 0.5
    assert low.dtype == jnp.bfloat16
    _close(low.astype(jnp.float32), want16, rtol=1e-2)


def test_new_blocks_compute_under_their_named_scopes(model):
    cfg = _cfg()
    published, kept = model.model_config(cfg)
    net = text.get_model("phi4flash", layers_kept=kept, **published)
    net.initialize(mx.init.Normal(0.02), ctx=mx.cpu())
    x, _ = _batch(cfg, s=16, b=1)

    def forward(ids):
        return net(mx.nd.NDArray(ids))._data

    program = str(jax.make_jaxpr(forward)(jnp.asarray(x)).pretty_print(
        name_stack=True))
    for scope in ("ssm.project", "ssm.conv", "ssm.scan", "gmu",
                  "attn.window", "attn.full", "attn.cross"):
        assert scope in program, scope
