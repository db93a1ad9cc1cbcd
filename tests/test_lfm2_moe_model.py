"""The lfm2_moe text model (``gluon.model_zoo.text``) and the blocks and ops
under it against the plain float32 reference of the benchmark's
``lfm2_8b_a1b_ep4`` configuration, at a small size on the CPU, all on
seeded weights: hidden 64, 8 experts of which 2 are held, top-2, published
layers 0, 2, 3, 4, 5 of 6 (conv + dense, attention, three conv, the four
with experts), 4 query heads over 2 key heads of 16, vocabulary 64.

Ops exercised by name: _contrib_gated_short_conv, _contrib_ring_write,
_contrib_sparse_moe, _contrib_rms_norm, _contrib_rotary_embedding,
_contrib_flash_attention, _contrib_lm_cross_entropy.
"""
import copy
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "chipbench", "configs", "lfm2_8b_a1b_ep4")
SMALL = {"hidden_size": 64, "intermediate_size": 96,
         "moe_intermediate_size": 32, "num_attention_heads": 4,
         "num_key_value_heads": 2, "num_experts_per_tok": 2,
         "num_experts": 2, "vocab_size": 64, "dtype": "float32",
         "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                         "conv"]}
S = 128


@pytest.fixture(scope="module")
def model():
    from chipbench.harness import bench as hbench

    return hbench.load_module(os.path.join(CONFIG, "model.py"))


def _cfg(first=0, count=2, **changes):
    from chipbench.harness import bench as hbench

    cfg = hbench.load_json(os.path.join(CONFIG, "config.json"))
    cfg = copy.deepcopy(dict(cfg, **{**SMALL, **changes}))
    cfg["published"].update(num_hidden_layers=6, num_experts=8)
    cfg["deployment"].update(router_width=8, experts_held=[first, count])
    cfg["job"]["max_seq_length"] = S
    return cfg


def _build(model, cfg, seed, rate=0.0):
    """``model.build`` without the settling, the attention kernel in the
    Pallas interpreter (off the TPU ``dispatch`` takes the dense side on
    its own). With ``rate`` 0 the layers have no rule and none of its
    buffers: the layout's rows for them are left out."""
    from mxnet_tpu.gluon.model_zoo import text

    published, kept, held = model.model_config(cfg)
    net = text.get_model("lfm2_moe", layers_kept=kept, experts_held=held,
                         bias_update_rate=rate, interpret=True, **published)
    net.cast(cfg["dtype"])
    net.initialize(mx.init.Zero(), ctx=mx.cpu())
    arrays = model.make_params(cfg, seed)
    names = _names(model, cfg, rate)
    params = list(net.collect_params().values())
    assert len(params) == len(names)
    for p, name in zip(params, names):
        if name in arrays:
            p.set_data(mx.nd.array(np.asarray(arrays[name])))
    return net


def _names(model, cfg, rate):
    rule = ("route_pairs", "route_recent", "bias_rate")
    return [n for n, _, _ in model.layout(cfg)
            if rate or not n.endswith(rule)]


def _export(model, cfg, net, rate=0.0):
    return {n: p.data().asnumpy().astype(np.float32) for n, p in zip(
        _names(model, cfg, rate), net.collect_params().values())}


def _batch(cfg, seed=3, b=2):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (b, S + 1), 0,
                             cfg["vocab_size"])
    return np.asarray(ids[:, :-1], np.int32), np.asarray(ids[:, 1:], np.int32)


def _close(got, want, rtol=2e-4, name=""):
    """float32 against float32 at the highest matmul precision: what is
    left is the order of the sums (2e-4 of the largest value; a gradient
    through five layers and a softmax 2e-3)."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = max(float(np.abs(want).max()), 1e-12)
    assert got.shape == want.shape, name
    assert float(np.abs(got - want).max()) <= rtol * scale, \
        (name, float(np.abs(got - want).max()), scale)


# ------------------------------------------- (a) system against reference -

def test_logits_loss_and_every_gradient_match_the_reference(model):
    """Token-dependent routing (the bias at zero, every token choosing its
    own two of eight), compared in float32."""
    cfg = _cfg()
    x, y = _batch(cfg)
    net = _build(model, cfg, 5)
    params = _export(model, cfg, net)
    names = [n for n, _, init in model.layout(cfg)
             if init in ("normal", "ones", "taps")]

    def ref_loss(p):
        return model.reference(cfg, dict(params, **p), (x, y))["loss"]

    want = model.reference(cfg, params, (x, y))
    want_grads = jax.grad(ref_loss)({n: params[n] for n in names})
    trainable = [p for p in net.collect_params().values()
                 if p.grad_req != "null"]
    assert len(trainable) == len(names)
    with autograd.record():
        out = net(mx.nd.array(x, dtype="int32"))
        loss = model.loss(cfg)(out, mx.nd.array(y, dtype="int32")).mean()
    loss.backward()
    _close(out.asnumpy(), want["logits"], name="logits")
    assert float(loss.asscalar()) == pytest.approx(float(want["loss"]),
                                                   rel=1e-5)
    for name, p in zip(names, trainable):
        _close(p.grad().asnumpy(), want_grads[name], rtol=2e-3, name=name)
        assert float(np.abs(want_grads[name]).max()) > 0, name


@pytest.mark.parametrize("fault", [
    "expert_dropped", "gate_c_dropped", "taps_reversed", "rope_theta_1e4",
    "qk_norm_dropped", "embedding_norm_dropped", "weights_float8"])
def test_every_fault_of_the_reference_shows_at_this_size(model, fault):
    cfg = _cfg()
    x, _ = _batch(cfg)
    params = _export(model, cfg, _build(model, cfg, 5))
    sound = np.asarray(model.reference(cfg, params, (x, None))["logits"])
    wrong = np.asarray(model.reference(cfg, params, (x, None),
                                       fault=fault)["logits"])
    assert np.abs(wrong - sound).max() > 1e-3 * np.abs(sound).max(), fault


def test_reference_knows_its_faults_by_name(model):
    assert set(model.FAULTS) == {
        "weights_float8", "expert_dropped", "gate_c_dropped",
        "taps_reversed", "rope_theta_1e4", "qk_norm_dropped",
        "embedding_norm_dropped"}
    with pytest.raises(ValueError):
        model.reference(_cfg(), {}, (np.zeros((1, 4), np.int32), None),
                        fault="no_such")


def test_trains_through_sharded_trainer_and_predicts(model):
    cfg = _cfg()
    x, y = _batch(cfg)
    net = _build(model, cfg, 5, rate=1e-2)
    trainer = ShardedTrainer(
        net, model.loss(cfg), "adam",
        {"learning_rate": 1e-3, "multi_precision": True},
        mesh=DeviceMesh({"dp": 1}))
    losses = [float(trainer.step(x, y).asscalar()) for _ in range(4)]
    assert losses[-1] < losses[0] and np.isfinite(losses).all()
    assert trainer.skipped_steps == 0
    load = net.expert_load()
    assert sorted(load) == [2, 3, 4, 5]          # published indices
    for rec in load.values():
        assert rec["calls"] == 4 and len(rec["pairs"]) == 2
        # every pair of the whole router is counted: 4 steps x 256 x top-2
        assert sum(rec["route_pairs"]) == 4 * 2 * S * 2
        assert rec["pairs"] == rec["route_pairs"][:2]
        assert np.asarray(rec["route_recent"]).shape == (4, 8)
        assert np.asarray(rec["route_recent"]).sum(0).tolist() \
            == rec["route_pairs"]
    # the rule moved the bias inside the step, by the rate a step at most
    for _i, moe in net.moe_layers():
        bias = moe.router_bias.data().asnumpy()
        assert 0 < np.abs(bias).max() <= 4e-2 + 1e-6
    from mxnet_tpu.telemetry import registry

    assert registry.get("mxtpu_moe_route_pairs").snapshot()
    # inference leaves counters and bias alone and agrees with the reference
    biases = [m.router_bias.data().asnumpy() for _, m in net.moe_layers()]
    got = trainer.predict(x).asnumpy()
    assert net.expert_load() == load
    for b, (_, m) in zip(biases, net.moe_layers()):
        assert (m.router_bias.data().asnumpy() == b).all()
    want = model.reference(cfg, _export(model, cfg, net, 1e-2), (x, None))
    _close(got, want["logits"], name="predict")
    net.zero_expert_load()
    assert all(rec["calls"] == 0 and not sum(rec["route_pairs"])
               and rec["route_recent"] == []
               for rec in net.expert_load().values())


# --------------------------------------------------- (b) the share test ---

def test_shares_add_up_to_the_uncut_layer(model):
    """The parts of an expert layer's output that the four shares give
    (there is no shared expert to count once) add up to the uncut
    reference's layer."""
    cfg = _cfg(0, 8)
    h, f, e = cfg["hidden_size"], cfg["moe_intermediate_size"], 8
    rng = np.random.RandomState(0)
    x = rng.randn(2, S, h).astype(np.float32)
    w = {"router.weight": rng.randn(e, h) * 0.3,
         "router.bias": rng.randn(e) * 0.1,
         "experts.gate": rng.randn(e, h, f) * 0.1,
         "experts.up": rng.randn(e, h, f) * 0.1,
         "experts.down": rng.randn(e, f, h) * 0.1}
    w = {k: v.astype(np.float32) for k, v in w.items()}

    def share(first, count):
        blk = nn.SparseMoE(h, f, e, 2, experts_held=(first, count),
                           norm_eps=1e-6)
        blk.initialize()
        sl = slice(first, first + count)
        for p, v in zip(blk.collect_params().values(), [
                w["router.weight"], w["router.bias"],
                w["experts.gate"][sl], w["experts.up"][sl],
                w["experts.down"][sl]]):
            p.set_data(mx.nd.array(v))
        return blk(mx.nd.array(x)).asnumpy()

    def ref(held):
        sl = slice(held[0], held[0] + held[1])   # the weights held there
        p = {f"m.{k}": jnp.asarray(v[sl] if k.startswith("experts.") else v)
             for k, v in w.items()}
        with jax.default_matmul_precision("highest"):
            out = model.expert_layer(cfg, p, "m",
                                     jnp.asarray(x.reshape(-1, h)), held)
        return np.asarray(out).reshape(x.shape)

    whole = ref((0, 8))                  # the uncut reference's layer
    _close(share(0, 8), whole, name="whole layer")
    parts = [share(first, 2) for first in (0, 2, 4, 6)]
    assert all(np.abs(part).max() > 1e-3 for part in parts)
    _close(sum(parts), whole, name="sum of the shares")
    for first in (0, 6):
        _close(share(first, 2), ref((first, 2)), name=f"share {first}")
    # a token none of whose experts is held gets nothing from the share
    none_held = np.abs(share(0, 2)).max(axis=-1) == 0
    assert 0 < none_held.mean() < 1


def test_reference_share_is_the_models_share(model):
    cfg = _cfg(2, 4)
    x, _ = _batch(cfg)
    net = _build(model, cfg, 9)
    params = _export(model, cfg, net)
    got = net(mx.nd.array(x, dtype="int32")).asnumpy()
    _close(got, model.reference(cfg, params, (x, None))["logits"])
    other = model.reference(cfg, params, (x, None),
                            experts_held=(2, 2))["logits"]
    assert np.abs(np.asarray(other) - got).max() > 1e-3


# ----------------------------------------------- (c) the blocks alone -----

def _conv_block(units=16, taps=3, seed=0):
    blk = nn.ShortConv(units, taps=taps)
    blk.initialize(mx.init.Normal(0.3))
    rng = np.random.RandomState(seed)
    u = rng.randn(2, 40, units).astype(np.float32)
    taps_w, w_in, w_out = (p.data().asnumpy()
                           for p in blk.collect_params().values())
    return blk, u, taps_w, w_in, w_out


def test_short_conv_against_a_conv1d_written_out_tap_by_tap():
    blk, u, w, w_in, w_out = _conv_block()
    bcx = u @ w_in.T
    b, c, x = bcx[..., :16], bcx[..., 16:32], bcx[..., 32:]
    z = b * x
    v = np.zeros_like(z)
    for t in range(z.shape[1]):
        for k in range(3):
            if t - 2 + k >= 0:
                v[:, t] += w[:, k] * z[:, t - 2 + k]
    _close(blk(mx.nd.array(u)).asnumpy(), (c * v) @ w_out.T)
    # the same through the library's conv1d: groups = channels, padding 2
    conv = jax.lax.conv_general_dilated(
        jnp.asarray(z.transpose(0, 2, 1)), jnp.asarray(w[:, None, :]),
        (1,), [(2, 0)], feature_group_count=16)
    _close(np.asarray(conv).transpose(0, 2, 1), v, name="conv1d")


@pytest.mark.parametrize("t", [0, 17, 39])
def test_short_conv_is_causal(t):
    """A change at position t moves nothing before t, and reaches t, t + 1
    and t + 2 (three taps) and no further."""
    blk, u, *_ = _conv_block()
    moved = u.copy()
    moved[:, t] += 1.0
    delta = np.abs(blk(mx.nd.array(moved)).asnumpy()
                   - blk(mx.nd.array(u)).asnumpy()).max(axis=(0, 2))
    assert (delta[:t] == 0).all() and delta[t] > 0
    assert (delta[t + 3:] == 0).all()


def test_short_conv_gradient_is_the_one_jax_derives():
    from mxnet_tpu.ops import registry

    op = registry.get("_contrib_gated_short_conv").fn

    def plain(bcx, w):
        b, c, x = jnp.split(bcx, 3, axis=-1)
        z = jnp.pad(b * x, ((0, 0), (2, 0), (0, 0)))
        return c * sum(z[:, k:k + bcx.shape[1]] * w[:, k] for k in range(3))

    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    bcx = jax.random.normal(keys[0], (2, 37, 48))
    w = jax.random.normal(keys[1], (16, 3))
    cot = jax.random.normal(keys[2], (2, 37, 16))

    def grads(fn):
        return jax.grad(lambda a, b: (fn(a, b) * cot).sum(),
                        argnums=(0, 1))(bcx, w)

    for got, want in zip(grads(op), grads(plain)):
        _close(got, want, rtol=1e-5)
    # bfloat16 streams: float32 inside, one rounding out
    out = op(bcx.astype(jnp.bfloat16), w.astype(jnp.bfloat16))
    assert out.dtype == jnp.bfloat16
    _close(out.astype(jnp.float32),
           plain(bcx.astype(jnp.bfloat16).astype(jnp.float32),
                 w.astype(jnp.bfloat16).astype(jnp.float32)), rtol=1e-2)


@pytest.mark.parametrize("heads,kv_heads", [(4, 2), (4, 4), (8, 2)])
def test_gqattention_against_the_dense_form_with_repeated_keys(heads,
                                                               kv_heads):
    units, d, s = 64, 64 // heads, 128
    blk = nn.GQAttention(units, heads, kv_heads, rope_theta=1e6,
                         epsilon=1e-5, interpret=True)
    blk.initialize(mx.init.Normal(0.2))
    rng = np.random.RandomState(1)
    x = rng.randn(2, s, units).astype(np.float32)
    w_qkv, g_q, g_k, w_o = (p.data().asnumpy()
                            for p in blk.collect_params().values())
    g_q, g_k = g_q + rng.randn(d).astype(np.float32) * 0.1, g_k * 1.5
    blk.q_norm.gamma.set_data(mx.nd.array(g_q))
    blk.k_norm.gamma.set_data(mx.nd.array(g_k))

    def rms(t, g):
        return t / np.sqrt((t * t).mean(-1, keepdims=True) + 1e-5) * g

    def rope(t):                                      # (B, S, H, d)
        half = d // 2
        freq = 1e6 ** (-np.arange(half) * 2.0 / d)
        ang = np.arange(s)[:, None] * freq[None, :]
        cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
        a, b = t[..., :half], t[..., half:]
        return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    qkv = x @ w_qkv.T
    q = qkv[..., :heads * d].reshape(2, s, heads, d)
    k = qkv[..., heads * d:(heads + kv_heads) * d].reshape(2, s, kv_heads, d)
    v = qkv[..., (heads + kv_heads) * d:].reshape(2, s, kv_heads, d)
    q, k = rope(rms(q, g_q)), rope(rms(k, g_k))
    k = np.repeat(k, heads // kv_heads, axis=2)   # key j serves 4j..4j+3
    v = np.repeat(v, heads // kv_heads, axis=2)
    sc = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    sc = np.where(np.tril(np.ones((s, s), bool)), sc, -np.inf)
    pr = np.exp(sc - sc.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    want = np.einsum("bhqk,bkhd->bqhd", pr, v).reshape(2, s, units) @ w_o.T
    _close(blk(mx.nd.array(x)).asnumpy(), want, rtol=1e-4)


def test_gqattention_refuses_heads_that_do_not_divide():
    with pytest.raises(ValueError):
        nn.GQAttention(64, 4, 3)
    with pytest.raises(ValueError):
        nn.GQAttention(60, 8, 2)


# ------------------------------------------------ (d) the model's shape ---

def test_layers_take_their_kind_from_the_published_index(model):
    cfg = _cfg()
    net = _build(model, cfg, 1)
    assert net.layers_kept == (0, 2, 3, 4, 5)
    assert [blk.kind for blk in net.layers] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert [type(blk.ffn).__name__ for blk in net.layers] == [
        "GatedMLP"] + ["SparseMoE"] * 4
    assert [i for i, _ in net.moe_layers()] == [2, 3, 4, 5]
    # the head is the embedding: one table
    tables = [n for n in net.collect_params() if "embed" in n]
    assert len(tables) == 1


@pytest.mark.parametrize("changes,error", [
    ({"tie_word_embeddings": False}, NotImplementedError),
    ({"conv_bias": True}, NotImplementedError),
    ({"use_expert_bias": False}, NotImplementedError),
    ({"layer_types": ["conv"] * 5 + ["sliding_attention"]},
     NotImplementedError),
    ({"layer_types": ["conv"] * 5}, ValueError)])
def test_what_is_not_built_is_refused(model, changes, error):
    from mxnet_tpu.gluon.model_zoo import text

    published, kept, held = model.model_config(_cfg(**changes))
    with pytest.raises(error):
        text.get_model("lfm2_moe", layers_kept=[0, 5], experts_held=held,
                       **published)


def test_layers_kept_must_ascend_inside_the_published_depth(model):
    from mxnet_tpu.gluon.model_zoo import text

    published, _, held = model.model_config(_cfg())
    for kept in ([2, 0], [0, 6], []):
        with pytest.raises(ValueError):
            text.get_model("lfm2_moe", layers_kept=kept, experts_held=held,
                           **published)


def test_new_blocks_compute_under_their_named_scopes(model):
    cfg = _cfg()
    net = _build(model, cfg, 1, rate=1e-3)
    x, y = _batch(cfg)
    trainer = ShardedTrainer(
        net, model.loss(cfg), "adam",
        {"learning_rate": 1e-3, "multi_precision": True},
        mesh=DeviceMesh({"dp": 1}))
    text = trainer.aot_lower(x, y).as_text(debug_info=True)
    for scope in ("sconv.project", "sconv.gate", "attn.project",
                  "attn.full", "moe.route", "moe.experts", "moe.balance",
                  "lm.head_loss"):
        assert scope in text, scope
