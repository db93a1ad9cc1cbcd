"""The selection bias that balances itself (``nn.SparseMoE(
bias_update_rate=)``, the auxiliary-loss-free rule of arXiv:2408.15664):
the rule's arithmetic, that the bias stays outside the gradient and still
in inference, and that under Zipf(1) ids and seeded weights the
configuration's settling evens out a router that starts uneven.
"""
import copy
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import moe as pmoe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "chipbench", "configs", "lfm2_8b_a1b_ep4")


def _layer(rate, experts=8, top_k=2, held=(0, 2), seed=0):
    blk = nn.SparseMoE(16, 8, experts, top_k, experts_held=held,
                       bias_update_rate=rate, norm_eps=1e-6)
    blk.initialize(mx.init.Normal(0.3))
    x = np.random.RandomState(seed).randn(2, 64, 16).astype(np.float32)
    return blk, mx.nd.array(x)


def test_rule_on_a_hand_made_count_vector():
    """A bias that sends every token to experts 0 and 1: c = [128, 128, 0,
    0, 0, 0, 0, 0], mean 32, so the two go down by the rate and the six
    others up by it."""
    blk, x = _layer(0.25)
    forced = np.array([9, 9, 0, 0, 0, 0, 0, 0], np.float32)
    blk.router_bias.set_data(mx.nd.array(forced))
    with autograd.train_mode():
        blk(x)
    rec = blk.expert_load()
    assert rec["route_pairs"] == [128.0, 128.0] + [0.0] * 6
    assert rec["route_recent"] == [rec["route_pairs"]]
    assert rec["pairs"] == [128.0, 128.0] and rec["calls"] == 1
    np.testing.assert_allclose(
        blk.router_bias.data().asnumpy(),
        forced + 0.25 * np.array([-1, -1, 1, 1, 1, 1, 1, 1]))


@pytest.mark.parametrize("hybridize", [False, True])
def test_rule_follows_the_counts_call_after_call(hybridize):
    """b += rate * sign(mean(c) - c) with c the call's own counts over the
    WHOLE router, an expert at the mean standing still; the rate is a
    buffer, so a schedule changes it without a new program."""
    blk, x = _layer(0.01)
    if hybridize:
        blk.hybridize()
    bias = blk.router_bias.data().asnumpy().copy()
    for call, rate in enumerate((0.01, 0.01, 0.002)):
        blk.bias_rate.set_data(mx.nd.array([rate]))
        with autograd.train_mode():
            blk(x)
        c = np.asarray(blk.expert_load()["route_recent"][call])
        assert c.sum() == 2 * 64 * 2 and len(c) == 8
        bias = bias + np.float32(rate) * np.sign(c.mean() - c)
        np.testing.assert_allclose(blk.router_bias.data().asnumpy(), bias,
                                   rtol=1e-6, atol=1e-7)
    # every call went through the router the rule had left
    ids, _, counts = pmoe.route_topk(
        x._data.reshape(-1, 16), blk.router_weight.data()._data,
        blk.router_bias.data()._data, 2, 1.0, route_counts=True)
    assert np.asarray(counts).tolist() == np.bincount(
        np.asarray(ids).ravel(), minlength=8).tolist()


def test_bias_is_outside_the_gradient_and_still_in_inference():
    blk, x = _layer(0.01)
    assert all(getattr(blk, n).grad_req == "null" for n in (
        "router_bias", "route_pairs", "route_recent", "bias_rate"))
    for name in ("router_bias", "route_pairs", "route_recent", "bias_rate"):
        assert getattr(blk, name).data().dtype == np.float32
    blk.cast("bfloat16")     # the buffers stay float32
    assert blk.router_bias.data().dtype == np.float32
    assert blk.route_recent.data().dtype == np.float32
    blk.cast("float32")
    before = blk.router_bias.data().asnumpy().copy()
    blk(x)                                        # inference
    assert (blk.router_bias.data().asnumpy() == before).all()
    assert blk.expert_load()["calls"] == 0
    x.attach_grad()
    with autograd.record():
        y = blk(x).sum()
    y.backward()
    assert np.abs(x.grad.asnumpy()).max() > 0
    moved = blk.router_bias.data().asnumpy()
    assert np.abs(moved - before).max() == pytest.approx(0.01)
    trainable = [n for n, p in blk.collect_params().items()
                 if p.grad_req != "null"]
    assert len(trainable) == 4 and not any("bias" in n for n in trainable)


def test_rate_zero_builds_the_layer_it_was():
    """No rule, no buffer, no third output: what ``deepseek_v3`` builds."""
    blk, x = _layer(0.0)
    names = [n.split("_", 1)[1] for n in blk.collect_params()]
    assert names == ["router_weight", "router_bias", "gate_weight",
                     "up_weight", "down_weight", "load_pairs", "load_peak",
                     "load_calls"]
    with autograd.train_mode():
        blk(x)
    assert set(blk.expert_load()) == {"first_expert", "pairs", "peak",
                                      "calls"}
    assert not blk.router_bias.data().asnumpy().any()
    y, load = pmoe.routed_experts(
        x._data.reshape(-1, 16), blk.router_weight.data()._data,
        blk.router_bias.data()._data, blk.gate_weight.data()._data,
        blk.up_weight.data()._data, blk.down_weight.data()._data, top_k=2)
    assert y.shape == (128, 16) and load.shape == (2,)


def test_norm_eps_is_the_renormalising_sums_floor():
    import jax.numpy as jnp

    x = jnp.zeros((1, 4)) - 3.5      # logits -14: scores 8.3e-7 each
    w = jnp.ones((4, 4))
    for eps, total in ((1e-20, 1.0), (1e-6, 1.663 / 2.663)):
        _, weights = pmoe.route_topk(x, w, jnp.zeros(4), 2, 1.0,
                                     norm_eps=eps)
        assert float(weights.sum()) == pytest.approx(total, abs=1e-3)


# ------------------------------------------------- the settling, small ----

@pytest.fixture(scope="module")
def model():
    from chipbench.harness import bench as hbench

    return hbench.load_module(os.path.join(CONFIG, "model.py"))


def _small_cfg():
    """Hidden 64 with weights N(0, 0.113): the router's logits as wide as
    2,048 inputs at 0.02 make them; 2,048 tokens a pass, 256 pairs an
    expert."""
    from chipbench.harness import bench as hbench

    cfg = copy.deepcopy(hbench.load_json(os.path.join(CONFIG,
                                                      "config.json")))
    cfg.update(hidden_size=64, intermediate_size=128,
               moe_intermediate_size=32, num_attention_heads=4,
               num_key_value_heads=2, vocab_size=2048, dtype="float32",
               initializer_range=0.113)
    cfg["job"]["max_seq_length"] = 2048
    return cfg


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_settling_evens_out_a_zipf_router(model, seed, capsys):
    """``build`` settles the bias by the program's own rule within the
    passes the file allows: every layer's busiest expert within 1.3 of the
    mean where the seeded weights start at 1.5 or more; and
    ``moe_router_imbalance`` reads it over training calls that follow."""
    import jax

    from chipbench.harness import bench as hbench

    cfg = _small_cfg()
    net = model.build(cfg, mx.cpu(), seed)
    line = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("# routing: ")][-1]
    routing = json.loads(line.split(": ", 1)[1])
    assert routing["passes"] <= cfg["settling"]["passes_max"]
    assert max(routing["imbalance_first"]) > 1.5
    assert max(routing["imbalance_reached"]) <= 1.3
    assert routing["rate_last"] == cfg["job"]["bias_update_rate"]
    # settled, the counters start from zero and the rate is the job's
    assert all(rec["calls"] == 0 for rec in net.expert_load().values())
    for _i, moe in net.moe_layers():
        assert moe.bias_rate.data().asnumpy().tolist() == [
            np.float32(cfg["job"]["bias_update_rate"])]
        assert np.abs(moe.router_bias.data().asnumpy()).max() > 0
    net.hybridize()
    key = jax.random.PRNGKey(seed + 100)
    for i in range(4):
        ids = model._tokens(cfg, jax.random.fold_in(key, i), 1, 2048, 1.0)
        with autograd.train_mode():
            net(mx.nd.array(np.asarray(ids), dtype="int32"))
    reader = hbench.load_module(os.path.join(
        REPO, "chipbench", "layer_metrics", "moe_router_imbalance.py"))
    run = {"mode": "train", "model": model}
    assert reader.applies(run)
    assert 1.0 <= reader.compute(run) <= 1.3
    window = model.routing_window(net)
    assert sorted(window) == ["2", "3", "4", "5"]
    for rec in window.values():
        assert rec["calls"] == 4 and rec["first"]["calls"] == 4
        assert rec["last"]["imbalance"] <= 1.3
        # 2,048 tokens x top-4 over 32 experts: 256 pairs an expert
        assert rec["last"]["pairs_per_held_expert"] == pytest.approx(
            256, rel=0.15)
