"""The ``kanana2_30b_a3b_ep8`` configuration and its cell: the published
counts against ``layout``, the operation counts against a hand count, and
the cell end to end at toy widths on the CPU mesh through the real
``chipbench/run.py`` code path (no number it prints is a device metric)."""
import json
import os

import numpy as np
import pytest

import chipbench_toy as toy

CONFIG = "kanana2_30b_a3b_ep8"
CELL = "kanana2_30b_a3b_train_s4096"
TOY = {"hidden_size": 64, "intermediate_size": 96,
       "moe_intermediate_size": 32, "num_attention_heads": 4,
       "kv_lora_rank": 32, "qk_nope_head_dim": 24, "qk_rope_head_dim": 8,
       "qk_head_dim": 32, "v_head_dim": 16, "num_hidden_layers": 3,
       "num_experts_per_tok": 3, "n_routed_experts": 2, "vocab_size": 64,
       "deployment.router_width": 8, "deployment.experts_held": [0, 2],
       "job.max_seq_length": 128,
       "job.optimizer_params.learning_rate": 1e-3}


def _load(name="model.py"):
    from chipbench.harness import bench as hbench

    folder = os.path.join(toy.BENCH, "configs", CONFIG)
    if name.endswith(".json"):
        return hbench.load_json(os.path.join(folder, name))
    return hbench.load_module(os.path.join(folder, name))


@pytest.fixture(scope="module")
def cfg():
    return _load("config.json")


@pytest.fixture(scope="module")
def model():
    return _load()


@pytest.fixture(scope="module")
def bench_dir(tmp_path_factory):
    dst = toy.toy_copy(str(tmp_path_factory.mktemp("toy") / "chipbench"))
    toy.edit_json(os.path.join(dst, f"configs/{CONFIG}/config.json"), **TOY)
    toy.edit_json(os.path.join(dst, f"workloads/{CELL}.json"),
                  **{"traffic.seq_len": 128, "traffic.pool_batches": 2,
                     "traffic.warmup_steps": 2, "traffic.trace_steps": 3})
    return dst


@pytest.fixture
def lifted(monkeypatch):
    restore = toy.lift_refusal(monkeypatch)
    yield
    restore()


# ------------------------------------------------------------ the counts --

def _count(spec, pick):
    return sum(int(np.prod(shape)) for name, shape, init in spec
               if init in ("normal", "ones") and pick(name))


def test_layout_against_the_published_counts(cfg, model):
    """36.05 M outside the experts and 4.72 M an expert in an expert
    layer, 64.1 M in the dense layer, 65.7 M embedding + head here (525 M
    uncut), 576 M held here; ~30 B whole."""
    spec = model.layout(cfg)
    layer1 = _count(spec, lambda n: n.startswith("layer1."))
    experts1 = _count(spec, lambda n: n.startswith("layer1.moe.experts."))
    assert experts1 == 16 * 3 * 2048 * 768 == 16 * 4_718_592
    assert layer1 - experts1 == 36_049_408        # 26.35 + 0.26 + 9.44 M + norms
    assert _count(spec, lambda n: n.startswith("layer0.")) == 64_098_816
    assert _count(spec, lambda n: n in ("embed.weight", "head.weight")) \
        == 2 * 16032 * 2048
    total = _count(spec, lambda n: True)
    assert total == 64_098_816 + 4 * layer1 + 2 * 16032 * 2048 + 2048
    assert 575e6 < total < 577e6
    pub = cfg["published"]
    whole = (64_098_816
             + (pub["num_hidden_layers"] - 1)
             * (36_049_408 + pub["n_routed_experts"] * 4_718_592)
             + 2 * pub["vocab_size"] * 2048 + 2048)
    assert 2 * pub["vocab_size"] * 2048 == 525_336_576
    assert 29.5e9 < whole < 31e9


def test_config_file_states_the_cut(cfg):
    row = {"num_hidden_layers": 48, "n_routed_experts": 128,
           "vocab_size": 128256}
    assert cfg["reduced"] == list(row) and cfg["published"] == row
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] == 8
    assert dep["router_width"] == row["n_routed_experts"]
    assert dep["experts_held"] == [0, cfg["n_routed_experts"]]
    assert cfg["n_routed_experts"] * 8 == row["n_routed_experts"]
    assert cfg["vocab_size"] * 8 == row["vocab_size"]
    # every width as published
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["kv_lora_rank"]) \
        == (2048, 6144, 768, 512)
    assert (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["num_attention_heads"],
            cfg["n_shared_experts"], cfg["num_experts_per_tok"]) \
        == (128, 64, 128, 32, 2, 6)
    assert len(cfg["check"]["reason"]) > 200
    # nothing but published keys reaches the package's model
    assert "flash" not in cfg


def test_the_model_gets_published_keys_only(cfg, model):
    published, held = model.model_config(cfg)
    assert held == (0, 16) and published["n_routed_experts"] == 128
    assert all(not isinstance(v, (dict, list)) for v in published.values())
    assert {"flash", "dtype", "initializer_range", "name", "source"} \
        .isdisjoint(published)


# ------------------------------------------------------------ the traffic -

def test_token_ids_follow_zipfs_law_from_the_seed(cfg, model):
    import jax

    wl = json.load(open(os.path.join(toy.BENCH, "workloads",
                                     f"{CELL}.json")))
    assert wl["traffic"]["token_zipf_exponent"] == 1.0
    assert cfg["check"]["token_zipf_exponent"] == 1.0
    traffic = dict(wl["traffic"], global_batch=8)
    x, y = model.make_batch(cfg, traffic, jax.random.PRNGKey(7))
    x, y = np.asarray(x), np.asarray(y)
    assert x.shape == y.shape == (8, 4096) and x.dtype == np.int32
    assert (x[:, 1:] == y[:, :-1]).all()
    assert 0 <= x.min() and x.max() < cfg["vocab_size"]
    # id i with probability 1 / ((i + 1) H), H = sum 1/k over 16,032 = 10.26
    h = (1.0 / np.arange(1, cfg["vocab_size"] + 1)).sum()
    freq = np.bincount(x.ravel(), minlength=cfg["vocab_size"]) / x.size
    for i in (0, 1, 9):
        assert freq[i] == pytest.approx(1 / ((i + 1) * h), rel=0.15)
    assert freq[:100].sum() == pytest.approx(
        (1.0 / np.arange(1, 101)).sum() / h, rel=0.03)
    again, _ = model.make_batch(cfg, traffic, jax.random.PRNGKey(7))
    other, _ = model.make_batch(cfg, traffic, jax.random.PRNGKey(8))
    assert (np.asarray(again) == x).all() and (np.asarray(other) != x).any()
    # exponent 0 is the uniform draw
    flat, _ = model.make_batch(cfg, dict(traffic, token_zipf_exponent=0.0),
                               jax.random.PRNGKey(7))
    assert np.bincount(np.asarray(flat).ravel(),
                       minlength=cfg["vocab_size"]).max() < 20
    ids = model.check_inputs(cfg, 2 ** 31 + 5, 2)
    assert ids.shape == (2, 4096) and (ids == 0).mean() > 0.05


# ------------------------------------------------- the routing comparison -

def test_route_margin_is_the_held_experts_distance_from_the_cut(cfg, model):
    """8 experts, top-3, experts 2 and 3 held: by hand."""
    import jax.numpy as jnp

    small = dict(cfg, num_experts_per_tok=3, routed_scaling_factor=1.0)
    z = np.array([[3.0, 2.0, 1.0, 0.5, -1.0, -2.0, -3.0, -4.0],    # 2 in by 0.5
                  [3.0, 2.0, -1.0, -3.0, 1.0, 0.5, 0.0, -2.0],     # 2 out
                  [0.0, 1.0, 2.0, 3.0, -1.0, -2.0, -3.0, -4.0]],   # both in
                 np.float32)
    p = {"m.router.weight": jnp.eye(8), "m.router.bias": jnp.zeros(8),
         "m.experts.gate": jnp.zeros((2, 8, 4)),
         "m.experts.up": jnp.zeros((2, 8, 4)),
         "m.experts.down": jnp.zeros((2, 4, 8)),
         "m.shared.gate.weight": jnp.zeros((4, 8)),
         "m.shared.up.weight": jnp.zeros((4, 8)),
         "m.shared.down.weight": jnp.zeros((8, 4))}
    _, margin = model._expert_layer(small, p, "m", jnp.asarray(z), (2, 2))
    sig = lambda v: 1 / (1 + np.exp(-v))   # noqa: E731
    want = [sig(1.0) - sig(0.5),            # expert 2 third, expert 3 fourth
            sig(1.0) - sig(-1.0),           # last in 1.0; expert 2 at -1.0
            sig(2.0) - sig(0.0)]            # expert 2 second; first out 0.0
    np.testing.assert_allclose(np.asarray(margin), want, rtol=1e-5)


def test_token_errors_leave_flipped_tokens_out_and_nothing_else(cfg, model):
    check = dict(cfg["check"], tolerance=0.03, settled_margin=0.01,
                 settled_share_min=0.5)
    want = np.zeros((1, 4, 3), np.float32)
    want[0, 0, 0] = 10.0                                    # the scale
    margin = np.array([[[0.2, 0.3], [0.001, 0.3], [0.2, 0.3], [0.4, 0.02]]])
    got = want.copy()
    got[0, 1, 2] = 5.0                  # a flipped token: margin 0.001
    got[0, 2, 1] = 0.1                  # rounding on a settled one
    note = model.token_errors(got, want, margin, check)
    assert note["ok"] and note["settled_share"] == 0.75
    assert note["max_err_over_scale_settled"] == pytest.approx(0.01)
    assert note["tokens_over_tolerance_share"] == 0.25
    assert note["token_err_p50_p90_max"][2] == pytest.approx(0.5)
    got[0, 3, 0] = 1.0                  # a fault on a settled token
    assert not model.token_errors(got, want, margin, check)["ok"]
    # too few settled tokens to say anything
    assert not model.token_errors(
        want, want, margin, dict(check, settled_margin=0.25))["ok"]


def test_flops_per_sample_against_a_hand_count(cfg, model):
    traffic = {"kind": "train", "global_batch": 2, "seq_len": 4096}
    parts = model.forward_macs_per_token(cfg, 4096)
    mla = 2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 32 * 128 * 2048
    assert mla == 26_345_472
    assert parts["mla_projections"] == 5 * mla
    assert parts["attention"] == 5 * 32 * (192 + 128) * 2048   # half of S
    assert parts["dense_mlp"] == 3 * 2048 * 6144
    assert parts["shared_experts"] == 4 * 3 * 2048 * 1536
    assert parts["routed_experts"] == 4 * (6 * 16 / 128) * 3 * 2048 * 768
    assert parts["router"] == 4 * 2048 * 128
    assert parts["head"] == 2048 * 16032
    per_token = 2 * sum(parts.values())
    assert per_token == pytest.approx(720e6, rel=0.005)   # the issue's count
    assert model.flops_per_sample(cfg, traffic) == per_token * 4096 * 3
    step = model.flops_per_sample(cfg, traffic) * 2
    assert step == pytest.approx(17.7e12, rel=0.005)


def test_attention_kernel_cost_against_a_hand_count(cfg, model):
    traffic = {"global_batch": 2, "seq_len": 4096}
    cost = model.attention_kernel_cost(cfg, traffic)
    # a query at position i meets keys 0..i: 4096 x 4097 / 2 pairs of
    # (192 + 128) multiply-accumulates, 64 (batch, head)s, 5 layers
    assert cost["flops"] == 2 * 8_390_656 * 320 * 64 * 5
    assert cost["bytes"] == (192 + 192 + 128 + 128) * 4096 * 2 * 64 * 5
    assert cost["shape"] == "bf16[64,4096,128]"
    # compute bounds it on a v5e: 8.7 ms a step against 2.0
    assert cost["flops"] / 197e12 == pytest.approx(8.72e-3, rel=0.01)
    assert cost["bytes"] / 819e9 == pytest.approx(2.05e-3, rel=0.01)


def test_check_bias_takes_four_held_experts_a_layer(cfg, model):
    seen = set()
    for nth in range(4):
        bias = model.check_bias(cfg, nth)
        assert bias.shape == (128,) and not bias[16:].any()
        plus = set(np.flatnonzero(bias == 1.0))
        assert len(plus) == 4 and set(np.flatnonzero(bias == -1.0)) \
            == set(range(16)) - plus
        seen |= plus
    assert seen == set(range(16))


# ------------------------------------------------------- the cell, toy ----

@pytest.mark.parametrize("trace", [0, 1])
def test_cell_end_to_end(trace, bench_dir, lifted, capsys):
    rc, last, lines = toy.run_cell(bench_dir, CELL, trace, capsys,
                                   seconds=2.0, seed=2 ** 31 + 11)
    assert rc == 0
    assert last["correct"] is True, lines
    assert last["failed"] == 0 and last["attempted"] > 0
    notes = {ln[2:].split(":", 1)[0]: json.loads(ln.split(":", 1)[1])
             for ln in lines[:-1]}
    assert notes["window"]["events"]["backend_compile"]["n"] == 0
    assert notes["checks"]["logits"]["max_err_over_scale"] \
        <= notes["checks"]["logits"]["tolerance"]
    # the same weights under the window's routing, by token
    routed = notes["routing_check"]
    assert routed["ok"] and routed["tokens"] == 2 * 128
    assert routed["max_err_over_scale_settled"] <= routed["tolerance"]
    assert routed["settled_share_min"] <= routed["settled_share"] <= 1.0
    flash = notes["dispatch"]["flash_attention"]
    assert list(flash["buckets"]) == ["bh8_sq128_sk128_d32v16_bfloat16_c1_"
                                      "q128k128"]
    spec = json.load(open(os.path.join(toy.REPO, "BENCHMARK.json")))
    if not trace:
        assert set(last["metrics"]) == {"train_samples_per_s", "setup_s"}
        return
    declared = {m["name"] for m in spec["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    # the CPU trace has no device plane and the CPU reports no memory:
    # what reads either is silent here
    assert {"mfu", "flash_kernel_share", "trainer_sync_ms",
            "compiles_in_window", "setup_compile_s",
            "moe_tokens_per_expert", "moe_load_imbalance"} \
        <= set(last["metrics"]) <= declared
    # the kernel is the family's default ON the TPU; here dispatch takes
    # the dense side (the kernel itself: tests/test_text_model.py)
    assert last["metrics"]["flash_kernel_share"]["value"] == 0.0
    # 2 x 128 tokens x top-3 over 8 experts: 96 pairs an expert a step
    # if the routing were even; two experts of a toy router are not (a
    # held expert gets at most every token: 256)
    assert 10 < last["metrics"]["moe_tokens_per_expert"]["value"] <= 256
    assert 1.0 <= last["metrics"]["moe_load_imbalance"]["value"] <= 2.0


def test_new_readers_find_nothing_in_the_other_cells(model):
    """What this configuration's readers read does not exist in a
    program, or a configuration, that lacks it: ``None``, not an error."""
    from chipbench.harness import bench as hbench
    from chipbench.harness import trace_reduce

    bert = hbench.load_module(os.path.join(
        toy.BENCH, "configs", "bert_base", "model.py"))
    run = {"mode": "train", "model": bert, "cfg": {}, "traffic": {},
           "trace": trace_reduce.EMPTY, "device": {"kind": "TPU v5 lite"},
           "chips": 1}
    for name in ("flash_roofline_share", "moe_tokens_per_expert",
                 "moe_load_imbalance"):
        reader = hbench.load_module(os.path.join(
            toy.BENCH, "layer_metrics", f"{name}.py"))
        assert reader.applies(run) and reader.compute(run) is None


def test_flash_roofline_share_reads_the_attention_calls_only(cfg, model):
    """Six steps of five 3 ms attention calls against an 8.72 ms roofline
    (1.72 TFLOP at 197 TFLOP/s; bytes would take 2 ms): 58.1 %. The
    grouped matmul of an expert layer is a Mosaic call too and is left
    out."""
    from chipbench.harness import bench as hbench

    reader = hbench.load_module(os.path.join(
        toy.BENCH, "layer_metrics", "flash_roofline_share.py"))
    attn = "jvp__.1 custom-call:tpu_custom_call bf16[64,4096,128]"
    other = "ragged-dot.1 custom-call:tpu_custom_call bf16[49152,768]"
    events, t = [], 0
    for _ in range(6 * 5):
        events += [[attn, t, 3_000_000], [other, t + 3_000_000, 1_000_000]]
        t += 5_000_000
    traffic = {"global_batch": 2, "seq_len": 4096, "trace_steps": 6}
    run = {"mode": "train", "model": model, "cfg": cfg, "traffic": traffic,
           "trace": {"devices": {"0": events}, "async": {}, "host": {}},
           "device": {"kind": "TPU v5 lite"}, "chips": 1}
    cost = model.attention_kernel_cost(cfg, traffic)
    least = cost["flops"] / 197e12
    assert least > cost["bytes"] / 819e9
    assert reader.compute(run) == pytest.approx(100 * least / 15e-3)
