"""The ``lfm2_8b_a1b_ep4`` configuration and its cell: the file against the
catalog's keys and the published counts against ``layout`` by hand, the
operation and byte counts against a hand count, the three new readers on
made-up observations and silent in the other cells, and the cell end to
end at toy widths on the CPU mesh through the real ``chipbench/run.py``
code path (no number it prints is a device metric)."""
import json
import os

import numpy as np
import pytest

import chipbench_toy as toy

CONFIG = "lfm2_8b_a1b_ep4"
CELL = "lfm2_8b_a1b_train_s8192"
# weights N(0, 0.113) give 64 inputs the router logits 2,048 give at 0.02
TOY = {"hidden_size": 64, "intermediate_size": 128,
       "moe_intermediate_size": 32, "num_attention_heads": 4,
       "num_key_value_heads": 2, "vocab_size": 512,
       "initializer_range": 0.113, "job.max_seq_length": 512,
       "job.optimizer_params.learning_rate": 1e-3,
       "settling.passes_min": 16, "settling.passes_max": 48}
READERS = ("moe_router_imbalance", "short_conv_time_share",
           "short_conv_roofline_share")


def _load(name="model.py", config=CONFIG):
    from chipbench.harness import bench as hbench

    folder = os.path.join(toy.BENCH, "configs", config)
    if name.endswith(".json"):
        return hbench.load_json(os.path.join(folder, name))
    return hbench.load_module(os.path.join(folder, name))


def _reader(name):
    from chipbench.harness import bench as hbench

    return hbench.load_module(os.path.join(
        toy.BENCH, "layer_metrics", f"{name}.py"))


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
                  **{"traffic.seq_len": 512, "traffic.global_batch": 2,
                     "traffic.pool_batches": 2, "traffic.warmup_steps": 2,
                     "traffic.trace_steps": 3, "host_cpus": None})
    return dst


@pytest.fixture
def lifted(monkeypatch):
    restore = toy.lift_refusal(monkeypatch)
    yield
    restore()


# ------------------------------------------------------------ the counts --

def _count(spec, pick):
    return sum(int(np.prod(shape)) for name, shape, init in spec
               if pick(name) and init in ("normal", "taps", "ones"))


def test_layout_against_the_published_counts(cfg, model):
    """By hand from the published widths: a short-conv operator 16.78 M,
    an attention operator 10.49 M, the dense feed-forward 44.04 M, an
    expert 11.01 M, the router 0.066 M, the tied table 33.55 M held (134.2
    M whole); 507.8 M held here, 8.3 B whole."""
    spec = model.layout(cfg)
    h = 2048
    conv = h * 3 * h + h * 3 + h * h
    attn = 2 * h * h + 2 * h * 512 + 2 * 64
    dense, expert, router = 3 * h * 7168, 3 * h * 1792, 32 * h
    assert (conv, attn, dense, expert, router) == (
        16_783_360, 10_485_888, 44_040_192, 11_010_048, 65_536)

    def layer(i):
        return _count(spec, lambda n: n.startswith(f"layer{i}."))

    norms = 2 * h
    assert layer(0) == conv + dense + norms == 60_827_648
    assert layer(2) == attn + router + 8 * expert + norms == 98_635_904
    assert layer(3) == layer(4) == layer(5) \
        == conv + router + 8 * expert + norms == 104_933_376
    table = 16384 * h
    assert _count(spec, lambda n: n == "embed.weight") == table == 33_554_432
    total = _count(spec, lambda n: True)
    assert total == table + layer(0) + layer(2) + 3 * layer(3) + h
    assert total == 507_820_160 and round(total * 16 / 1e9, 2) == 8.13
    assert "507.8 M" in cfg["deployment"]["parameters_here"]
    # the buffers beside them: bias, rate, counters, 128 calls of history
    buffers = sum(int(np.prod(s)) for _, s, init in spec
                  if init in ("bias", "rate", "counter"))
    assert buffers == 4 * (32 + 1 + 8 + 1 + 1 + 32 + 128 * 32)
    # the whole model: 18 conv and 6 attention layers, 2 dense, 22 sparse
    kinds = cfg["layer_types"]
    assert (kinds.count("conv"), kinds.count("full_attention")) == (18, 6)
    whole = (18 * conv + 6 * attn + 2 * dense
             + 22 * (router + 32 * expert) + 24 * norms
             + cfg["published"]["vocab_size"] * h + h)
    assert 8.2e9 < whole < 8.5e9
    active = whole - 22 * 28 * expert - cfg["published"]["vocab_size"] * h
    assert 1.3e9 < active < 1.6e9          # "1.5 B active"


def test_config_file_states_the_cut(cfg):
    row = {"num_hidden_layers": 24, "num_experts": 32, "vocab_size": 65536}
    assert cfg["reduced"] == list(row) and cfg["published"] == row
    dep = cfg["deployment"]
    assert dep["layers_kept"] == [0, 2, 3, 4, 5]
    assert cfg["num_hidden_layers"] == len(dep["layers_kept"]) == 5
    assert dep["experts_held"] == [0, 8] and cfg["num_experts"] == 8
    assert dep["router_width"] == 32 and dep["chips_sharing_a_layer"] == 4
    assert cfg["vocab_size"] * 4 == row["vocab_size"]
    assert "16" in dep["bytes_per_parameter"]
    # one dense layer and one whole period (1 attention : 3 conv), all four
    # with experts
    kinds = [cfg["layer_types"][i] for i in dep["layers_kept"]]
    assert kinds == ["conv", "full_attention", "conv", "conv", "conv"]
    assert [i >= cfg["num_dense_layers"] for i in dep["layers_kept"]] \
        == [False, True, True, True, True]
    assert {"tie_word_embeddings", "weights", "conv_taps",
            "expert_bias_rule", "bias_update_rate", "learning_rate",
            "settling", "data"} <= set(cfg["assumed"])
    job = cfg["job"]
    assert job["optimizer"] == "adam" and job["max_seq_length"] == 8192
    assert job["optimizer_params"]["wd"] == 0.0
    assert job["optimizer_params"]["multi_precision"] is True
    assert 0 < job["optimizer_params"]["learning_rate"] <= 1e-4
    assert 0 < job["bias_update_rate"] <= 1e-2
    assert cfg["settling"]["target"] == 1.25
    assert cfg["check"]["samples"] == 1 and cfg["check"]["tolerance"] < 0.05
    assert len(cfg["check"]["reason"]) > 200


def test_catalog_numbers_are_in_the_file(cfg):
    """Every key of the catalog row's ``config`` (model-configs guide,
    ``LFM2-8B-A1B``) under the same key, but the three in ``reduced``;
    ``layer_types`` copied whole."""
    period = ["conv", "conv", "conv", "full_attention"]
    row = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
           "intermediate_size": 7168,
           "layer_types": ["conv", "conv", "full_attention"] + 4 * period
           + ["conv", "conv", "full_attention", "conv", "conv"],
           "max_position_embeddings": 128000, "model_type": "lfm2_moe",
           "moe_intermediate_size": 1792, "norm_eps": 1e-05,
           "norm_topk_prob": True, "num_attention_heads": 32,
           "num_dense_layers": 2, "num_experts": 32,
           "num_experts_per_tok": 4, "num_hidden_layers": 24,
           "num_key_value_heads": 8, "rope_theta": 1000000,
           "routed_scaling_factor": 1, "use_expert_bias": True,
           "vocab_size": 65536}
    assert cfg["source"] == ("https://huggingface.co/LiquidAI/LFM2-8B-A1B/"
                             "blob/main/config.json")
    differs = {k for k, v in row.items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"])
    assert len(cfg["layer_types"]) == 24 == cfg["published"][
        "num_hidden_layers"]
    assert cfg["tie_word_embeddings"] is True \
        and "tie_word_embeddings" not in row


def test_the_model_gets_published_keys_only(cfg, model):
    published, kept, held = model.model_config(cfg)
    assert kept == [0, 2, 3, 4, 5] and held == (0, 8)
    assert published["num_hidden_layers"] == 24
    assert published["num_experts"] == 32
    assert published["vocab_size"] == 16384
    assert published["layer_types"] == cfg["layer_types"]
    assert all(not isinstance(v, dict) for v in published.values())
    assert {"dtype", "initializer_range", "name", "source", "job",
            "deployment"}.isdisjoint(published)


def test_check_bias_is_the_margin_pattern_the_file_describes(cfg, model):
    for nth in range(4):
        bias = model.check_bias(cfg, nth)
        assert bias.shape == (32,) and not bias[8:].any()
        assert sorted(np.flatnonzero(bias == 1.0)) == [2 * nth, 2 * nth + 1]
        assert (bias[:8] == -1.0).sum() == 6


# ------------------------------------------------------------ the traffic -

def test_the_cells_traffic_is_the_issues(cfg, model):
    import jax

    wl = json.load(open(os.path.join(toy.BENCH, "workloads",
                                     f"{CELL}.json")))
    assert wl["chips"] == 1 and wl["mode"] == "train"
    assert wl["host_cpus"] == 4 and len(wl["host_cpus_why"]) > 40
    assert wl["traffic"] == {
        "kind": "train", "global_batch": 1, "seq_len": 8192,
        "token_zipf_exponent": 1.0, "mesh": {"dp": 1}, "pool_batches": 4,
        "warmup_steps": 3, "trace_steps": 6, "trainer_options": {}}
    assert wl["end_to_end"] == {"train_samples_per_s": "samples/s"}
    traffic = dict(wl["traffic"], global_batch=2)
    x, y = model.make_batch(cfg, traffic, jax.random.PRNGKey(7))
    x, y = np.asarray(x), np.asarray(y)
    assert x.shape == y.shape == (2, 8192) and x.dtype == np.int32
    assert (x[:, 1:] == y[:, :-1]).all()
    assert 0 <= x.min() and x.max() < cfg["vocab_size"]
    # Zipf(1) over 16,384 ids: id 0 is 1 / H(16384) = 9.7 % of the tokens
    assert (x == 0).mean() == pytest.approx(0.0973, rel=0.1)
    ids = model.check_inputs(cfg, 2 ** 31 + 5, 1)
    assert ids.shape == (1, 8192)


# -------------------------------------------------------------- the costs -

def test_flops_per_sample_against_a_hand_count(cfg, model):
    """The issue's count: 216.3 M multiply-accumulates a token forward,
    10.6 TFLOP a step."""
    traffic = {"kind": "train", "global_batch": 1, "seq_len": 8192}
    parts = model.forward_macs_per_token(cfg, 8192)
    h = 2048
    assert parts["conv_projections"] == 4 * (h * 6144 + h * h) == 67_108_864
    assert parts["attention_projections"] == 2 * h * h + 2 * h * 512 \
        == 10_485_760
    # 32 query heads x (64 + 64) a key seen, 4,096 keys a query
    assert parts["attention"] == 4096 * 32 * 64 * 2 == 16_777_216
    assert parts["dense_mlp"] == 3 * h * 7168 == 44_040_192
    # top-4 x 8 / 32 = one held pair a token a layer
    assert parts["routed_experts"] == 4 * 1 * 3 * h * 1792 == 44_040_192
    assert parts["router"] == 4 * 32 * h == 262_144
    assert parts["head"] == h * 16384 == 33_554_432
    assert sum(parts.values()) == pytest.approx(216.3e6, rel=0.001)
    assert model.flops_per_sample(cfg, traffic) == pytest.approx(
        sum(parts.values()) * 6 * 8192)
    assert model.flops_per_sample(cfg, traffic) == pytest.approx(
        10.63e12, rel=0.002)
    # the short convolutions' operators 31 %, the experts 20 %
    assert parts["conv_projections"] / sum(parts.values()) \
        == pytest.approx(0.31, abs=0.005)
    assert parts["routed_experts"] / sum(parts.values()) \
        == pytest.approx(0.20, abs=0.005)


def test_attention_kernel_cost_against_a_hand_count(cfg, model):
    traffic = {"global_batch": 1, "seq_len": 8192}
    cost = model.attention_kernel_cost(cfg, traffic)
    pairs = 8192 * 8193 // 2
    assert cost["flops"] == 2 * pairs * 128 * 32 == 274_911_461_376
    # q and out 32 heads, k and v 8 heads, 64 wide, bf16
    assert cost["bytes"] == (2 * 32 + 2 * 8) * 64 * 8192 * 2 == 83_886_080
    assert cost["shape"] == "bf16[32,8192,64]"
    # compute bounds it on a v5e: 1.40 ms a step against 0.10
    assert cost["flops"] / 197e12 == pytest.approx(1.395e-3, rel=0.01)
    assert cost["bytes"] / 819e9 == pytest.approx(0.102e-3, rel=0.01)


def test_short_conv_cost_against_a_hand_count(cfg, model):
    traffic = {"global_batch": 1, "seq_len": 8192}
    cost = model.short_conv_cost(cfg, traffic)
    stream = 8192 * 2048 * 2                      # one (S, hidden) in bf16
    assert cost["bytes"] == (4 + 7) * stream * 4 == 1_476_395_008
    assert cost["scope"] == "sconv.gate"
    assert "flops" not in cost          # bytes bound it: no vector peak
    assert cost["bytes"] / 819e9 == pytest.approx(1.803e-3, rel=0.01)


# ------------------------------------------------------------ the readers -

def _phases_run(model, cfg, by_scope, busy_ms, route=None):
    class Model:
        short_conv_cost = staticmethod(model.short_conv_cost)

        @staticmethod
        def expert_load():
            return route

    traffic = {"global_batch": 1, "seq_len": 8192, "trace_steps": 6}
    return {"mode": "train", "model": Model, "cfg": cfg, "traffic": traffic,
            "device": {"kind": "TPU v5 lite"}, "chips": 1,
            "step_phases": None if by_scope is None else {
                "busy_ms": busy_ms, "by_scope_ms": by_scope}}


def test_readers_on_made_up_observations(cfg, model):
    """4.5 ms a step under ``sconv.gate`` (1.5 forward, 3.0 backward) of
    150 busy: 3 %, against a 1.803 ms roofline 40.1 %; two expert layers
    whose busiest experts took 1.2 and 1.4 of the mean: 1.3."""
    by_scope = {"sconv.gate": {"forward": 1.5, "backward": 3.0},
                "sconv.project": {"forward": 20.0, "backward": 40.0}}
    pairs = [100.0] * 32
    route = {2: {"pairs": pairs[:8], "peak": 1, "calls": 1,
                 "route_pairs": [120.0] + [100.0] * 30 + [80.0]},
             3: {"pairs": pairs[:8], "peak": 1, "calls": 1,
                 "route_pairs": [140.0, 60.0] + [100.0] * 30}}
    run = _phases_run(model, cfg, by_scope, 150.0, route)
    assert _reader("short_conv_time_share").compute(run) \
        == pytest.approx(3.0)
    assert _reader("short_conv_roofline_share").compute(run) \
        == pytest.approx(100 * 1_476_395_008 / 819e9 / 4.5e-3)
    assert _reader("moe_router_imbalance").compute(run) \
        == pytest.approx(1.3)
    # a step that names no such scope, a run without a joined trace
    for silent in (_phases_run(model, cfg, {"moe.route": {"forward": 1.0}},
                               150.0),
                   _phases_run(model, cfg, None, 0.0)):
        assert _reader("short_conv_time_share").compute(silent) is None
        assert _reader("short_conv_roofline_share").compute(silent) is None
        assert _reader("moe_router_imbalance").compute(silent) is None


@pytest.mark.parametrize("config", ["bert_base", "kanana2_30b_a3b_ep8",
                                    "phi4_mini_flash_l6", "resnet50_v1"])
@pytest.mark.parametrize("name", READERS)
def test_new_readers_find_nothing_in_the_other_cells(name, config):
    """What the new readers read does not exist in a configuration, or a
    program, that lacks it (the parent commit among them): ``None``, not
    an error."""
    other = _load(config=config)
    run = {"mode": "train", "model": other, "cfg": {}, "traffic": {},
           "device": {"kind": "TPU v5 lite"}, "chips": 1,
           "step_phases": {"busy_ms": 100.0, "by_scope_ms": {
               "sconv.gate": {"forward": 1.0}}}}
    reader = _reader(name)
    assert reader.applies(run) and reader.compute(run) is None


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
    logits = notes["checks"]["logits"]
    assert logits["samples"] == 1
    assert logits["max_err_over_scale"] <= logits["tolerance"]
    assert notes["checks"]["loss_last_cycle"] \
        < notes["checks"]["loss_first_cycle"]
    routing = notes["routing"]
    assert routing["passes"] <= 48 and len(routing["imbalance_reached"]) == 4
    assert max(routing["imbalance_reached"]) \
        < max(routing["imbalance_first"])
    window = notes["routing_window"]
    assert sorted(window) == ["2", "3", "4", "5"]
    steps = 2 + last["attempted"]
    for rec in window.values():
        assert rec["calls"] == steps
        assert rec["first"]["calls"] == rec["last"]["calls"] == min(10, steps)
        assert sum(rec["last"]["route_pairs"]) \
            == min(10, steps) * 2 * 512 * 4
    # grouped keys through the flash family: the settling's forward (one
    # sequence; the comparison's falls in its bucket), then the step's
    # decision (two)
    flash = notes["dispatch"]["flash_attention"]
    assert list(flash["buckets"]) == [
        "bh4_sq512_sk512_d16_bfloat16_c1_q512k512_g2",
        "bh8_sq512_sk512_d16_bfloat16_c1_q512k512_g2"]
    spec = json.load(open(os.path.join(toy.REPO, "BENCHMARK.json")))
    if not trace:
        assert set(last["metrics"]) == {"train_samples_per_s", "setup_s"}
        return
    declared = {m["name"] for m in spec["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    # the CPU trace has no device plane and the CPU reports no memory:
    # what reads either is silent here
    assert {"mfu", "flash_kernel_share", "trainer_sync_ms",
            "compiles_in_window", "setup_compile_s", "moe_tokens_per_expert",
            "moe_load_imbalance", "moe_router_imbalance"} \
        <= set(last["metrics"]) <= declared
    # 2 x 512 tokens x top-4 over 32 experts: 128 pairs an expert a step
    assert last["metrics"]["moe_tokens_per_expert"]["value"] \
        == pytest.approx(128, rel=0.25)
    assert 1.0 <= last["metrics"]["moe_router_imbalance"]["value"] < 2.0


def test_the_cell_and_its_readers_are_declared_in_this_order():
    """In this order among whatever follows, not last: the next PR's
    additions do not fail it."""
    spec = json.load(open(os.path.join(toy.REPO, "BENCHMARK.json")))
    cells = [c["name"] for c in spec["workloads"]]
    cell = spec["workloads"][cells.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, CELL, 1)
    assert cells.index(CELL) > cells.index("phi4_mini_flash_train_s4096")
    config = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["file"] == f"chipbench/configs/{CONFIG}/config.json"
    names = [m["name"] for m in spec["per_layer"]]
    at = [names.index(r) for r in READERS]
    assert at == sorted(at) and at[0] > names.index(
        "step_phase_unmatched_share")
    for m in spec["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "train_samples_per_s"
    lists = {m["name"]: m.get("workloads")
             for m in spec["end_to_end"] + spec["per_layer"]}
    kanana = "kanana2_30b_a3b_train_s4096"
    for name, listed in lists.items():
        if not listed or name in READERS:
            continue
        # every metric the language model's cell reports, this one does
        assert (CELL in listed) == (kanana in listed), name
        if CELL in listed:
            assert listed.index(CELL) > listed.index(kanana), name
