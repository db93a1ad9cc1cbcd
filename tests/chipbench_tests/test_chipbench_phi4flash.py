"""The ``phi4_mini_flash_l6`` configuration and its cell: the published
counts against ``layout`` by hand, the operation and byte counts against a
hand count, the three scan readers on a made-up trace and silent in the
other cells, and the cell end to end at toy widths on the CPU mesh through
the real ``chipbench/run.py`` code path (no number it prints is a device
metric)."""
import json
import os

import numpy as np
import pytest

import chipbench_toy as toy

CONFIG = "phi4_mini_flash_l6"
CELL = "phi4_mini_flash_train_s4096"
TOY = {"hidden_size": 128, "intermediate_size": 256,
       "num_attention_heads": 4, "num_key_value_heads": 2,
       "sliding_window": 48, "vocab_size": 96, "job.max_seq_length": 128,
       "job.optimizer_params.learning_rate": 1e-3}
READERS = ("ssm_scan_roofline_share", "ssm_scan_time_share",
           "ssm_kernel_share")


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
                  **{"traffic.seq_len": 128, "traffic.global_batch": 2,
                     "traffic.pool_batches": 2, "traffic.warmup_steps": 2,
                     "traffic.trace_steps": 3})
    return dst


@pytest.fixture
def lifted(monkeypatch):
    restore = toy.lift_refusal(monkeypatch)
    yield
    restore()


# ------------------------------------------------------------ the counts --

def _count(spec, pick):
    return sum(int(np.prod(shape)) for name, shape, _ in spec if pick(name))


def test_layout_against_the_published_counts(cfg, model):
    """By hand from the published widths: a Mamba mixer 41.2 M, attention
    19.7 M, a gated memory unit 26.2 M, cross-attention 13.1 M, an MLP 78.6
    M; 697 M held here, 3.85 B whole."""
    spec = model.layout(cfg)
    h, inner, inter = 2560, 5120, 10240
    mlp = 3 * h * inter
    norms = 4 * h
    mamba = (h * 2 * inner + inner * 4 + inner           # in, conv
             + inner * (160 + 32) + 160 * inner + inner  # x, step
             + inner * 16 + inner + inner * h)           # A, D, out
    attn = h * h + h + h * h + h + h * h + h + 4 * 64 + 128
    cross = attn - (h * h + h)
    gmu = 2 * h * inner
    assert (mlp, mamba, attn, cross, gmu) == (
        78_643_200, 41_241_600, 19_668_864, 13_112_704, 26_214_400)

    def layer(i):
        return _count(spec, lambda n: n.startswith(f"layer{i}."))

    assert layer(0) == layer(16) == mamba + mlp + norms
    assert layer(1) == layer(17) == attn + mlp + norms
    assert layer(18) == gmu + mlp + norms
    assert layer(19) == cross + mlp + norms
    embed = 25008 * h
    assert _count(spec, lambda n: n == "embed.weight") == embed == 64_020_480
    total = _count(spec, lambda n: True)
    assert total == (2 * mamba + 2 * attn + gmu + cross + 6 * (mlp + norms)
                     + embed + 2 * h)
    assert total == 697_094_272 and round(total * 16 / 1e9, 1) == 11.2
    pub = cfg["published"]
    whole = (9 * mamba + 9 * attn + 7 * gmu + 7 * cross
             + pub["num_hidden_layers"] * (mlp + norms)
             + pub["vocab_size"] * h + 2 * h)
    assert 3.84e9 < whole < 3.86e9
    assert [model.kind_of(cfg, i) for i in range(32)].count("mamba") == 8
    assert [model.kind_of(cfg, i) for i in (0, 1, 16, 17, 18, 19, 31)] == [
        "mamba", "window", "mamba_memory", "full", "gmu", "cross", "cross"]


def test_config_file_states_the_cut(cfg):
    row = {"num_hidden_layers": 32, "vocab_size": 200064}
    assert cfg["reduced"] == list(row) and cfg["published"] == row
    assert cfg["layers_kept"] == [0, 1, 16, 17, 18, 19]
    assert cfg["num_hidden_layers"] == len(cfg["layers_kept"]) == 6
    assert cfg["vocab_size"] * 8 == row["vocab_size"]
    assert cfg["deployment"]["chips_sharing_a_layer"] == 8
    assert "16" in cfg["deployment"]["bytes_per_parameter"]
    # every width as published
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["sliding_window"], cfg["mb_per_layer"],
            cfg["layer_norm_eps"], cfg["tie_word_embeddings"]) \
        == (2560, 10240, 40, 20, 512, 2, 1e-5, True)
    assert {"mamba", "mamba_init", "memory", "head_pairs",
            "attention_bias"} <= set(cfg["assumed"])
    assert cfg["job"]["optimizer"] == "adam" and cfg["job"][
        "optimizer_params"] == {"learning_rate": 1e-4, "wd": 0.0,
                                "multi_precision": True}
    assert cfg["check"]["samples"] == 1 and cfg["check"]["tolerance"] < 0.05
    assert len(cfg["check"]["reason"]) > 200


def test_the_model_gets_published_keys_only(cfg, model):
    published, kept = model.model_config(cfg)
    assert kept == [0, 1, 16, 17, 18, 19]
    assert published["num_hidden_layers"] == 32
    assert published["vocab_size"] == 25008
    assert all(not isinstance(v, (dict, list)) for v in published.values())
    assert {"dtype", "initializer_range", "name", "source", "layers_kept"} \
        .isdisjoint(published)


def test_catalog_numbers_are_in_the_file(cfg):
    """Every number of the catalog row's ``config`` under the same key,
    but the two in ``reduced``."""
    row = {"embd_pdrop": 0, "hidden_size": 2560, "intermediate_size": 10240,
           "layer_norm_eps": 1e-05, "max_position_embeddings": 262144,
           "mb_per_layer": 2, "num_attention_heads": 40,
           "num_key_value_heads": 20, "resid_pdrop": 0,
           "sliding_window": 512}
    assert {k: cfg[k] for k in row} == row
    assert (cfg["hidden_act"], cfg["model_type"], cfg["mlp_bias"],
            cfg["lm_head_bias"]) == ("silu", "phi4flash", False, False)


# ------------------------------------------------------------ the traffic -

def test_token_ids_follow_zipfs_law_from_the_seed(cfg, model):
    import jax

    wl = json.load(open(os.path.join(toy.BENCH, "workloads",
                                     f"{CELL}.json")))
    assert wl["chips"] == 1 and wl["mode"] == "train"
    assert wl["traffic"] == {
        "kind": "train", "global_batch": 1, "seq_len": 4096,
        "token_zipf_exponent": 1.0, "mesh": {"dp": 1}, "pool_batches": 4,
        "warmup_steps": 3, "trace_steps": 6, "trainer_options": {}}
    traffic = dict(wl["traffic"], global_batch=8)
    x, y = model.make_batch(cfg, traffic, jax.random.PRNGKey(7))
    x, y = np.asarray(x), np.asarray(y)
    assert x.shape == y.shape == (8, 4096) and x.dtype == np.int32
    assert (x[:, 1:] == y[:, :-1]).all()
    assert 0 <= x.min() and x.max() < cfg["vocab_size"]
    h = (1.0 / np.arange(1, cfg["vocab_size"] + 1)).sum()
    freq = np.bincount(x.ravel(), minlength=cfg["vocab_size"]) / x.size
    for i in (0, 1, 9):
        assert freq[i] == pytest.approx(1 / ((i + 1) * h), rel=0.15)
    again, _ = model.make_batch(cfg, traffic, jax.random.PRNGKey(7))
    other, _ = model.make_batch(cfg, traffic, jax.random.PRNGKey(8))
    assert (np.asarray(again) == x).all() and (np.asarray(other) != x).any()
    ids = model.check_inputs(cfg, 2 ** 31 + 5, 1)
    assert ids.shape == (1, 4096) and (ids == 0).mean() > 0.05


# -------------------------------------------------------------- the costs -

def test_pairs_in_band_by_hand(model):
    assert model.pairs_in_band(4096) == 4096 * 4097 // 2 == 8_390_656
    # 512 x 513 / 2 growing rows, then 3584 rows of 512
    assert model.pairs_in_band(4096, 512) == 131_328 + 3584 * 512 \
        == 1_966_336
    assert model.pairs_in_band(8, 3) == 1 + 2 + 3 * 6
    assert model.pairs_in_band(4, 9) == 10
    brute = sum(1 for q in range(50) for k in range(50) if 0 <= q - k < 7)
    assert model.pairs_in_band(50, 7) == brute


def test_flops_per_sample_against_a_hand_count(cfg, model):
    traffic = {"kind": "train", "global_batch": 1, "seq_len": 4096}
    parts = model.forward_macs_per_token(cfg, 4096)
    h, inner = 2560, 5120
    assert parts["ssm_projections"] == 2 * (h * 10240 + inner * 192
                                            + 160 * inner + inner * h)
    assert parts["ssm_scan"] == 2 * 3 * inner * 16
    assert parts["attention_projections"] == 3 * 2 * h * h + 2 * h * h
    # 40 query heads x (64 + 128) a key seen; window 480.1, full and cross
    # 2048.5 keys a query
    keys = (1_966_336 + 2 * 8_390_656) / 4096
    assert parts["attention"] == pytest.approx(40 * 192 * keys)
    assert parts["gmu"] == 2 * h * inner
    assert parts["mlp"] == 6 * 3 * h * 10240
    assert parts["head"] == h * 25008
    per_token = 2 * sum(parts.values())
    assert per_token == pytest.approx(1.4648e9, rel=0.001)
    assert model.flops_per_sample(cfg, traffic) == pytest.approx(
        per_token * 4096 * 3)
    # a step: 18.0 TFLOP, of which attention 0.86
    assert model.flops_per_sample(cfg, traffic) == pytest.approx(
        18.0e12, rel=0.005)
    assert 2 * parts["attention"] * 4096 * 3 == pytest.approx(0.864e12,
                                                              rel=0.01)


def test_attention_kernel_cost_against_a_hand_count(cfg, model):
    traffic = {"global_batch": 1, "seq_len": 4096}
    cost = model.attention_kernel_cost(cfg, traffic)
    # two calls a layer, 20 query heads each, (64 + 128) a pair in the band
    pairs = 1_966_336 + 2 * 8_390_656
    assert cost["flops"] == 2 * pairs * 192 * 20 * 2
    # a call: q 20 x 64, out 20 x 128, k 10 x 64, v 10 x 128, bf16
    call = (20 * 192 + 10 * 192) * 4096 * 2
    assert cost["bytes"] == call * 2 * 3 == 283_115_520
    assert cost["shape"] == "bf16[20,4096,128]"
    # compute bounds it on a v5e: 1.46 ms a step against 0.35
    assert cost["flops"] / 197e12 == pytest.approx(1.462e-3, rel=0.01)
    assert cost["bytes"] / 819e9 == pytest.approx(0.346e-3, rel=0.01)


def test_ssm_scan_kernel_cost_against_a_hand_count(cfg, model):
    traffic = {"global_batch": 1, "seq_len": 4096}
    cost = model.ssm_scan_kernel_cost(cfg, traffic)
    wide, narrow = 4096 * 5120, 4096 * 16
    forward = wide * (2 + 4 + 2) + narrow * 2 * 2
    backward = wide * (2 + 4 + 2 + 2 + 4) + narrow * 4 * 2
    assert cost["bytes"] == 2 * (forward + backward) == 924_319_744
    assert cost["shape"] == "bf16[1,4096,5120]"
    assert "flops" not in cost          # bytes bound it: no vector peak
    assert cost["bytes"] / 819e9 == pytest.approx(1.129e-3, rel=0.01)


# ------------------------------------------------------------ the readers -

def _scan_run(model, cfg, events, dispatch):
    traffic = {"global_batch": 1, "seq_len": 4096, "trace_steps": 6}
    return {"mode": "train", "model": model, "cfg": cfg, "traffic": traffic,
            "trace": {"devices": {"0": events}, "async": {}, "host": {}},
            "device": {"kind": "TPU v5 lite"}, "chips": 1,
            "dispatch_stats": dispatch}


def test_scan_readers_read_the_scan_calls_only(cfg, model):
    """Six steps of two forward (2 ms) and two backward (4 ms) scan calls,
    six attention calls (1 ms) and 40 ms of fusions: 12 of 58 ms are the
    scan's, against a 1.129 ms roofline."""
    fwd = "jvp_ssm.scan_.1 custom-call:tpu_custom_call bf16[1,4096,5120]"
    bwd = ("transpose_jvp_ssm.scan__.1 custom-call:tpu_custom_call "
           "bf16[1,4096,5120]")
    attn = "jvp_attn.full_.1 custom-call:tpu_custom_call bf16[20,4096,128]"
    other = "fusion.7 fusion:kOutput bf16[4096,2560]"
    events, t = [], 0
    for _ in range(6):
        for name, ms, n in ((fwd, 2, 2), (bwd, 4, 2), (attn, 1, 6),
                            (other, 40, 1)):
            for _ in range(n):
                events.append([name, t, ms * 1_000_000])
                t += ms * 1_000_000
    stats = {"selective_scan": {"kernel": 2, "xla": 0},
             "selective_scan_bwd": {"kernel": 2, "xla": 0}}
    run = _scan_run(model, cfg, events, stats)
    least = model.ssm_scan_kernel_cost(cfg, run["traffic"])["bytes"] / 819e9
    assert _reader("ssm_scan_roofline_share").compute(run) \
        == pytest.approx(100 * least / 12e-3)
    assert _reader("ssm_scan_time_share").compute(run) \
        == pytest.approx(100 * 12 / 58)
    assert _reader("ssm_kernel_share").compute(run) == 100.0
    # the backward as the chunked XLA recomputation: half the decisions
    stats["selective_scan_bwd"] = {"kernel": 0, "xla": 2}
    assert _reader("ssm_kernel_share").compute(run) == 50.0
    # flash_roofline_share reads the new attention_kernel_cost unchanged
    flash = model.attention_kernel_cost(cfg, run["traffic"])
    assert _reader("flash_roofline_share").compute(run) == pytest.approx(
        100 * flash["flops"] / 197e12 / 6e-3)
    # the scan as XLA code leaves no call to read
    run = _scan_run(model, cfg, [e for e in events if "5120" not in e[0]],
                    stats)
    assert _reader("ssm_scan_roofline_share").compute(run) is None
    assert _reader("ssm_scan_time_share").compute(run) is None


@pytest.mark.parametrize("config", ["bert_base", "kanana2_30b_a3b_ep8",
                                    "resnet50_v1"])
@pytest.mark.parametrize("name", READERS)
def test_scan_readers_find_nothing_in_the_other_cells(name, config):
    """What the scan readers read does not exist in a program, or a
    configuration, that lacks it (the parent commit among them): ``None``,
    not an error."""
    from chipbench.harness import trace_reduce

    other = _load(config=config)
    events = [["jvp__.1 custom-call:tpu_custom_call bf16[64,4096,128]", 0,
               1_000_000]]
    for trace in (trace_reduce.EMPTY,
                  {"devices": {"0": events}, "async": {}, "host": {}}):
        run = {"mode": "train", "model": other, "cfg": {}, "traffic": {},
               "trace": trace, "device": {"kind": "TPU v5 lite"},
               "chips": 1,
               "dispatch_stats": {"flash_attention": {"kernel": 5, "xla": 0}}}
        reader = _reader(name)
        assert reader.applies(run) and reader.compute(run) is None


# ------------------------------------------------------- the reference ----

def test_reference_faults_are_known_by_name(cfg, model):
    assert set(model.FAULTS) == {
        "state_bf16", "dt_bf16", "window_off", "lam_dropped",
        "memory_after_gate", "cross_own_kv", "weights_float8"}
    with pytest.raises(ValueError):
        model.reference(cfg, {}, (np.zeros((1, 4), np.int32), None),
                        fault="no_such")


def test_the_comparison_catches_a_bfloat16_scan_state(tmp_path, capsys):
    """``check.scan`` at work, through ``harness/check.against_reference``
    (``chipbench/fault_readings.py``, the runner PERF.md's readings come
    from): over 4,096 positions with the comparison's slowed decays the
    sound reference passes the file's own tolerance and the reference
    whose state is rounded to bfloat16 every step does not; the step in
    bfloat16 hardly moves it (the CPU tests' alone:
    tests/test_selective_scan.py). Toy widths: weights N(0, 0.05) so that
    128 inputs give the unit activations 2,560 give at 0.02, and decays a
    64th where the file's are a quarter (256 channels carry less of the
    logits than 5,120; at a quarter the fault reads 0.015-0.027 here)."""
    from chipbench import fault_readings

    dst = toy.toy_copy(str(tmp_path / "chipbench"))
    toy.edit_json(os.path.join(dst, f"configs/{CONFIG}/config.json"),
                  **{**TOY, "job.max_seq_length": 4096,
                     "initializer_range": 0.05,
                     "check.scan.decay_scale": 2.0 ** -6})
    assert fault_readings.main(
        [CELL, "--bench-dir", dst, "--seeds", str(2 ** 31 + 11),
         "--faults", "state_bf16", "dt_bf16"]) == 0
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["seed"] == 2 ** 31 + 11
    sound, state, step = row["sound"], row["state_bf16"], row["dt_bf16"]
    assert sound["ok"] and sound["max_err_over_scale"] < sound["tolerance"]
    assert not state["ok"]
    assert state["max_err_over_scale"] > 4 * sound["tolerance"]
    assert state["moves_reference"] > 100 * step["moves_reference"] > 0


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
    # two softmaxes a layer over three attention layers and two scans:
    # the step program's decisions (two sequences), then the comparison's
    # forward (one)
    dispatch = notes["dispatch"]
    flash = "_sq128_sk128_d32v64_bfloat16_c1_q128k128_g2"
    assert list(dispatch["flash_attention"]["buckets"]) == [
        "bh2" + flash, "bh2" + flash + "_w48",
        "bh4" + flash, "bh4" + flash + "_w48"]
    assert list(dispatch["selective_scan"]["buckets"]) == [
        "b1_s128_d256_n16_bfloat16_t64l256",
        "b2_s128_d256_n16_bfloat16_t64l256"]
    spec = json.load(open(os.path.join(toy.REPO, "BENCHMARK.json")))
    if not trace:
        assert set(last["metrics"]) == {"train_samples_per_s", "setup_s"}
        return
    declared = {m["name"] for m in spec["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    # the CPU trace has no device plane and the CPU reports no memory:
    # what reads either is silent here
    assert {"mfu", "flash_kernel_share", "flash_backward_kernel_share",
            "ssm_kernel_share", "trainer_sync_ms", "compiles_in_window",
            "setup_compile_s"} <= set(last["metrics"]) | {
                "flash_backward_kernel_share"} <= declared | {
                "flash_backward_kernel_share"}
    assert not {"moe_tokens_per_expert", "moe_load_imbalance"} \
        & set(last["metrics"])
    # the kernels are the families' default ON the TPU; here dispatch
    # takes the XLA side (the kernels: tests/test_selective_scan.py,
    # tests/test_phi4flash_model.py)
    assert last["metrics"]["ssm_kernel_share"]["value"] == 0.0
    assert last["metrics"]["flash_kernel_share"]["value"] == 0.0


def test_the_cell_is_declared_where_the_issue_says():
    spec = json.load(open(os.path.join(toy.REPO, "BENCHMARK.json")))
    cell = spec["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, CONFIG, CELL, 1)
    assert spec["configs"][-1]["name"] == CONFIG
    assert spec["configs"][-1]["reduced"] == ["num_hidden_layers",
                                              "vocab_size"]
    lists = {m["name"]: m.get("workloads")
             for m in spec["end_to_end"] + spec["per_layer"]}
    kanana = "kanana2_30b_a3b_train_s4096"
    for name, cells in lists.items():
        if not cells:
            continue
        if name.startswith("moe_"):
            assert CELL not in cells
        elif kanana in cells or name in READERS:
            assert cells[-1] == CELL, name
        else:
            assert CELL not in cells, name
    assert [m["name"] for m in spec["per_layer"][-3:]] == list(READERS)
