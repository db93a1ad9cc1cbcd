"""Every mode of the benchmark rehearsed end to end on the CPU mesh at toy
widths, through the real ``chipbench/run.py`` code path. A CPU run says
that the control flow is right and what the program counts; none of the
numbers it prints is a device metric."""
import hashlib
import json
import os
import textwrap

import pytest

import chipbench_toy as toy

LAST_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}
TRAIN_CELLS = ["resnet50_train_1chip", "resnet50_train_dp4",
               "bert_base_train_s384"]


@pytest.fixture(scope="module")
def bench_dir(tmp_path_factory):
    return toy.toy_copy(str(tmp_path_factory.mktemp("toy") / "chipbench"))


@pytest.fixture
def lifted(monkeypatch):
    restore = toy.lift_refusal(monkeypatch)
    yield
    restore()


def _benchmark_json():
    with open(os.path.join(toy.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _declared(kind, cell):
    """Names of the BENCHMARK.json metrics of ``kind`` that ``cell``
    reports."""
    return {m["name"] for m in _benchmark_json()[kind]
            if cell in m.get("workloads", [cell])}


def test_refuses_without_a_tpu(capsys):
    """No chip is an error: non-zero exit, one line on stderr, and not a
    byte on stdout that could be read as a result."""
    from chipbench import run as cbrun

    # a cell without host_cpus: the real files would confine this process
    with pytest.raises(SystemExit) as exc:
        cbrun.main(["--workload", "resnet50_train_dp4", "--seed", "1",
                    "--seconds", "1", "--trace", "0"])
    assert exc.value.code not in (0, None)
    out, err = capsys.readouterr()
    assert out == ""
    assert "no TPU" in err and len(err.strip().splitlines()) == 1


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_train_cell_end_to_end(cell, bench_dir, lifted, capsys):
    rc, last, lines = toy.run_cell(bench_dir, cell, 0, capsys)
    assert rc == 0
    assert set(last) == LAST_KEYS
    assert set(last["device"]) == DEVICE_KEYS
    assert last["correct"] is True, lines
    assert last["failed"] == 0 and last["attempted"] > 0
    assert set(last["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert set(last["metrics"]) == _declared("end_to_end", cell)
    for m in last["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    # nothing but the last line is bare JSON: the rest are "# " notes
    assert all(ln.startswith("# ") for ln in lines[:-1])
    notes = {ln[2:].split(":", 1)[0]: json.loads(ln.split(":", 1)[1])
             for ln in lines[:-1]}
    chips = 4 if cell.endswith("dp4") else 1
    assert notes["checks"]["parameters_span_devices"] == chips
    assert notes["checks"]["loss_spans_devices"] == chips
    assert notes["window"]["events"]["backend_compile"]["n"] == 0


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_train_cell_traced(cell, bench_dir, lifted, capsys):
    rc, last, lines = toy.run_cell(bench_dir, cell, 1, capsys)
    assert rc == 0
    assert set(last) == LAST_KEYS | {"breakdown"}
    assert set(last["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
    assert last["device"]["window_s"] > 0
    assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}
    got = set(last["metrics"])
    # the CPU trace has no device plane: readers that need one find
    # nothing and are left out; what is there is declared for this cell
    assert got <= _declared("per_layer", cell)
    assert {"compiles_in_window", "setup_compile_s", "trainer_sync_ms",
            "mfu"} <= got
    assert last["metrics"]["compiles_in_window"]["value"] == 0
    if cell.startswith("bert"):
        # off the TPU dispatch takes the XLA baseline: a count, not a time
        assert last["metrics"]["flash_kernel_share"]["value"] == 0.0
    else:
        assert "flash_kernel_share" not in got


@pytest.mark.parametrize("trace", [0, 1])
def test_serve_cell_end_to_end(trace, bench_dir, lifted, capsys):
    cell = "resnet50_serve_open"
    rc, last, lines = toy.run_cell(bench_dir, cell, trace, capsys)
    assert rc == 0
    assert last["correct"] is True, lines
    assert last["failed"] == 0 and last["attempted"] > 50
    if trace:
        assert set(last) == LAST_KEYS | {"breakdown"}
        assert {"serve_batch_fill", "serve_queue_wait_ms",
                "serve_gen_late_ms", "compiles_in_window",
                "setup_compile_s"} <= set(last["metrics"])
        assert 0 < last["metrics"]["serve_batch_fill"]["value"] <= 100
    else:
        assert set(last) == LAST_KEYS
        assert set(last["metrics"]) == {"serve_p50_ms", "serve_p99_ms",
                                        "setup_s"}
        assert 0 < last["metrics"]["serve_p50_ms"]["value"] \
            <= last["metrics"]["serve_p99_ms"]["value"]


def test_same_seed_same_inputs(bench_dir):
    """Inputs and weights come from --seed alone."""
    import numpy as np

    from chipbench.harness import arrivals, bench as hbench

    wl = hbench.load_json(os.path.join(
        bench_dir, "workloads/resnet50_serve_open.json"))
    a = arrivals.schedule(wl["traffic"], 3, 2.0)
    b = arrivals.schedule(wl["traffic"], 3, 2.0)
    c = arrivals.schedule(wl["traffic"], 4, 2.0)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0][:10], c[0][:10])
    for config in ("resnet50_v1", "bert_base"):
        cfg = hbench.load_json(os.path.join(
            bench_dir, f"configs/{config}/config.json"))
        model = hbench.load_module(os.path.join(
            bench_dir, f"configs/{config}/model.py"))
        p1, p2, p3 = (model.make_params(cfg, s) for s in (3, 3, 4))
        assert all(np.array_equal(x, y) for x, y in zip(p1, p2))
        assert not np.array_equal(np.asarray(p1[0]), np.asarray(p3[0]))


# ------------------------------------------------ driven by data, shown ---

THROWAWAY_MODEL = '''
"""A throw-away configuration: two dense layers."""
import jax
import jax.numpy as jnp
import numpy as np


def build(cfg, ctx, seed):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import nn

    net = nn.HybridSequential()
    net.add(nn.Dense(cfg["hidden"], activation="relu", in_units=cfg["inputs"]),
            nn.Dense(cfg["classes"], in_units=cfg["hidden"]))
    mx.random.seed(seed)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    return net


def loss(cfg):
    from mxnet_tpu.gluon import loss as gloss

    return gloss.SoftmaxCrossEntropyLoss()


def seed_params(net, cfg, seed):
    pass   # the comparison takes the weights as the window left them


def export_params(net, cfg):
    return [np.asarray(p.data().asnumpy(), np.float32)
            for p in net.collect_params().values()]


def make_batch(cfg, traffic, key):
    kx, ky = jax.random.split(key)
    b = int(traffic["global_batch"])
    return (jax.random.normal(kx, (b, cfg["inputs"]), jnp.float32),
            jax.random.randint(ky, (b,), 0, cfg["classes"])
            .astype(jnp.float32))


def check_inputs(cfg, seed, n):
    return np.random.default_rng(seed).standard_normal(
        (n, cfg["inputs"])).astype(np.float32)


def flops_per_sample(cfg, traffic):
    return 6 * (cfg["inputs"] * cfg["hidden"] + cfg["hidden"] * cfg["classes"])


def reference(cfg, params, batch, train=False):
    x, _ = batch
    w1, b1, w2, b2 = (jnp.asarray(p) for p in params)
    with jax.default_matmul_precision("highest"):
        return {"logits": jax.nn.relu(x @ w1.T + b1) @ w2.T + b2}
'''

THROWAWAY_MODE = '''
"""A throw-away mode: counts how often it can build a batch."""
import time

from chipbench.harness.bench import Outcome


def run(bench):
    import jax

    bench.setup_done()
    n, t_end = 0, time.perf_counter() + bench.seconds
    while time.perf_counter() < t_end:
        jax.block_until_ready(bench.model.make_batch(
            bench.cfg, bench.traffic, jax.random.PRNGKey(n)))
        n += 1
    bench.window_closed()
    return Outcome(correct=True, attempted=n, failed=0,
                   end_to_end={"batches_per_s": n / bench.seconds},
                   run={"mode": "throwaway_mode", "built": n}, notes={})
'''

THROWAWAY_METRIC = '''
"""A throw-away per-layer metric."""
LAYER = "throwaway"
MOVES = "batches_per_s"
UNIT = "count"


def applies(run):
    return run["mode"] == "throwaway_mode"


def compute(run):
    return run["built"]
'''


def _tree_digest(root):
    digest = {}
    for folder, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                digest[os.path.relpath(path, root)] = \
                    hashlib.sha1(f.read()).hexdigest()
    return digest


def test_new_cells_need_new_files_only(tmp_path, lifted, capsys):
    """A configuration, a mode, two workloads and a per-layer metric
    added as NEW files to a copy of the benchmark run through the
    unchanged harness; no file that was there is touched."""
    bench_dir = toy.toy_copy(str(tmp_path / "chipbench"))
    before = _tree_digest(bench_dir)

    def add(rel, text):
        path = os.path.join(bench_dir, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            f.write(textwrap.dedent(text).lstrip())

    add("configs/throwaway/model.py", THROWAWAY_MODEL)
    add("configs/throwaway/config.json", json.dumps({
        "inputs": 12, "hidden": 16, "classes": 5, "dtype": "float32",
        "job": {"optimizer": "sgd",
                "optimizer_params": {"learning_rate": 0.05}},
        "check": {"samples": 4, "tolerance": 1e-3, "reason": "float32"}}))
    add("modes/throwaway_mode.py", THROWAWAY_MODE)
    add("layer_metrics/throwaway_built.py", THROWAWAY_METRIC)
    traffic = {"kind": "train", "global_batch": 8, "mesh": {"dp": 1},
               "pool_batches": 2, "warmup_steps": 2, "trace_steps": 2,
               "trainer_options": {}}
    add("workloads/throwaway_train.json", json.dumps({
        "config": "throwaway", "mode": "train", "chips": 1,
        "traffic": traffic,
        "end_to_end": {"train_samples_per_s": "samples/s"}}))
    add("workloads/throwaway_count.json", json.dumps({
        "config": "throwaway", "mode": "throwaway_mode", "chips": 1,
        "traffic": traffic, "end_to_end": {"batches_per_s": "1/s"}}))

    rc, last, lines = toy.run_cell(bench_dir, "throwaway_train", 0, capsys,
                                   seconds=0.5)
    assert rc == 0 and last["correct"] is True, lines
    assert last["metrics"]["train_samples_per_s"]["value"] > 0

    rc, last, _ = toy.run_cell(bench_dir, "throwaway_count", 0, capsys,
                               seconds=0.3)
    assert rc == 0 and set(last["metrics"]) == {"batches_per_s", "setup_s"}
    assert last["metrics"]["batches_per_s"]["unit"] == "1/s"

    rc, last, _ = toy.run_cell(bench_dir, "throwaway_count", 1, capsys,
                               seconds=0.3)
    assert last["metrics"]["throwaway_built"] == {
        "value": float(last["attempted"]), "unit": "count"}
    # the readers of the other modes did not apply
    assert set(last["metrics"]) == {"throwaway_built", "compiles_in_window",
                                    "setup_compile_s"}

    after = _tree_digest(bench_dir)
    assert {k: after[k] for k in before} == before
