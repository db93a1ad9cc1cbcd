"""``BENCHMARK.json`` against the files it names, and the small pieces of
the yardstick (arrivals, percentiles, peaks) against values worked out by
hand."""
import json
import os
import re

import numpy as np
import pytest

import chipbench_toy as toy

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    path = os.path.join(toy.REPO, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        return json.load(f)


def _reader(name):
    from chipbench.harness import bench as hbench

    return hbench.load_module(os.path.join(
        toy.BENCH, "layer_metrics", f"{name}.py"))


def test_top_level_shape(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"] == ["python3", "chipbench/run.py"]
    assert spec["paths"][0] == "chipbench"
    assert isinstance(spec["run_seconds"], int) \
        and 1 <= spec["run_seconds"] <= 51
    # the full check must fit 43,200 s with all 24 cells a later PR may add
    runs = 2 + 14 * 24
    assert runs * (spec["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in spec[k]]
    assert all(NAME.match(n) for n in names)
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        ns = [e["name"] for e in spec[kind]]
        assert len(ns) == len(set(ns))
    for e in spec["configs"] + spec["workloads"]:
        assert len(e["why"]) <= 200, e["name"]


def test_configs_point_at_their_files(spec):
    used = {w["config"] for w in spec["workloads"]}
    for c in spec["configs"]:
        assert c["name"] in used
        assert c["file"].startswith(tuple(p + "/" for p in spec["paths"]))
        with open(os.path.join(toy.REPO, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert set(cfg) >= {"assumed", "deployment", "dtype", "job", "check"}
        assert os.path.isfile(os.path.join(
            toy.REPO, os.path.dirname(c["file"]), "model.py"))
    files = [c["file"] for c in spec["configs"]]
    assert len(files) == len(set(files))


def test_cells_match_their_workload_files(spec):
    cells = spec["workloads"]
    assert 2 <= len(cells) <= 24
    four = [c for c in cells if c["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    pairs = [(c["config"], c["traffic"]) for c in cells]
    assert len(pairs) == len(set(pairs))
    for c in cells:
        with open(os.path.join(toy.BENCH, "workloads",
                               f"{c['name']}.json")) as f:
            wl = json.load(f)
        assert wl["config"] == c["config"] and wl["chips"] == c["chips"]
        assert c["chips"] in (1, 4)
        assert os.path.isfile(os.path.join(toy.BENCH, "modes",
                                           f"{wl['mode']}.py"))
        mesh = wl["traffic"].get("mesh")
        if mesh is not None:
            assert int(np.prod(list(mesh.values()))) == c["chips"]
        # the cell reports setup_s, its own end-to-end metrics, and no other
        declared = {m["name"]: m["unit"] for m in spec["end_to_end"]
                    if c["name"] in m.get("workloads", [c["name"]])}
        assert declared.pop("setup_s") == "s"
        assert declared == wl["end_to_end"] and declared


def test_end_to_end_metrics(spec):
    metrics = {m["name"]: m for m in spec["end_to_end"]}
    assert 1 <= len(metrics) <= 16
    assert metrics["setup_s"]["bound"] <= 0.1
    assert "workloads" not in metrics["setup_s"]
    cells = {c["name"] for c in spec["workloads"]}
    for m in metrics.values():
        assert 0.01 <= m["bound"] <= 0.1
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells


def test_per_layer_metrics_have_readers(spec):
    cells = {c["name"]: c for c in spec["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in spec["end_to_end"]}
    assert 1 <= len(spec["per_layer"]) <= 128
    listed = {m["name"] for m in spec["per_layer"]}
    on_disk = {f[:-3] for f in os.listdir(os.path.join(
        toy.BENCH, "layer_metrics")) if f.endswith(".py")}
    assert listed <= on_disk
    modes = {}
    for name in cells:
        with open(os.path.join(toy.BENCH, "workloads",
                               f"{name}.json")) as f:
            wl = json.load(f)
        modes[name] = {"mode": wl["mode"], "chips": wl["chips"]}
    # a reader that is not listed (it waits for its cell: the serving
    # readers, PERF.md section 7) applies to no cell that is
    for name in on_disk - listed:
        assert not any(_reader(name).applies(run)
                       for run in modes.values()), name
    for m in spec["per_layer"]:
        reader = _reader(m["name"])
        assert (reader.LAYER, reader.MOVES, reader.UNIT) == \
            (m["layer"], m["moves"], m["unit"]), m["name"]
        assert m["source"] in SOURCES
        assert "bound" not in m
        where = set(m.get("workloads", cells))
        # reported only where the metric it moves is
        assert where <= e2e[m["moves"]], m["name"]
        for name in where:
            assert reader.applies(modes[name]), (m["name"], name)
    for c in cells:
        assert any(c in m.get("workloads", cells)
                   for m in spec["per_layer"])


def test_host_cpus_confines_a_process_to_its_first_cpus():
    """In a child: a run of the benchmark is a process of its own."""
    import subprocess
    import sys

    code = ("import os, sys; sys.path.insert(0, sys.argv[1]); "
            "from chipbench.harness import device; "
            "before = sorted(os.sched_getaffinity(0)); "
            "device.pin_host_cpus(None); "
            "assert sorted(os.sched_getaffinity(0)) == before; "
            "device.pin_host_cpus(1); "
            "assert sorted(os.sched_getaffinity(0)) == before[:1]; "
            "print('ok')")
    out = subprocess.run([sys.executable, "-c", code, toy.REPO],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


# ------------------------------------------------------------ arrivals ---

def test_poisson_schedule_has_the_rate_and_the_row_mix():
    from chipbench.harness import arrivals

    traffic = {"rate_per_s": 500.0, "interarrival_cv": 1.0, "pool": 64,
               "rows": {"1": 0.70, "2": 0.15, "4": 0.10, "8": 0.05}}
    due, rows, offset = arrivals.schedule(traffic, 9, 40.0)
    assert len(due) == len(rows) == len(offset)
    assert np.all(np.diff(due) > 0) and due[0] > 0 and due[-1] < 40.0
    assert len(due) == pytest.approx(500 * 40, rel=0.03)
    gaps = np.diff(due)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, rel=0.05)
    assert rows.mean() == pytest.approx(1.8, rel=0.03)   # 0.7+0.3+0.4+0.4
    assert set(np.unique(rows)) == {1, 2, 4, 8}
    assert offset.min() >= 0 and offset.max() < 64


def test_bursty_schedule_keeps_the_mean_rate():
    from chipbench.harness import arrivals

    traffic = {"rate_per_s": 500.0, "interarrival_cv": 3.0, "pool": 8,
               "rows": {"1": 1.0}}
    due, rows, _ = arrivals.schedule(traffic, 9, 200.0)
    gaps = np.diff(due)
    assert len(due) == pytest.approx(500 * 200, rel=0.05)
    assert gaps.std() / gaps.mean() == pytest.approx(3.0, rel=0.1)
    assert set(rows) == {1}


@pytest.mark.parametrize("bad", [
    {"rate_per_s": 0.0}, {"interarrival_cv": 0.0},
    {"rows": {"1": 0.5, "2": 0.4}}])
def test_schedule_rejects_malformed_traffic(bad):
    from chipbench.harness import arrivals

    traffic = dict({"rate_per_s": 10.0, "interarrival_cv": 1.0, "pool": 4,
                    "rows": {"1": 1.0}}, **bad)
    with pytest.raises(ValueError):
        arrivals.schedule(traffic, 1, 1.0)


# --------------------------------------------------------------- stats ---

def test_percentiles_by_hand():
    from chipbench.harness import stats

    xs = list(range(1, 102))          # 1..101: rank k holds k+1
    assert stats.percentile(xs, 50) == 51
    assert stats.percentile(xs, 99) == 100
    assert stats.percentile(xs, 0) == 1 and stats.percentile(xs, 100) == 101
    assert stats.percentile([], 50) is None
    assert stats.percentile([3.14159265358979], 99) == 3.14159265358979
    assert stats.spread([10, 10, 11, 12, 14]) == pytest.approx(2 / 11)


# --------------------------------------------------------------- peaks ---

def test_peak_table_names_its_source_and_has_no_default():
    from chipbench.harness import peaks

    v5e = peaks.lookup("TPU v5 lite")
    assert v5e["bf16_tflops"] == 197.0 and v5e["hbm_gbytes_per_s"] == 819.0
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(LookupError):
        peaks.lookup("cpu")
