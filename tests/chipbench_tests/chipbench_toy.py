"""Shared by the chipbench tests: a copy of ``chipbench/`` cut to toy widths,
and the one place where the refusal to run without a TPU is lifted (by the
test, never by an option of the benchmark)."""
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "chipbench")

TOY_RESNET = {"layers": [1, 1, 1, 1], "channels": [8, 16, 32, 64, 128],
              "classes": 10, "image_size": 32}
TOY_BERT = {"hidden_size": 32, "intermediate_size": 64,
            "num_attention_heads": 4, "num_hidden_layers": 2,
            "vocab_size": 100, "max_position_embeddings": 64}


def edit_json(path, **changes):
    with open(path) as f:
        data = json.load(f)
    for key, value in changes.items():
        target = data
        *parents, leaf = key.split(".")
        for p in parents:
            target = target[p]
        target[leaf] = value
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


def toy_copy(dst):
    """``chipbench/`` copied to ``dst`` with both configurations and all
    four cells cut to sizes the CPU runs in seconds."""
    shutil.copytree(BENCH, dst,
                    ignore=shutil.ignore_patterns("__pycache__"))
    edit_json(os.path.join(dst, "configs/resnet50_v1/config.json"),
              **TOY_RESNET)
    # the published 3e-5 moves a toy network too slowly for a one-second
    # window to show the loss falling
    edit_json(os.path.join(dst, "configs/bert_base/config.json"),
              **TOY_BERT, **{"job.max_seq_length": 32,
                             "job.optimizer_params.learning_rate": 1e-3})
    small = {"traffic.pool_batches": 2, "traffic.warmup_steps": 2,
             "traffic.trace_steps": 3}
    # host_cpus would confine the test's own process: a run of the
    # benchmark is a process of its own, a test is not
    edit_json(os.path.join(dst, "workloads/resnet50_train_1chip.json"),
              **small, **{"traffic.global_batch": 8, "host_cpus": None})
    edit_json(os.path.join(dst, "workloads/resnet50_train_dp4.json"),
              **small, **{"traffic.global_batch": 16})
    edit_json(os.path.join(dst, "workloads/bert_base_train_s384.json"),
              **small, **{"traffic.global_batch": 4, "traffic.seq_len": 32})
    edit_json(os.path.join(dst, "workloads/resnet50_serve_open.json"),
              **{"traffic.rate_per_s": 100.0, "traffic.trace_seconds": 0.4})
    return dst


def lift_refusal(monkeypatch):
    """Let a run proceed on the CPU mesh of ``tests/conftest.py``: the
    TPU check passes, and the CPU borrows the v5e's row of the peak
    table so that ``mfu`` has a denominator. Restores the two jax cache
    thresholds ``run.main`` sets."""
    import jax

    from chipbench.harness import device, peaks

    monkeypatch.setattr(device, "require_tpu", lambda chips: jax.devices())
    v5e = peaks.table()["TPU v5 lite"]
    monkeypatch.setattr(peaks, "lookup", lambda kind: v5e)
    saved = (jax.config.jax_persistent_cache_min_compile_time_secs,
             jax.config.jax_persistent_cache_min_entry_size_bytes)

    def restore():
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          saved[0])
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          saved[1])

    return restore


def run_cell(bench_dir, workload, trace, capsys, seconds=1.0, seed=7):
    """One run through the real ``run.main``; returns (exit code, the
    parsed last line, every line of stdout)."""
    import time

    from chipbench import run as cbrun

    capsys.readouterr()
    rc = cbrun.main(["--workload", workload, "--seed", str(seed),
                     "--seconds", str(seconds), "--trace", str(trace)],
                    bench_dir=bench_dir, t0=time.perf_counter())
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.strip()]
    return rc, json.loads(lines[-1]), lines
