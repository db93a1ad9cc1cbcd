"""chip_smoke.py (the one-command proof that the system starts on the
chip): the refusal without a TPU, and its phase functions driven at toy
sizes on the CPU mesh — kernels in interpret mode here, and only here."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY = {
    "train": {"model": "resnet18_v1", "batch": 4, "image": 32,
              "classes": 10, "steps": 2, "dtype": "bfloat16"},
    "serve": {"rows": (1, 3, 2)},
    "kernels": {"attn": (1, 2, 256, 64), "attn_whole": (1, 2, 384, 64),
                "decode": (2, 2, 256, 64),
                "opt": (40, 130), "gemm": (64, 128, 256),
                "scan": (1, 100, 128, 8)},
    "encoder": {"units": 64, "heads": 2, "hidden": 128, "seq": 128,
                "batch": 2, "dtype": "bfloat16"},
}


def test_refuses_without_a_tpu():
    """No accelerator: non-zero exit, one plain line naming the missing
    TPU, no phase run and no result printed."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO,
                                                       "chip_smoke.py")],
                         capture_output=True, text=True, timeout=120,
                         env=env)
    assert out.returncode != 0
    assert out.stdout == ""
    lines = [ln for ln in out.stderr.splitlines() if "chip_smoke" in ln]
    assert len(lines) == 1 and "no TPU" in lines[0], out.stderr[-800:]


def test_last_line_has_exactly_the_contract_keys():
    """The driver parses the last stdout line: ``ok`` and ``device`` and
    nothing else, whatever the summary above it carries."""
    import json

    summary = dict(chip_smoke.device_info(), ok=True,
                   phases={"train": {"ok": True}}, wall_s=1.0)
    line = chip_smoke.result_line(summary)
    assert "\n" not in line
    doc = json.loads(line)
    assert list(doc) == ["ok", "device"] and doc["ok"] is True
    assert set(doc["device"]) == {"platform", "kind", "count"}
    assert isinstance(doc["device"]["platform"], str)
    assert isinstance(doc["device"]["kind"], str)
    assert type(doc["device"]["count"]) is int
    assert json.loads(chip_smoke.result_line(
        dict(summary, ok=False)))["ok"] is False


def test_train_then_serve_phases_at_toy_size():
    rep, net = chip_smoke.phase_train(**TOY["train"])
    assert rep["skipped_steps"] == 0 and rep["devices"] == 1
    assert rep["steps"] == 2
    # the same (by then hybridized) network behind HTTP: answers equal
    # net(x), and nothing compiles after warm-up — neither a service miss
    # nor a jit retrace behind the service's back
    srv = chip_smoke.phase_serve(net, image=32, dtype="bfloat16",
                                 **TOY["serve"])
    assert srv["requests"] == 3 and srv["rows"] == 6
    assert srv["recompiles_during_run"] == 0
    assert srv["buckets"] == [2, 4, 8, 16, 32]  # the default ladder
    assert srv["max_rel_err"] <= 2 ** -5


def test_kernel_phase_covers_every_family_in_interpret_mode():
    from mxnet_tpu import kernels

    rep = chip_smoke.phase_kernels(interpret=True, **TOY["kernels"])
    assert rep["interpret"] is True
    assert {k.split("/")[0] for k in rep["families"]} \
        == set(kernels.families())
    assert all(r["ok"] for r in rep["families"].values())
    # BERT's bucket (one block a head) in both dtypes, bf16 at the bound
    # the forward registers against the float32 dense softmax
    assert rep["families"]["flash_attention/bfloat16/s384_whole"][
        "tolerance"] == 1e-2
    assert rep["families"]["flash_attention/float32/s384_whole"][
        "tolerance"] == 1e-4
    # forced onto the kernel, never the XLA baseline
    assert all(n >= 1 for n in rep["dispatched_kernel"].values())


def test_kernel_phase_reports_a_family_out_of_tolerance(monkeypatch):
    from mxnet_tpu import kernels

    e = kernels.entry("twobit_decompress")
    monkeypatch.setattr(e, "xla", lambda codes, thr, **kw:
                        codes.astype("float32") * thr + 1.0)
    with pytest.raises(AssertionError, match="twobit_decompress"):
        chip_smoke.phase_kernels(interpret=True, **TOY["kernels"])


def test_encoder_and_data_parallel_phases_at_toy_size():
    enc = chip_smoke.phase_encoder(**TOY["encoder"])
    # off-TPU the untuned dispatch routes attention to the XLA baseline
    assert enc["flash_dispatch"]["xla"] >= 1
    dp, _ = chip_smoke.phase_train(dp=2, **dict(TOY["train"], steps=1))
    assert dp["devices"] == 2 and dp["batch"] == 8


def test_dispatch_interpret_false_is_the_compiled_kernel_not_a_fallback():
    """interpret=False forces the Mosaic-compiled kernel: on the CPU
    backend that is an error, not a quiet trip through the interpreter
    or the XLA baseline."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import kernels

    codes = jnp.ones((256, 128), jnp.int8)
    assert kernels.choice_for("twobit_decompress", codes, 0.5) \
        == ("xla", "untuned_default")
    with pytest.raises(Exception, match="(?i)interpret|tpu|mosaic"):
        jax.block_until_ready(kernels.dispatch(
            "twobit_decompress", codes, 0.5, interpret=False))
