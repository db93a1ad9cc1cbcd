"""Kernel layer (mxnet_tpu/kernels/): registry, dispatch, numerics.

Numeric contracts asserted here (each family's ``tolerance`` field):

* int8_gemm / twobit_* are **bit-exact vs their XLA
  baseline under jit** — both sides compiled, XLA applies the same FMA
  contraction to both, so ``==`` holds elementwise. (Eager-vs-jit is NOT
  bit-exact — op-by-op eager dispatch skips contraction — so the eager
  comparisons below use a 1-ULP-scale allclose instead.)
* flash_attention / decode_attention reorder the softmax reduction
  (online/blocked), so they carry an rtol=2e-5 float32 contract.

Dispatch semantics: table winner routes, corrupt table loads empty and
falls back to untuned defaults, ``MXNET_TPU_KERNELS=0`` restores the
baseline numerics bit-exactly, Pallas-unavailable latches with one
warning, and bucket keys feed the distcheck pass-4 churn sweep.
"""
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import kernels
from mxnet_tpu.kernels import table as ktable

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FAMILIES = ("decode_attention", "flash_attention", "flash_attention_bwd",
            "grouped_matmul", "int8_gemm", "selective_scan",
            "selective_scan_bwd", "twobit_compress", "twobit_decompress")


@pytest.fixture
def kernel_cache_dir(tmp_path, monkeypatch):
    """Fresh disk cache for the dispatch table; memory-only afterwards."""
    from mxnet_tpu import compile as C

    d = str(tmp_path / "cache")
    monkeypatch.setenv("MXNET_TPU_CACHE_DIR", d)
    C.configure(cache_dir=d)
    ktable.invalidate()
    yield d
    C.configure(cache_dir=None)
    ktable.invalidate()


def _jit(fn):
    import jax

    return jax.jit(fn)


# ===================================================================== #
# registry census                                                       #
# ===================================================================== #

def test_registry_census():
    assert kernels.families() == sorted(FAMILIES)
    for fam in FAMILIES:
        e = kernels.entry(fam)
        assert callable(e.kernel) and callable(e.xla)
        assert callable(e.bucket) and callable(e.supports)
        assert e.tolerance, f"{fam}: numeric contract undocumented"
    # serving-decode families default to the kernel on TPU
    assert kernels.entry("flash_attention").default_tpu
    assert kernels.entry("decode_attention").default_tpu


# ===================================================================== #
# per-family interpret-mode numerics vs the XLA baseline                #
# ===================================================================== #

@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_vs_xla(causal):
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(1, 2, 128, 64).astype(np.float32))
               for _ in range(3))
    e = kernels.entry("flash_attention")
    out = e.kernel(q, k, v, 0.125, causal=causal, interpret=True)
    ref = e.xla(q, k, v, 0.125, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


_BERT = ((32, 12, 384, 64), 64, "bfloat16", False)       # q shape, dv
_LM = ((2, 32, 4096, 192), 128, "bfloat16", True)


@pytest.mark.parametrize("shape,family,blocks,key", [
    # the four buckets the benchmark's attention cells print (PERF.md
    # section 3): nobody names a block, the family reads the shape
    (_BERT, "flash_attention", {},
     "bh512_sq512_sk512_d64_bfloat16_c0_q384k384"),
    (_BERT, "flash_attention_bwd", {},
     "bh512_sq512_sk512_d64_bfloat16_c0_q384k384"),
    (_LM, "flash_attention", {},
     "bh64_sq4096_sk4096_d192v128_bfloat16_c1_q1024k1024"),
    (_LM, "flash_attention_bwd", {},
     "bh64_sq4096_sk4096_d192v128_bfloat16_c1_q512k512"),
    # equal widths take the shape's blocks like any other
    (((1, 8, 2048, 128), 128, "float32", True), "flash_attention", {},
     "bh8_sq2048_sk2048_d128_float32_c1_q1024k1024"),
    # a pair the caller names forces the tile and lands in the key
    (_LM, "flash_attention", {"block_q": 512, "block_k": 256},
     "bh64_sq4096_sk4096_d192v128_bfloat16_c1_q512k256"),
    (_BERT, "flash_attention", {"block_k": 128},
     "bh512_sq512_sk512_d64_bfloat16_c0_q384k128"),
], ids=["bert_fwd", "bert_bwd", "lm_fwd", "lm_bwd", "equal_widths_2048",
        "forced_pair", "forced_one"])
def test_flash_blocks_come_from_the_shape(shape, family, blocks, key):
    import jax
    import jax.numpy as jnp

    (b, h, s, d), dv, dtype, causal = shape
    q = k = jax.ShapeDtypeStruct((b, h, s, d), jnp.dtype(dtype))
    v = out = cot = jax.ShapeDtypeStruct((b, h, s, dv), jnp.dtype(dtype))
    lse = jax.ShapeDtypeStruct((b, h, s), jnp.float32)
    args = (q, k, v) if family == "flash_attention" \
        else (q, k, v, out, lse, cot)
    e = kernels.entry(family)
    assert e.supports(*args, d ** -0.5, causal=causal, **blocks)
    assert e.bucket(*args, d ** -0.5, causal=causal, **blocks) == key


def test_flash_supports_is_one_condition_forward_and_backward():
    """A shape the backward kernels cannot take is refused at the forward
    (dense XLA and autodiff), which only a forced pair under 128 can
    meet: 64 x 64 on 576 positions leaves the backward a block of 64."""
    import jax
    import jax.numpy as jnp

    e = kernels.entry("flash_attention")
    q = jax.ShapeDtypeStruct((1, 2, 576, 64), jnp.float32)
    assert not e.supports(q, q, q, 0.125, block_q=64, block_k=64)
    q = jax.ShapeDtypeStruct((1, 2, 512, 64), jnp.float32)
    assert e.supports(q, q, q, 0.125, block_q=64, block_k=64)
    q = jax.ShapeDtypeStruct((1, 2, 100, 64), jnp.float32)
    assert not e.supports(q, q, q, 0.125)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_xla_side_is_the_dense_gradient(causal):
    """The XLA side of the pair is one function forward and backward: the
    backward family's is the gradient of the forward family's."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.kernels import flash

    rng = np.random.RandomState(3)
    q, k = (jnp.asarray(rng.randn(1, 2, 128, 32), jnp.float32)
            for _ in range(2))
    v, cot = (jnp.asarray(rng.randn(1, 2, 128, 16), jnp.float32)
              for _ in range(2))
    scale = 32 ** -0.5
    out, vjp = jax.vjp(lambda a, b, c: kernels.entry("flash_attention").xla(
        a, b, c, scale, causal=causal), q, k, v)
    lse = flash.row_log_sum_exp(q, k, scale, causal)
    got = kernels.entry("flash_attention_bwd").xla(
        q, k, v, out, lse, cot, scale, causal=causal)
    for g, w in zip(got, vjp(cot)):
        assert g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # and the kernel side of the same family agrees with it
    kern = kernels.entry("flash_attention_bwd").kernel(
        q, k, v, out, lse, cot, scale, causal=causal, interpret=True)
    for g, w in zip(kern, got):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-5)


def test_decode_attention_kernel_vs_xla():
    import jax.numpy as jnp

    rng = np.random.RandomState(1)
    B, H, S, D = 2, 2, 256, 64
    q = jnp.asarray(rng.randn(B, H, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    # ragged: one row stops mid-block, exercising the position mask AND
    # the whole-block skip
    lengths = jnp.asarray([S, 100], np.int32)
    e = kernels.entry("decode_attention")
    out = e.kernel(q, k, v, lengths, 0.125, interpret=True)
    ref = e.xla(q, k, v, lengths, 0.125)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # positions past `lengths` must not leak into the output: growing
    # the padded tail must not change row 1
    k2 = k.at[1, :, 100:].set(1e4)
    out2 = e.kernel(q, k2, v, lengths, 0.125, interpret=True)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(out),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("relu,bias", [(False, False), (True, True)])
def test_int8_gemm_bit_exact_under_jit(relu, bias):
    import jax.numpy as jnp

    rng = np.random.RandomState(4)
    qx = jnp.asarray(rng.randint(-127, 128, (48, 96)).astype(np.int8))
    w = jnp.asarray(rng.randint(-127, 128, (64, 96)).astype(np.int8))
    scale = jnp.asarray((rng.rand(64) * 0.01 + 1e-4).astype(np.float32))
    b = jnp.asarray(rng.randn(64).astype(np.float32)) if bias else None
    e = kernels.entry("int8_gemm")
    kfn = _jit(lambda *a: e.kernel(*a, bias=b, relu=relu, interpret=True))
    xfn = _jit(lambda *a: e.xla(*a, bias=b, relu=relu))
    out_k = kfn(qx, w, scale)
    out_x = xfn(qx, w, scale)
    assert out_k.shape == (48, 64)
    # the XLA baseline IS the quantization.py fused-op math — bit
    # equality here is the int8-GEMM-vs-fused-ops exactness contract
    assert np.array_equal(np.asarray(out_k), np.asarray(out_x))
    if relu:
        assert float(np.asarray(out_k).min()) >= 0.0


def test_int8_gemm_matches_quantized_fc_op():
    """End to end through the _contrib_quantized_fully_connected op (the
    registry consumer): same answer with kernels enabled and disabled."""
    rng = np.random.RandomState(5)
    x = rng.randn(4, 16).astype(np.float32)
    w = (rng.randn(8, 16) * 0.1).astype(np.float32)
    b = rng.randn(8).astype(np.float32)
    absmax = np.abs(w).max(axis=1)
    scale = (absmax / 127.0).astype(np.float32)
    qw = np.clip(np.round(w / scale[:, None]), -127, 127).astype(np.int8)

    def run():
        return mx.nd.invoke(
            "_contrib_quantized_fully_connected", mx.nd.array(x),
            mx.nd.array(qw, dtype="int8"), mx.nd.array(scale),
            mx.nd.array(b), num_hidden=8, min_calib_range=float(x.min()),
            max_calib_range=float(x.max())).asnumpy()

    base = run()
    os.environ["MXNET_TPU_KERNELS"] = "0"
    try:
        off = run()
    finally:
        os.environ.pop("MXNET_TPU_KERNELS", None)
    assert np.array_equal(base, off)
    rel = np.abs(base - (x @ w.T + b)).max() / np.abs(x @ w.T + b).max()
    assert rel < 0.05


def test_twobit_bit_exact_under_jit():
    import jax.numpy as jnp

    rng = np.random.RandomState(6)
    g = jnp.asarray(rng.randn(4096).astype(np.float32))
    res = jnp.asarray(rng.randn(4096).astype(np.float32) * 0.1)
    ce = kernels.entry("twobit_compress")
    de = kernels.entry("twobit_decompress")
    # thr is a STATIC hyperparameter (baked into the kernel body), so it
    # must be closed over, not traced through jit
    ckfn = _jit(lambda a, b: ce.kernel(a, b, 0.5, interpret=True))
    cxfn = _jit(lambda a, b: ce.xla(a, b, 0.5))
    codes_k, res_k = ckfn(g, res)
    codes_x, res_x = cxfn(g, res)
    assert codes_k.dtype == np.int8
    assert np.array_equal(np.asarray(codes_k), np.asarray(codes_x))
    assert np.array_equal(np.asarray(res_k), np.asarray(res_x))
    assert set(np.unique(np.asarray(codes_k))) <= {-1, 0, 1}
    dk = _jit(lambda c: de.kernel(c, 0.5, interpret=True))(codes_k)
    dx = _jit(lambda c: de.xla(c, 0.5))(codes_x)
    assert np.array_equal(np.asarray(dk), np.asarray(dx))


# ===================================================================== #
# dispatch routing                                                      #
# ===================================================================== #

def _flash_args():
    import jax.numpy as jnp

    rng = np.random.RandomState(7)
    q, k, v = (jnp.asarray(rng.randn(1, 2, 128, 64).astype(np.float32))
               for _ in range(3))
    return q, k, v, 0.125


def test_dispatch_env_disabled_restores_baseline_bitexact(monkeypatch):
    q, k, v, scale = _flash_args()
    e = kernels.entry("flash_attention")
    monkeypatch.setenv("MXNET_TPU_KERNELS", "0")
    assert not kernels.enabled()
    assert kernels.choice_for("flash_attention", q, k, v, scale) \
        == ("xla", "env_disabled")
    out = kernels.dispatch("flash_attention", q, k, v, scale)
    # the opt-out IS the baseline: same callable, bit-identical result
    assert np.array_equal(np.asarray(out), np.asarray(e.xla(q, k, v, scale)))


def test_dispatch_untuned_default_and_interpret_forced():
    q, k, v, scale = _flash_args()
    choice, reason = kernels.choice_for("flash_attention", q, k, v, scale)
    if kernels.on_tpu():  # pragma: no cover - CPU CI
        assert (choice, reason) == ("kernel", "untuned_default_tpu")
    else:
        assert (choice, reason) == ("xla", "untuned_default")
    kernels.reset_stats()
    out = kernels.dispatch("flash_attention", q, k, v, scale,
                           interpret=True)
    assert out.shape == q.shape
    st = kernels.dispatch_stats()["flash_attention"]
    assert st["kernel"] == 1
    assert st["reasons"] == {"interpret_forced": 1}


def test_dispatch_unsupported_shape_falls_back():
    import jax.numpy as jnp

    rng = np.random.RandomState(8)
    q, k, v = (jnp.asarray(rng.randn(1, 2, 100, 64).astype(np.float32))
               for _ in range(3))  # 100 % 128 != 0
    assert kernels.choice_for("flash_attention", q, k, v, 0.125) \
        == ("xla", "unsupported_shape")
    out = kernels.dispatch("flash_attention", q, k, v, 0.125,
                           interpret=True)  # still safe: routes to XLA
    assert out.shape == q.shape


def test_dispatch_tuned_table_routes(kernel_cache_dir):
    q, k, v, scale = _flash_args()
    e = kernels.entry("flash_attention")
    bucket = e.bucket(q, k, v, scale)
    ktable.record("flash_attention", bucket, "kernel", 1.0, 2.0)
    assert ktable.save()
    ktable.invalidate()
    assert kernels.choice_for("flash_attention", q, k, v, scale) \
        == ("kernel", "tuned")
    ktable.record("flash_attention", bucket, "xla", 2.0, 1.0)
    assert kernels.choice_for("flash_attention", q, k, v, scale) \
        == ("xla", "tuned")


def test_dispatch_table_corrupt_entry_falls_back(kernel_cache_dir):
    from mxnet_tpu.telemetry import registry as treg

    q, k, v, scale = _flash_args()
    e = kernels.entry("flash_attention")
    bucket = e.bucket(q, k, v, scale)
    ktable.record("flash_attention", bucket, "kernel", 1.0, 2.0)
    path = ktable.save()
    # torn write: flip bytes INSIDE the entries payload so json still
    # parses but the CRC no longer matches
    with open(path, "r", encoding="utf-8") as f:
        raw = f.read()
    with open(path, "w", encoding="utf-8") as f:
        f.write(raw.replace('"winner": "kernel"', '"winner": "xlaaaa"'))
    m = treg.get("mxtpu_kernels_table_corrupt_total")
    before = sum(m.series().values()) if m is not None else 0
    ktable.invalidate()
    t = ktable.load()
    assert t["entries"] == {}  # corrupt loads EMPTY, never raises
    assert ktable.census()["corrupt_seen"]
    assert "CRC" in ktable.census()["corrupt_seen"]
    m = treg.get("mxtpu_kernels_table_corrupt_total")
    assert sum(m.series().values()) == before + 1
    # dispatch falls back to the untuned default, and still answers
    assert kernels.choice_for("flash_attention", q, k, v, scale)[1] \
        in ("untuned_default", "untuned_default_tpu")
    out = kernels.dispatch("flash_attention", q, k, v, scale)
    assert out.shape == q.shape
    # unparseable garbage loads empty too
    with open(path, "wb") as f:
        f.write(b"\x00garbage\xff")
    ktable.invalidate()
    assert ktable.load()["entries"] == {}


def test_pallas_unavailable_latches_once(monkeypatch, caplog):
    import logging

    q, k, v, scale = _flash_args()
    monkeypatch.setattr(kernels, "pallas_available", lambda: False)
    kernels._warned_families.discard("flash_attention")
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.kernels"):
        for _ in range(3):
            out = kernels.dispatch("flash_attention", q, k, v, scale)
    assert out.shape == q.shape
    warns = [r for r in caplog.records if "Pallas unavailable" in r.message]
    assert len(warns) == 1  # latched: one warning, not one per call
    assert "flash_attention" in kernels.fallback_report()["warned_families"]


def test_token_salt_tracks_dispatch_state(monkeypatch, kernel_cache_dir):
    ktable.invalidate()
    base = kernels.token_salt()
    monkeypatch.setenv("MXNET_TPU_KERNELS", "0")
    assert kernels.token_salt() != base  # flipped gate -> new executable
    monkeypatch.delenv("MXNET_TPU_KERNELS")
    assert kernels.token_salt() == base
    q, k, v, scale = _flash_args()
    e = kernels.entry("flash_attention")
    ktable.record("flash_attention", e.bucket(q, k, v, scale), "kernel",
                  1.0, 2.0)
    assert kernels.token_salt() != base  # retuned table -> new identity


# ===================================================================== #
# distcheck pass 4 — dispatch keys must not churn                       #
# ===================================================================== #

def test_dispatch_keys_no_churn():
    from mxnet_tpu.analysis import distcheck

    q, k, v, scale = _flash_args()
    distcheck.reset_cache_stats()
    kernels.reset_stats()
    for _ in range(6):
        kernels.choice_for("flash_attention", q, k, v, scale)
    stats = distcheck.cache_stats()
    site = stats.get(("dispatch", "kernels.flash_attention"))
    assert site is not None, stats
    # a pure bucketing function: ONE legitimate miss, then hits
    assert site["misses"] == 1 and site["hits"] == 5
    assert not [i for i in distcheck.check_churn()
                if "kernels.flash_attention" in i.node]
    distcheck.reset_cache_stats()


# ===================================================================== #
# autotuner — opperf --kernels writes the persisted table               #
# ===================================================================== #

def test_opperf_kernels_writes_table(kernel_cache_dir):
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import opperf

    res = opperf.bench_kernels(runs=2, warmup=1,
                               families=["twobit_compress",
                                         "twobit_decompress"])
    assert res["table_path"] and os.path.exists(res["table_path"])
    assert len(res["results"]) == 2
    for r in res["results"]:
        assert r["winner"] in ("kernel", "xla")
        if not kernels.on_tpu():
            assert r["interpret"] is True  # honest off-TPU stamp
    ktable.invalidate()  # force the disk round-trip (CRC verifies)
    t = ktable.load()
    assert len(t["entries"]) == 2
    assert t["opperf"]["runs"] == 2
    assert ktable.census()["corrupt_seen"] is None \
        or "CRC" not in ktable.census()["corrupt_seen"]
    # the measured winner now routes dispatch for that exact bucket
    import jax.numpy as jnp

    rng = np.random.RandomState(9)
    g = jnp.asarray(rng.randn(65536).astype(np.float32))
    r0 = jnp.zeros_like(g)
    choice, reason = kernels.choice_for("twobit_compress", g, r0, 0.5)
    assert reason == "tuned"
    key = "twobit_compress|" + \
        kernels.entry("twobit_compress").bucket(g, r0, 0.5)
    assert choice == t["entries"][key]["winner"]


def test_opperf_flash_sweep_marks_the_tile_the_shape_picks():
    """``opperf.py --flash-sweep``: one row a tile with its blocks and
    heads a program, the shape's own marked, a row for dense XLA last;
    off the TPU the interpreter's clock and no device time."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import opperf

    # the real sweep holds both attention cells' buckets at the tile
    # the kernel picks for them
    from mxnet_tpu.kernels import flash
    for label, (b, h, s, d, dv), _causal, tiles in opperf._FLASH_SWEEP:
        bq, bk = flash.default_blocks(s, s, d, dv)
        assert (bq, bk, flash.heads_a_program(b * h, s, s, bq, bk)) \
            in tiles, label
    rows = opperf.sweep_flash_forward(
        runs=1, warmup=1, dtype="float32",
        cases=[("toy", (1, 4, 256, 32, 16), True,
                [(128, 128, 1), (256, 256, 1), (256, 256, 4)])])
    assert [r["blocks"] for r in rows] \
        == [[128, 128], [256, 256], [256, 256], "dense XLA"]
    assert [r["chosen"] for r in rows] == [False, False, True, False]
    assert all(r["wall_ms"] > 0 and r["device_ms"] is None
               and r["interpret"] for r in rows)


def test_opperf_flash_window_sweep_marks_the_tile_the_band_picks():
    """The windowed, grouped rows of ``--flash-sweep``: forward and fused
    backward at every tile, the one ``flash._inside_band`` picks marked;
    the real sweep holds the hybrid decoder's bucket at that tile."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import opperf

    from mxnet_tpu.kernels import flash
    for label, (_b, _h, _hk, s, d, dv), window, tiles in \
            opperf._FLASH_WINDOW_SWEEP:
        for blocks in (flash.default_blocks(s, s, d, dv, 2),
                       flash.backward_blocks(s, s, d, dv)):
            assert flash._inside_band(blocks, (s, s), window) in tiles, label
    rows = opperf.sweep_flash_window(
        runs=1, warmup=1,
        cases=[("toy", (1, 4, 2, 512, 64, 128), 128,
                [(128, 128), (256, 256)])])
    assert [(r["side"], r["blocks"]) for r in rows] == [
        ("forward", [128, 128]), ("backward", [128, 128]),
        ("forward", [256, 256]), ("backward", [256, 256])]
    assert [r["chosen"] for r in rows] == [True, True, False, False]
    assert all(r["wall_ms"] > 0 and r["device_ms"] is None
               and r["interpret"] and r["window"] == 128 for r in rows)


def test_opperf_kernels_has_a_row_for_the_attention_backward(
        kernel_cache_dir):
    """The backward is a family of its own: the autotuner times its
    kernels against the dense gradient and the table holds the row under the
    backward's bucket (its own blocks), which then routes its dispatch
    while the forward's stays untuned."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import opperf

    res = opperf.bench_kernels(runs=1, warmup=1,
                               families=["flash_attention_bwd"])
    (row,) = res["results"]
    assert row["family"] == "flash_attention_bwd"
    assert row["bucket"] == "bh2_sq128_sk128_d64_float32_c1_q128k128"
    assert row["kernel_ms"] > 0 and row["xla_ms"] > 0
    args, kw = dict(opperf._kernel_cases())["flash_attention_bwd"]()
    assert kernels.choice_for("flash_attention_bwd", *args, **kw) \
        == (row["winner"], "tuned")
    assert kernels.choice_for("flash_attention", *args[:3], **kw)[1] \
        == "untuned_default"
    # and a table that changed is another executable identity
    salt = kernels.token_salt()
    ktable.record("flash_attention_bwd", row["bucket"],
                  "xla" if row["winner"] == "kernel" else "kernel", 1.0, 2.0)
    assert kernels.token_salt() != salt


# ===================================================================== #
# trainer integration — the MXNET_TPU_KERNELS=0 opt-out                 #
# ===================================================================== #

@pytest.mark.slow
def test_trainer_parity_kernels_on_vs_off():
    """Three ShardedTrainer steps land on identical weights with the
    kernel layer enabled and with MXNET_TPU_KERNELS=0 — the end-to-end
    numerics-parity opt-out contract."""
    from mxnet_tpu.gluon import loss as gloss, nn
    from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer

    def run():
        mx.random.seed(0)
        net = nn.Dense(4)
        net.initialize(mx.init.Xavier())
        x = mx.nd.array(np.random.RandomState(0).randn(8, 6)
                        .astype(np.float32))
        y = mx.nd.array(np.random.RandomState(1).randint(0, 4, 8)
                        .astype(np.float32))
        net(x)  # materialize deferred shapes
        tr = ShardedTrainer(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9},
                            mesh=DeviceMesh({"dp": 1}), nan_guard=False)
        for _ in range(3):
            tr.step(x, y).wait_to_read()
        return {k: v.data().asnumpy() for k, v in
                net.collect_params().items()}

    base = run()
    os.environ["MXNET_TPU_KERNELS"] = "0"
    try:
        off = run()
    finally:
        os.environ.pop("MXNET_TPU_KERNELS", None)
    # gluon's global name counter differs between runs (dense0 vs
    # dense1) — compare positionally on the sorted suffix
    def vals(d):
        return [d[k] for k in sorted(d, key=lambda n: n.split("_", 1)[-1])]

    assert len(base) == len(off)
    for a, b in zip(vals(base), vals(off)):
        assert np.array_equal(a, b)
