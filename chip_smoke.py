#!/usr/bin/env python
"""chip_smoke.py — the quickest proof the system still starts on the chip.

One process, one command, no arguments: ``python chip_smoke.py``. It

1. reads ``jax.devices()`` and exits non-zero with one line unless the
   platform is ``tpu`` (nothing else runs, nothing is printed as a result);
2. trains ResNet-50 (1000 classes, 224x224, batch 128, bfloat16) through
   ``parallel.ShardedTrainer`` with its DEFAULT options for a few steps;
3. serves the same network through ``serving.ModelContainer.add_block`` ->
   ``ModelServer.start()/.warmup()`` -> ``HttpFrontEnd(port=0)`` and answers
   a handful of real HTTP POSTs, compared with a direct ``net(x)``;
4. runs every registered Pallas kernel family through ``kernels.dispatch``
   forced onto the Mosaic-compiled kernel against its XLA baseline;
5. takes one ``TransformerEncoderCell`` step at BERT-base width, so flash
   attention's Pallas forward and its backward kernels compile inside a
   real program;
6. with more than one chip, repeats the ResNet-50 step data-parallel over
   all of them.

Each phase is a function of its sizes; ``tests/test_chip_smoke.py`` drives
the same functions at toy sizes on the CPU mesh (kernels in interpret mode
there, and only there). The refusal lives in :func:`main`.

Output: the device and versions, one line per phase (``ok``, ``compile_s``
= wall time of the part that compiles, ``run_s`` = wall time of the steady
part, ``xla_compiles``/``xla_compile_s`` = jax's own backend-compile
events inside the phase), ``compile.totals()``, a ``summary:`` line that
holds all of it as one JSON document, and as the LAST line one JSON object
with exactly two keys,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
A phase that fails is reported with its error and makes the exit code 1.

The compile cache is ``JAX_COMPILATION_CACHE_DIR`` where that is set, else
the fixed ``.mxtpu_cache`` next to this file (``mxnet_tpu.compile``): a
second call over the same directory compiles next to nothing.
"""
import json
import sys
import time
import traceback
import urllib.request

import numpy as np

# the real sizes (ISSUE 21): the flagship model at full width
REAL = {
    "train": {"model": "resnet50_v1", "batch": 128, "image": 224,
              "classes": 1000, "steps": 5, "dtype": "bfloat16"},
    # a handful of requests of 1-3 rows each
    "serve": {"rows": (1, 3, 2, 1, 3)},
    "kernels": {"attn": (2, 12, 1024, 64),        # (B, H, S, D)
                "attn_whole": (32, 12, 384, 64),  # BERT's bucket: one block
                "decode": (8, 12, 2048, 64),      # (B, H, S_max, D)
                "opt": (512, 512, 3, 3),          # ResNet-50's largest conv
                "gemm": (512, 1024, 1024),        # (M, N, K)
                "scan": (1, 1000, 1024, 16)},     # (B, S, channels, states)
    "encoder": {"units": 768, "heads": 12, "hidden": 3072, "seq": 512,
                "batch": 8, "dtype": "bfloat16"},
}

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_xla = {"n": 0, "s": 0.0, "armed": False}


def _arm_compile_listener():
    """Count jax's own backend-compile events (persistent-cache loads
    included): the ground truth under ``compile.stats()``, which cannot
    see a retrace inside an executable it already holds."""
    if _xla["armed"]:
        return
    import jax

    def on_event(event, duration, **_):
        if event == _BACKEND_COMPILE:
            _xla["n"] += 1
            _xla["s"] += duration

    jax.monitoring.register_event_duration_secs_listener(on_event)
    _xla["armed"] = True


class _Clock:
    """compile_s / run_s / xla-compile accounting for one phase."""

    def __init__(self):
        _arm_compile_listener()
        self.t0 = time.perf_counter()
        self.n0, self.s0 = _xla["n"], _xla["s"]
        self.compile_s = None

    def compiled(self):
        """Mark the end of the compiling part."""
        self.compile_s = time.perf_counter() - self.t0
        self.t1 = time.perf_counter()

    def xla_compiles(self):
        return _xla["n"] - self.n0

    def fields(self):
        return {"compile_s": round(self.compile_s, 2),
                "run_s": round(time.perf_counter() - self.t1, 2),
                "xla_compiles": _xla["n"] - self.n0,
                "xla_compile_s": round(_xla["s"] - self.s0, 2)}


def device_info():
    import jax
    import jaxlib

    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    devs = jax.devices()
    return {"device": {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)},
            "versions": {"jax": jax.__version__,
                         "jaxlib": jaxlib.__version__,
                         "libtpu": libtpu_version,
                         "python": sys.version.split()[0]}}


def _platforms(raw):
    return {d.platform for d in raw.devices()}


# ------------------------------------------------------------------ train --

def phase_train(*, model, batch, image, classes, steps, dtype, dp=1,
                seed=0):
    """`steps` ShardedTrainer steps (after the compiling one) on a seeded
    synthetic batch, every one ending in a blocking read. Returns
    (report, net) — the trained net feeds :func:`phase_serve`."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer

    platform = jax.devices()[0].platform
    ctx = mx.tpu()
    mx.random.seed(seed)
    rs = np.random.RandomState(seed)
    net = vision.get_model(model, classes=classes)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    net.cast(dtype)
    global_batch = batch * dp
    x = mx.nd.array(rs.uniform(size=(global_batch, 3, image, image))
                    .astype(np.float32), ctx=ctx).astype(dtype)
    y = mx.nd.array(rs.randint(0, classes, global_batch)
                    .astype(np.float32), ctx=ctx)
    clock = _Clock()
    net(x[0:2])  # materialize deferred shapes
    # default options on purpose (nan_guard=True): what users get
    trainer = ShardedTrainer(
        net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.01, "momentum": 0.9, "wd": 1e-4},
        mesh=DeviceMesh({"dp": dp}))
    params = net.collect_params()
    watched = [n for n in params if n.endswith("weight")]
    watched = [watched[0], watched[-1]]
    before = {n: params[n].data().asnumpy().astype(np.float32)
              for n in watched}
    losses = [float(trainer.step(x, y).asscalar())]  # compiles
    clock.compiled()
    for _ in range(steps):
        loss = trainer.step(x, y)
        losses.append(float(loss.asscalar()))  # blocking read
    fields = clock.fields()
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if trainer.skipped_steps:
        raise AssertionError(
            f"the nan guard skipped {trainer.skipped_steps} step(s) of "
            f"{steps + 1}; losses {losses}")
    for n in watched:
        after = params[n].data().asnumpy().astype(np.float32)
        if not np.abs(after - before[n]).max() > 0:
            raise AssertionError(f"parameter {n} did not change")
    where = set(_platforms(loss._data))
    for p in params.values():
        where |= _platforms(p.data()._data)
    if where != {platform}:
        raise AssertionError(
            f"loss/parameters live on {sorted(where)}, not {platform!r}")
    w0 = params[watched[0]].data()._data
    n_loss, n_param = (len(loss._data.sharding.device_set),
                       len(w0.sharding.device_set))
    if n_loss != dp or n_param != dp:
        raise AssertionError(
            f"dp={dp} but the loss spans {n_loss} and a parameter "
            f"{n_param} device(s)")
    report = {"model": model, "batch": global_batch, "dp": dp,
              "dtype": dtype, "steps": steps,
              "loss_first": round(losses[0], 4),
              "loss_last": round(losses[-1], 4),
              "skipped_steps": trainer.skipped_steps,
              "devices": n_param, **fields}
    return report, net


# ------------------------------------------------------------------ serve --

def _post(url, payload, timeout):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def phase_serve(net, *, image, dtype, rows, seed=1, name="smoke"):
    """`net` behind the normal serving stack: container -> server ->
    warm-up over the DEFAULT bucket ladder -> HTTP front end, then one
    real POST per entry of `rows`. Every answer must be 200 and equal a
    direct ``net(x)`` on the same rows within bf16 tolerance, with no
    compile of any kind after warm-up."""
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import compile as mxcompile
    from mxnet_tpu import serving

    ctx = mx.tpu()
    rs = np.random.RandomState(seed)
    # rounded to the model dtype up front, so the JSON round trip and the
    # server-side cast are exact
    data = np.asarray(rs.uniform(size=(sum(rows), 3, image, image)),
                      dtype=jnp.dtype(dtype)).astype(np.float32)
    net.hybridize()
    want = net(mx.nd.array(data, ctx=ctx).astype(dtype)) \
        .asnumpy().astype(np.float32)

    clock = _Clock()
    container = serving.ModelContainer()
    container.add_block(name, net, (3, image, image), dtype=dtype)
    server = serving.ModelServer(container).start()
    front = None
    try:
        warm = server.warmup()
        front = serving.HttpFrontEnd(server, port=0).start()
        clock.compiled()
        misses0 = mxcompile.stats().get("serving", {}).get("misses", 0)
        xla0 = clock.xla_compiles()
        worst, off = 0.0, 0
        for k in rows:
            status, body = _post(
                f"{front.url}/v1/models/{name}:predict",
                {"data": data[off:off + k].tolist()}, timeout=120)
            if status != 200:
                raise AssertionError(f"HTTP {status}: {body}")
            got = np.asarray(body["outputs"][0], np.float32)
            ref = want[off:off + k]
            if got.shape != ref.shape:
                raise AssertionError(
                    f"answer shaped {got.shape}, expected {ref.shape}")
            if not np.isfinite(got).all():
                raise AssertionError("non-finite served output")
            # 2**-5 of the output scale: 8 bf16 ulps, room for the tiling
            # differences between the bucket's batch size and net(x)'s
            err = float(np.abs(got - ref).max()
                        / max(np.abs(ref).max(), 1e-6))
            if err > 2 ** -5:
                raise AssertionError(
                    f"served rows {off}:{off + k} differ from net(x) by "
                    f"{err:.4f} of the output scale")
            worst = max(worst, err)
            off += k
        recompiles = mxcompile.stats().get("serving", {}) \
            .get("misses", 0) - misses0
        xla_after = clock.xla_compiles() - xla0
        if recompiles or xla_after:
            raise AssertionError(
                f"{recompiles} compile-service misses and {xla_after} "
                "backend compiles after warm-up")
        buckets = warm["models"][name]["buckets"]
        return {"buckets": buckets, "requests": len(rows),
                "rows": sum(rows), "max_rel_err": round(worst, 5),
                "recompiles_during_run": recompiles, **clock.fields()}
    finally:
        if front is not None:
            front.close()
        server.stop()


# ---------------------------------------------------------------- kernels --

def _kernel_cases(attn, decode, opt, gemm, seed=2):
    """(label, family, arrays, static kwargs, attention dtype | None) per
    registered family at one shape each; attention in f32 and bf16.
    Static scalars (scale, thr) ride in the kwargs and bake into the
    kernel, as in ``benchmark/opperf.py``."""
    import jax.numpy as jnp

    r = np.random.default_rng(seed)

    def f32(*shape):
        return jnp.asarray(r.standard_normal(shape, dtype=np.float32))

    cases = []
    b, h, s, d = attn
    db, dh, ds, dd = decode
    for dt in (jnp.float32, jnp.bfloat16):
        name = jnp.dtype(dt).name
        q, k, v = (f32(b, h, s, d).astype(dt) for _ in range(3))
        cases.append((f"flash_attention/{name}", "flash_attention",
                      (q, k, v), {"scale": d ** -0.5, "causal": True}, dt))
        dq = f32(db, dh, dd).astype(dt)
        dk, dv = (f32(db, dh, ds, dd).astype(dt) for _ in range(2))
        lens = jnp.asarray(r.integers(1, ds + 1, db), jnp.int32)
        cases.append((f"decode_attention/{name}", "decode_attention",
                      (dq, dk, dv, lens), {"scale": dd ** -0.5}, dt))
    g, m = f32(*opt), f32(*opt)
    gm, gn, gk = gemm
    qx = jnp.asarray(r.integers(-127, 128, (gm, gk)), jnp.int8)
    qw = jnp.asarray(r.integers(-127, 128, (gn, gk)), jnp.int8)
    sc = jnp.asarray(r.random(gn), jnp.float32) * 0.01
    cases.append(("int8_gemm", "int8_gemm", (qx, qw, sc),
                  {"bias": f32(gn), "relu": True}, None))
    cases.append(("twobit_compress", "twobit_compress", (g, m * 0.1),
                  {"thr": 0.5}, None))
    codes = jnp.asarray(r.integers(-4, 5, opt), jnp.int8)
    cases.append(("twobit_decompress", "twobit_decompress", (codes,),
                  {"thr": 0.5}, None))
    # an expert layer's three grouped products in bf16, its type: 512 rows
    # in four groups, one of them empty, every row live
    bf = jnp.bfloat16
    sizes = jnp.asarray([200, 0, 180, 132], jnp.int32)
    rows = f32(512, 256).astype(bf)
    for mode, b in (("nn", f32(4, 256, 384)), ("nt", f32(4, 384, 256)),
                    ("tn", f32(512, 384))):
        cases.append((f"grouped_matmul/{mode}", "grouped_matmul",
                      (rows, b.astype(bf), sizes), {"mode": mode}, bf))
    return cases


def _backward_cases(attn, seed=4):
    """The attention backward's family at both widths the benchmark's
    cells run (equal, no mask; keys half as wide again as values, causal),
    in f32 and bf16: ``((label, family, arrays, kwargs, dtype), dense)``.
    The forward's output and row log-sum-exp it is handed, and the
    gradient ``dense`` it is held to, come from the dense float32
    softmax at the highest precision, not from a kernel."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.kernels.flash import (flash_attention_reference,
                                         row_log_sum_exp)

    r = np.random.default_rng(seed)
    b, h, s, d = attn
    out = []
    for dt in (jnp.float32, jnp.bfloat16):
        for dk, dv, causal in ((d, d, False), (3 * d, 2 * d, True)):
            q, k, v, cot = (
                jnp.asarray(r.standard_normal((b, h, s, w),
                                              dtype=np.float32)).astype(dt)
                for w in (dk, dk, dv, dv))
            scale = dk ** -0.5
            with jax.default_matmul_precision("highest"):
                up = [x.astype(jnp.float32) for x in (q, k, v, cot)]
                o, vjp = jax.vjp(lambda *t: flash_attention_reference(
                    *t, scale, causal), *up[:3])
                dense = vjp(up[3])
                lse = row_log_sum_exp(up[0], up[1], scale, causal)
            label = (f"flash_attention_bwd/{jnp.dtype(dt).name}/"
                     f"d{dk}v{dv}")
            out.append(((label, "flash_attention_bwd",
                         (q, k, v, o.astype(dt), lse, cot),
                         {"scale": scale, "causal": causal}, dt), dense))
    return out


def _whole_block_cases(attn, seed=5):
    """The attention forward at BERT's bucket (a head's whole sequence
    one block, no mask), in f32 and bf16, held like the backward to the
    float32 dense softmax of the same rounded inputs at the highest
    precision: ``((label, family, arrays, kwargs, dtype), dense)``."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.kernels.flash import flash_attention_reference

    r = np.random.default_rng(seed)
    b, h, s, d = attn
    out = []
    for dt in (jnp.float32, jnp.bfloat16):
        q, k, v = (jnp.asarray(r.standard_normal((b, h, s, d),
                                                 dtype=np.float32)).astype(dt)
                   for _ in range(3))
        with jax.default_matmul_precision("highest"):
            dense = flash_attention_reference(
                *(x.astype(jnp.float32) for x in (q, k, v)), d ** -0.5,
                False)
        out.append(((f"flash_attention/{jnp.dtype(dt).name}/s{s}_whole",
                     "flash_attention", (q, k, v),
                     {"scale": d ** -0.5, "causal": False}, dt), dense))
    return out


def _chunk_states(args, chunk):
    """The float32 state every ``chunk`` positions of the step-by-step
    recurrence, (batch, chunks, states, channels): what the scan's forward
    kernel saves for its backward."""
    import jax
    import jax.numpy as jnp

    x, step, a, bm, cm, _ = args

    def one(h, inp):
        x_t, s_t, b_t = inp
        return (jnp.exp(s_t[..., None] * a) * h
                + (s_t * x_t)[..., None] * b_t[:, None, :]), h

    h0 = jnp.zeros((x.shape[0], x.shape[2], a.shape[1]), jnp.float32)
    _, before = jax.lax.scan(one, h0, tuple(
        t.swapaxes(0, 1) for t in (x, step, bm)))
    return before[::chunk].transpose(1, 0, 3, 2)


def _scan_cases(scan, seed=6):
    """The selective scan, forward and backward, in f32 and bf16, held to
    the step-by-step ``lax.scan`` in float32 (and its gradient) of the same
    rounded inputs: ``((label, family, arrays, kwargs, dtype), want)``.
    The length is no multiple of the kernel's chunk; the backward is
    handed the states the forward kernel saved at chunk boundaries, as
    its ``custom_vjp`` hands them."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.kernels import selective_scan as ss

    r = np.random.default_rng(seed)
    b, s, dch, n = scan
    f32 = jnp.float32

    def normal(*shape):
        return jnp.asarray(r.standard_normal(shape, dtype=np.float32))

    out = []
    for dt in (f32, jnp.bfloat16):
        x, bm, cm, cot = (t.astype(dt) for t in (
            normal(b, s, dch), normal(b, s, n), normal(b, s, n),
            normal(b, s, dch)))
        step = jax.nn.softplus(normal(b, s, dch) - 2.0)
        a = -jnp.exp(normal(dch, n) * 0.5)
        args = (x, step, a, bm, cm, normal(dch))
        up = tuple(t.astype(f32) for t in args)
        want, vjp = jax.vjp(ss.selective_scan_reference, *up)
        states = _chunk_states(up, ss.CHUNK)
        name = jnp.dtype(dt).name
        out.append(((f"selective_scan/{name}", "selective_scan", args, {},
                     dt), want))
        out.append(((f"selective_scan_bwd/{name}", "selective_scan_bwd",
                     args + (states, cot), {}, dt), vjp(cot.astype(f32))))
    return out


def _close_to_dense(got, want, dt, family):
    """``_close`` at what the attention families register for the chip
    against the float32 dense softmax: the largest error over the largest
    |entry|. The backward: 1e-4 in f32 (a sum over a thousand keys of
    Mosaic's float32 matmul passes read 2.8e-5, my chip run, PR 27; an
    elementwise atol does not scale with the sum) and 2e-2 in bf16 (read
    3.3e-3 to 4.6e-3). The forward: 1e-4 in f32 and 1e-2 in bf16 (``p``
    rounded to bf16 before ``p @ v`` and the output to bf16: read 2.4e-3
    to 2.9e-3 in the interpreter, PR 29)."""
    import jax.numpy as jnp

    got = np.asarray(got).astype(np.float32)
    want = np.asarray(want).astype(np.float32)
    err = float(np.abs(got - want).max())
    tol = 1e-4 if dt == jnp.float32 \
        else 2e-2 if family == "flash_attention_bwd" else 1e-2
    return err, err <= tol * float(np.abs(want).max()), tol


def _close(got, want, dt):
    """(max abs error, inside tolerance?, the tolerance applied). The
    elementwise and integer families register bit-exactness (dt None);
    attention registers rtol=atol=2e-5 for f32; for bf16, where it
    registers nothing, 2**-6 (four ulps at 1.0): the dense baseline rounds
    its scores to bf16 before the softmax, the kernel keeps them in f32."""
    import jax.numpy as jnp

    got = np.asarray(got).astype(np.float32)
    want = np.asarray(want).astype(np.float32)
    err = float(np.abs(got - want).max()) if got.size else 0.0
    if dt is None:
        return err, bool(np.array_equal(got, want)), "bit-exact"
    tol = 2e-5 if dt == jnp.float32 else 2 ** -6
    return err, bool(np.allclose(got, want, rtol=tol, atol=tol)), tol


def phase_kernels(*, attn, attn_whole, decode, opt, gemm, scan, interpret):
    """Every registered family through ``kernels.dispatch`` FORCED onto
    its kernel (``interpret=False``: compiled by Mosaic; True only for the
    CPU test) and compared with the family's XLA baseline (the attention
    backward, and the forward at BERT's bucket ``attn_whole``, with the
    float32 dense gradient / softmax of the same inputs, which is what
    their XLA sides compute in the inputs' dtype; the selective scan and
    its backward with the step-by-step recurrence in float32). Both sides are
    traced at the highest matmul precision: the registered tolerances are
    statements about the algorithm, and at the TPU's default (bf16 passes
    for an f32 matmul) kernel and baseline each sit ~1e-2 from the truth
    (my chip run, PR 21). The default-precision variant of flash compiles
    inside :func:`phase_encoder`."""
    import jax

    from mxnet_tpu import compile as mxcompile
    from mxnet_tpu import kernels

    clock = _Clock()
    cases = _kernel_cases(attn, decode, opt, gemm)
    dense = {}      # label -> the float32 dense result a case is held to
    for case, want in (_backward_cases(attn) + _whole_block_cases(attn_whole)
                       + _scan_cases(scan)):
        cases.append(case)
        dense[case[0]] = want
    missing = set(kernels.families()) - {c[1] for c in cases}
    if missing:
        raise AssertionError(f"no smoke case for families {missing}")
    fns, results, failed = [], {}, []
    for label, family, arrays, kw, dt in cases:
        entry = kernels.entry(family)
        kfn = mxcompile.jit(
            lambda *a, _f=family, _kw=kw: kernels.dispatch(
                _f, *a, interpret=interpret, **_kw),
            site="smoke", token=("kernel", label, bool(interpret)))
        xfn = mxcompile.jit(
            lambda *a, _e=entry, _kw=kw: _e.xla(*a, **_kw),
            site="smoke", token=("xla", label))
        try:
            with jax.default_matmul_precision("highest"):
                got = jax.block_until_ready(kfn(*arrays))
                want = dense[label] if label in dense \
                    else jax.block_until_ready(xfn(*arrays))
        except Exception as e:  # the compiler's refusal IS the finding
            results[label] = {"ok": False, "error":
                              f"{type(e).__name__}: {str(e)[:600]}"}
            failed.append(label)
            continue
        fns.append((kfn, arrays))
        got_l = got if isinstance(got, tuple) else (got,)
        want_l = want if isinstance(want, tuple) else (want,)
        errs, oks, tols = zip(*(
            _close_to_dense(a, b, dt, family) if label in dense
            else _close(a, b, dt) for a, b in zip(got_l, want_l)))
        results[label] = {"ok": all(oks), "max_abs_err": max(errs),
                          "tolerance": tols[0]}
        if not all(oks):
            failed.append(label)
    clock.compiled()
    with jax.default_matmul_precision("highest"):
        for kfn, arrays in fns:  # second call: no compile, just the kernel
            jax.block_until_ready(kfn(*arrays))
    stats = kernels.dispatch_stats()
    report = {"families": results, "interpret": bool(interpret),
              "dispatched_kernel": {f: s["kernel"]
                                    for f, s in stats.items()},
              **clock.fields()}
    if failed:
        raise AssertionError(
            f"kernel families refused or out of tolerance: {failed}\n"
            + json.dumps(results, indent=1))
    return report


# ---------------------------------------------------------------- encoder --

def phase_encoder(*, units, heads, hidden, seq, batch, dtype, steps=2,
                  seed=3):
    """One ``TransformerEncoderCell`` through ``ShardedTrainer``: on a
    TPU the untuned dispatch takes the Pallas flash forward and, as a
    decision of its own, the backward's kernels, inside one compiled
    train step."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import kernels
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.contrib.nn import TransformerEncoderCell
    from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer

    ctx = mx.tpu()
    mx.random.seed(seed)
    rs = np.random.RandomState(seed)
    net = TransformerEncoderCell(units, hidden, heads)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    net.cast(dtype)
    x = mx.nd.array(rs.standard_normal((batch, seq, units))
                    .astype(np.float32), ctx=ctx).astype(dtype)
    y = mx.nd.array(rs.standard_normal((batch, seq, units))
                    .astype(np.float32), ctx=ctx).astype(dtype)
    clock = _Clock()
    kernels.reset_stats()
    net(x)  # materialize deferred shapes
    trainer = ShardedTrainer(net, gloss.L2Loss(), "sgd",
                             {"learning_rate": 0.01, "momentum": 0.9},
                             mesh=DeviceMesh({"dp": 1}))
    losses = [float(trainer.step(x, y).asscalar())]
    clock.compiled()
    for _ in range(steps):
        losses.append(float(trainer.step(x, y).asscalar()))
    if not all(np.isfinite(losses)) or trainer.skipped_steps:
        raise AssertionError(
            f"losses {losses}, skipped {trainer.skipped_steps}")
    flash = kernels.dispatch_stats().get("flash_attention", {})
    if jax.devices()[0].platform == "tpu" and not flash.get("kernel"):
        raise AssertionError(
            "the encoder step did not take the Pallas flash kernel: "
            f"{flash} (a tuned dispatch table under the cache directory "
            "routes it to XLA?)")
    return {"units": units, "heads": heads, "hidden": hidden, "seq": seq,
            "batch": batch, "dtype": dtype,
            "loss_first": round(losses[0], 4),
            "loss_last": round(losses[-1], 4),
            "flash_dispatch": {"kernel": flash.get("kernel", 0),
                               "xla": flash.get("xla", 0)},
            "flash_backward_dispatch": kernels.dispatch_stats().get(
                "flash_attention_bwd", {}).get("buckets", {}),
            **clock.fields()}


# ------------------------------------------------------------------- run ---

def run(sizes):
    """Run every phase at `sizes`, kernels Mosaic-compiled; returns the
    summary dict (``ok`` is False when any phase failed — each failure
    carries its error)."""
    import jax

    from mxnet_tpu import compile as mxcompile

    summary = dict(device_info(), phases={})
    n_dev = summary["device"]["count"]
    net = None

    def attempt(name, fn):
        t0 = time.perf_counter()
        try:
            rep = fn()
            rep = dict(ok=True, **rep)
        except Exception as e:
            traceback.print_exc()
            rep = {"ok": False, "error": f"{type(e).__name__}: "
                                         f"{str(e)[:2000]}",
                   "wall_s": round(time.perf_counter() - t0, 2)}
        summary["phases"][name] = rep
        print(f"phase {name}: {json.dumps(rep)}", flush=True)
        return rep

    def train():
        nonlocal net
        rep, net = phase_train(**sizes["train"])
        return rep

    attempt("train", train)
    if net is not None:
        attempt("serve", lambda: phase_serve(
            net, image=sizes["train"]["image"],
            dtype=sizes["train"]["dtype"], **sizes["serve"]))
    else:
        summary["phases"]["serve"] = {
            "ok": False, "error": "no trained network: train failed"}
    net = None
    attempt("kernels", lambda: phase_kernels(interpret=False,
                                             **sizes["kernels"]))
    attempt("encoder", lambda: phase_encoder(**sizes["encoder"]))
    if n_dev > 1:
        attempt("train_dp", lambda: phase_train(
            dp=n_dev, **sizes["train"])[0])
    else:
        print("phase train_dp: one chip attached, nothing to spread "
              "over", flush=True)
    summary["compile"] = mxcompile.totals()
    summary["cache_dir"] = mxcompile.cache_dir()
    summary["jax_cache_dir"] = jax.config.jax_compilation_cache_dir
    summary["ok"] = all(p["ok"] for p in summary["phases"].values())
    return summary


def result_line(summary):
    """The contract's last line: exactly the keys ``ok`` and ``device``
    (``platform``, ``kind``, ``count`` as jax reports them). Everything
    else is on the lines above it."""
    dev = summary["device"]
    return json.dumps({"ok": bool(summary["ok"]),
                       "device": {"platform": str(dev["platform"]),
                                  "kind": str(dev["kind"]),
                                  "count": int(dev["count"])}})


def main():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py: no TPU (jax reports platform "
              f"{dev.platform!r}); nothing run", file=sys.stderr)
        return 1
    # the program itself, before a single line is printed: where only this
    # file exists the run ends here, with no output that reads as a result
    import mxnet_tpu  # noqa: F401

    info = device_info()
    print(f"device: {json.dumps(info['device'])}", flush=True)
    print(f"versions: {json.dumps(info['versions'])}", flush=True)
    t0 = time.perf_counter()
    summary = run(REAL)
    summary["wall_s"] = round(time.perf_counter() - t0, 1)
    print(f"compile.totals(): {json.dumps(summary['compile'])}",
          flush=True)
    print(f"summary: {json.dumps(summary)}", flush=True)
    print(result_line(summary), flush=True)
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
