"""The selective scan (kernel families ``selective_scan`` and
``selective_scan_bwd``): the Pallas kernels in the interpreter and the
chunked XLA form against a step-by-step loop, forward and backward, at
lengths that are no multiple of the chunk; the op over them; what the
families count and refuse."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import kernels
from mxnet_tpu.kernels import selective_scan as ss
from mxnet_tpu.ops import registry as opreg


def _case(bsz, s, dch, n, dtype=jnp.float32, seed=1):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(k[0], (bsz, s, dch)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (bsz, s, dch)) - 2.0)
    a = -jnp.exp(jax.random.normal(k[2], (dch, n)) * 0.5)
    b = jax.random.normal(k[3], (bsz, s, n)).astype(dtype)
    c = jax.random.normal(k[4], (bsz, s, n)).astype(dtype)
    d = jax.random.normal(k[5], (dch,))
    cot = jax.random.normal(k[6], (bsz, s, dch))
    return (x, dt, a, b, c, d), cot


def _loop(x, dt, a, b, c, d):
    """The recurrence a step at a time in numpy float64: the oracle of
    the oracle."""
    x, dt, a, b, c, d = (np.asarray(t, np.float64) for t in (x, dt, a, b, c, d))
    h = np.zeros((x.shape[0], x.shape[2], a.shape[1]))
    y = np.zeros(x.shape)
    for t in range(x.shape[1]):
        h = np.exp(dt[:, t, :, None] * a) * h \
            + (dt[:, t] * x[:, t])[:, :, None] * b[:, t, None, :]
        y[:, t] = (h * c[:, t, None, :]).sum(-1) + d * x[:, t]
    return y


def _kernel(*args):
    return kernels.dispatch("selective_scan", *args, interpret=True)


FORMS = {"kernel": _kernel, "chunked": ss.selective_scan_chunked}


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def test_reference_scan_is_the_step_by_step_loop():
    args, _ = _case(2, 37, 128, 8)
    assert _rel(ss.selective_scan_reference(*args), _loop(*args)) < 1e-5


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("bsz,s,dch,n", [(2, 150, 256, 16), (1, 64, 128, 8),
                                         (1, 70, 640, 16)])
def test_forward_and_backward_against_the_loop(form, bsz, s, dch, n):
    """150 and 70 positions are no multiple of the 64-position chunk (the
    forms pad with steps of dt = 0); 640 channels are five 128-lane
    programs, 256 one."""
    args, cot = _case(bsz, s, dch, n)
    assert s % ss.CHUNK or s == ss.CHUNK

    def loss(f):
        return lambda *a: (f(*a) * cot).sum()

    want, want_g = jax.value_and_grad(
        loss(ss.selective_scan_reference), range(6))(*args)
    got, got_g = jax.value_and_grad(loss(FORMS[form]), range(6))(*args)
    assert _rel(FORMS[form](*args), _loop(*args)) < 1e-5
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for name, g, w in zip("x dt a b c d".split(), got_g, want_g):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert _rel(g, w) < 1e-4, name
        assert float(jnp.abs(w).max()) > 0, name


@pytest.mark.parametrize("form", sorted(FORMS))
def test_bfloat16_inputs_keep_a_float32_state(form):
    """x, B and C in bfloat16, the step, the decay and the state float32:
    the output is the float32 recurrence of the same rounded inputs,
    rounded once."""
    args, _ = _case(1, 200, 256, 16, jnp.bfloat16)
    got = FORMS[form](*args)
    assert got.dtype == jnp.bfloat16
    want = _loop(*(np.asarray(t.astype(jnp.float32)) for t in args))
    assert _rel(got.astype(jnp.float32), want) < 1e-2   # one rounding: 2**-8
    # ... and nothing but that one rounding: almost every output is the
    # float64 recurrence's, rounded. A state (or a step) kept in bfloat16
    # moves most of them by several units in the last place; the chip's
    # logits comparison cannot see that fault (PERF.md section 4), this can
    same = np.asarray(got == jnp.asarray(want, jnp.float32).astype(
        jnp.bfloat16))
    assert same.mean() > 0.99
    x, dt, a, b, c, d = (t.astype(jnp.float32) for t in args)
    coarse = ss.selective_scan_chunked(
        x, jax.lax.reduce_precision(dt, 8, 7), a, b, c, d)
    assert np.asarray(coarse.astype(jnp.bfloat16) == got).mean() < 0.9


def test_the_state_carries_over_chunks_and_channel_blocks():
    """A long memory (slow decay) over four chunks: the last position
    still sees the first; a form that lost the state at a chunk boundary
    would not."""
    args, _ = _case(1, 256, 256, 8)
    x, dt, a, b, c, d = args
    args = (x, dt * 0.01, a, b, c, d)
    first_gone = (x.at[:, 0].set(0.0),) + args[1:]
    for form in FORMS.values():
        assert _rel(form(*args), _loop(*args)) < 1e-5
        assert float(jnp.abs(form(*args)[:, -1]
                             - form(*first_gone)[:, -1]).max()) > 1e-3


def test_families_are_registered_counted_and_bucketed(monkeypatch):
    assert {"selective_scan", "selective_scan_bwd"} <= set(kernels.families())
    args, cot = _case(1, 100, 128, 8)
    kernels.reset_stats()
    jax.grad(lambda *a: (_kernel(*a) * cot).sum())(*args)
    stats = kernels.dispatch_stats()
    key = f"b1_s128_d128_n8_float32_t{ss.CHUNK}l128"
    for family in ("selective_scan", "selective_scan_bwd"):
        assert stats[family]["kernel"] == 1 and stats[family]["xla"] == 0
        assert stats[family]["reasons"] == {"interpret_forced": 1}
        assert list(stats[family]["buckets"]) == [key]
    # off the TPU an untuned bucket takes the chunked XLA form, forward
    # and backward (the backward is asked inside the forward's vjp only
    # where the forward ran as the kernel)
    kernels.reset_stats()
    assert kernels.choice_for("selective_scan", *args) \
        == ("xla", "untuned_default")
    got = kernels.dispatch("selective_scan", *args)
    assert _rel(got, _loop(*args)) < 1e-5
    # ... and on it the kernel, by default
    monkeypatch.setattr(kernels, "on_tpu", lambda: True)
    assert kernels.choice_for("selective_scan", *args) \
        == ("kernel", "untuned_default_tpu")
    # the environment's opt-out reaches the backward's own decision
    monkeypatch.setenv("MXNET_TPU_KERNELS", "0")
    _, states = ss.selective_scan_forward(*args, interpret=True)
    kernels.reset_stats()
    grads = kernels.dispatch("selective_scan_bwd", *args, states, cot)
    assert kernels.dispatch_stats()["selective_scan_bwd"]["xla"] == 1
    want = jax.grad(lambda *a: (ss.selective_scan_reference(*a) * cot).sum(),
                    range(6))(*args)
    for g, w in zip(grads, want):
        assert _rel(g, w) < 1e-4


@pytest.mark.parametrize("dch,n,ok", [(256, 16, True), (128, 8, True),
                                      (100, 16, False), (256, 12, False)])
def test_supports_whole_lane_blocks_and_sublane_tiles(dch, n, ok):
    args, _ = _case(1, 16, dch, n)
    assert ss._supports(*args) is ok
    if not ok:   # the op still runs: dispatch takes the chunked form
        assert kernels.choice_for("selective_scan", *args) \
            == ("xla", "unsupported_shape")
        assert _rel(kernels.dispatch("selective_scan", *args,
                                     interpret=True), _loop(*args)) < 1e-5


def test_lanes_a_program_divide_the_channels():
    assert [ss._lanes_of(c) for c in (5120, 1024, 640, 384, 256, 128)] \
        == [512, 512, 128, 128, 256, 128]


def test_no_pallas_at_import():
    """Registering the family imports no Pallas and traces nothing: the
    kernels import it inside the functions that build a call."""
    import os
    import subprocess
    import sys

    code = ("import sys, mxnet_tpu\n"
            "from mxnet_tpu import kernels\n"
            "assert 'selective_scan' in kernels.families()\n"
            "assert not any(m.startswith('jax.experimental.pallas') "
            "or m.startswith('jax._src.pallas') for m in sys.modules), "
            "[m for m in sys.modules if 'pallas' in m]\n")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_op_applies_softplus_and_the_decay_in_float32():
    """``_contrib_selective_scan`` takes the step before its softplus and
    ``A_log``; a bfloat16 step projection still gives a float32 step."""
    op = opreg.get("_contrib_selective_scan").fn
    (x, dt, a, b, c, d), _ = _case(1, 50, 128, 8)
    raw = jax.random.normal(jax.random.PRNGKey(4), dt.shape) - 3.0
    bias = jax.random.normal(jax.random.PRNGKey(5), (128,)) * 0.1
    a_log = jnp.log(-a)
    got = op(x, raw.astype(jnp.bfloat16), a_log, b, c, d,
             bias.astype(jnp.bfloat16), interpret=True)
    step = jax.nn.softplus(raw.astype(jnp.bfloat16).astype(jnp.float32)
                           + bias.astype(jnp.bfloat16).astype(jnp.float32))
    assert _rel(got, _loop(x, step, a, b, c, d)) < 1e-5


def test_causal_conv1d_by_hand():
    op = opreg.get("_contrib_causal_conv1d").fn
    x = jnp.arange(1.0, 7.0).reshape(1, 6, 1)
    w = jnp.array([[1.0, 10.0, 100.0, 1000.0]])
    y = op(x, w, jnp.array([0.5]))
    # y_t = 1000 x_t + 100 x_(t-1) + 10 x_(t-2) + x_(t-3) + 0.5
    assert y.ravel().tolist() == [1000.5, 2100.5, 3210.5, 4321.5, 5432.5,
                                  6543.5]
    silu = op(x, w, jnp.array([0.5]), activation="silu")
    np.testing.assert_allclose(silu, y * jax.nn.sigmoid(y), rtol=1e-6)
    with pytest.raises(ValueError):
        op(x, w, jnp.array([0.5]), activation="relu")
