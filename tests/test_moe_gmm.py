"""The expert layer's first rung on the ``grouped_matmul`` family
(``kernels/grouped_matmul.py``, megablox ``gmm`` / ``tgmm`` in the Pallas
interpreter here): the layer's output and all five cotangents as the
``ragged_dot`` rung gives them and as a float32 ``jax.numpy`` reference
gives them, at live counts of none, one inside a tile with an empty group,
and one past the first rung; and, lowered for the TPU at two expert cells'
real widths, a bound on the distinct Mosaic kernels the layer's forward and
backward hold."""
import re

import numpy as np
import pytest

from mxnet_tpu import kernels
from mxnet_tpu.parallel import moe as pmoe

# 512 tokens, top-4 of a router 16 wide, 4 experts held: 2,048 pairs, a
# first rung of 1,024 rows and a second of every pair
T, H, F, E, N, K = 512, 256, 128, 16, 4, 4
RUNGS = (1024, 2048)


@pytest.fixture
def fresh_traces():
    """The rungs are jitted once a process: a test that changes what the
    first rung dispatches to traces them anew."""
    import jax

    def clear():
        pmoe._ladder.cache_clear()
        jax.clear_caches()
        kernels.reset_stats()

    clear()
    yield clear
    clear()


def _kernel_side(monkeypatch):
    """Send every ``grouped_matmul`` decision to the kernel (the
    interpreter here), as a tuned table row does."""
    real = kernels.table.lookup
    monkeypatch.setattr(
        kernels.table, "lookup",
        lambda family, bucket: ({"winner": "kernel"}
                                if family == "grouped_matmul"
                                else real(family, bucket)))


def _pairs(groups, seed=0):
    """The ladder's inputs for (token, choice) pairs routed to ``groups``
    (T * K ints, ``N`` = an absent expert): bfloat16 x and weights, the
    router weights float32 and zero on absent pairs, as ``routed_experts``
    hands them over, and a float32 cotangent of the output."""
    import jax.numpy as jnp

    rs = np.random.RandomState(seed)
    group = np.asarray(groups, np.int32)
    order = np.argsort(group, kind="stable").astype(np.int32)
    inv = np.empty_like(order)
    inv[order] = np.arange(order.size, dtype=np.int32)
    sizes = np.bincount(group, minlength=N + 1)[:N].astype(np.int32)
    wk = np.where(group.reshape(T, K) < N,
                  rs.uniform(0.1, 1.0, (T, K)), 0.0).astype(np.float32)
    bf = jnp.bfloat16
    x = jnp.asarray(rs.randn(T, H), bf)
    w_gate, w_up = (jnp.asarray(0.1 * rs.randn(N, H, F), bf)
                    for _ in range(2))
    w_down = jnp.asarray(0.1 * rs.randn(N, F, H), bf)
    g = jnp.asarray(rs.randn(T, H), jnp.float32)
    return ((x, jnp.asarray(wk), w_gate, w_up, w_down, jnp.asarray(order),
             jnp.asarray(inv), jnp.asarray(sizes)), g)


def _ladder_and_cotangents(args, g):
    import jax

    layer = pmoe._ladder(K, RUNGS)

    def run(*a):
        y, vjp = jax.vjp(lambda *d: layer(*d, *a[5:]), *a[:5])
        return y, vjp(g)

    return jax.jit(run)(*args)


def _reference(args, g):
    """Every live pair's gated-SiLU MLP in float32 from the same bfloat16
    operands, summed with its router weight; the cotangents by
    ``jax.vjp``."""
    import jax
    import jax.numpy as jnp

    x, wk, w_gate, w_up, w_down, order, inv, sizes = args
    f32 = jnp.float32
    ids = jnp.searchsorted(jnp.cumsum(sizes), inv, side="right")
    ids = ids.reshape(T, K)

    def layer(x, wk, w_gate, w_up, w_down):
        x, w_gate, w_up, w_down = (a.astype(f32)
                                   for a in (x, w_gate, w_up, w_down))
        y = jnp.zeros((T, H), f32)
        for j in range(K):
            live = ids[:, j] < N
            e = jnp.minimum(ids[:, j], N - 1)
            gate = jnp.einsum("th,thf->tf", x, w_gate[e])
            up = jnp.einsum("th,thf->tf", x, w_up[e])
            out = jnp.einsum("tf,tfh->th", jax.nn.silu(gate) * up, w_down[e])
            y = y + jnp.where(live[:, None], wk[:, j, None] * out, 0.0)
        return y

    y, vjp = jax.vjp(layer, *args[:5])
    return y, vjp(g)


def _groups(case):
    """``groups`` for ``_pairs``: ``none`` routes nothing here; ``inside``
    900 live pairs, expert 2 given none, so the live count ends inside a
    row tile; ``overflow`` 1,500 live pairs, past the first rung."""
    rs = np.random.RandomState(1)
    live = {"none": 0, "inside": 900, "overflow": 1500}[case]
    held = (0, 1, 3) if case == "inside" else tuple(range(N))
    groups = np.full(T * K, N)
    groups[rs.choice(T * K, live, replace=False)] = rs.choice(held, live)
    return groups, live


@pytest.mark.parametrize("case", ["none", "inside", "overflow"])
def test_first_rung_kernel_matches_ragged_dot_and_float32(
        monkeypatch, fresh_traces, case):
    """The layer's output and the cotangents of x, the router weights and
    the three expert weights: the kernel rung against the ``ragged_dot``
    rung (one rounding of bfloat16 apart, the sums run in another order)
    and both against the float32 reference (the bfloat16 roundings of the
    gate, the activation and the products between them). The first rung
    makes 3 + 8 decisions, all for the kernel; the second none."""
    groups, live = _groups(case)
    args, g = _pairs(groups)
    assert int(args[-1].sum()) == live
    assert pmoe.buffer_rungs(T, K, N, E) == RUNGS
    xla_y, xla_d = _ladder_and_cotangents(args, g)
    assert kernels.dispatch_stats()["grouped_matmul"]["kernel"] == 0
    fresh_traces()
    _kernel_side(monkeypatch)
    y, d = _ladder_and_cotangents(args, g)
    stats = kernels.dispatch_stats()["grouped_matmul"]
    assert (stats["kernel"], stats["xla"]) == (11, 0)
    ref_y, ref_d = _reference(args, g)
    for got, xla, ref in zip((y, *d), (xla_y, *xla_d), (ref_y, *ref_d)):
        got, xla, ref = (np.asarray(a, np.float32) for a in (got, xla, ref))
        assert np.isfinite(got).all()
        scale = float(np.abs(ref).max()) or 1.0
        np.testing.assert_allclose(got, xla, rtol=0, atol=1e-2 * scale)
        np.testing.assert_allclose(got, ref, rtol=0, atol=3e-2 * scale)
        if live == 0:
            assert not got.any()
    if case == "overflow":
        # past the first rung both sides run the same ragged_dot rung
        np.testing.assert_array_equal(np.asarray(y), np.asarray(xla_y))


@pytest.mark.parametrize("mode", ["nn", "nt", "tn"])
def test_family_matches_its_xla_side(mode):
    """Each of the three forms alone, bfloat16, empty groups and a live
    count inside a row tile: the kernel in the interpreter against
    ``ragged_dot`` on the live rows."""
    import jax.numpy as jnp

    from mxnet_tpu.kernels import grouped_matmul as gm

    rs = np.random.RandomState(2)
    rows, k, n, groups = 1024, 256, 384, 4
    sizes = jnp.asarray([300, 0, 250, 190], jnp.int32)
    a = jnp.asarray(rs.randn(rows, k), jnp.bfloat16)
    b = jnp.asarray(rs.randn(*{"nn": (groups, k, n), "nt": (groups, n, k),
                               "tn": (rows, n)}[mode]), jnp.bfloat16)
    assert kernels.choice_for("grouped_matmul", a, b, sizes, mode=mode) \
        == ("xla", "untuned_default")
    got = kernels.dispatch("grouped_matmul", a, b, sizes, mode=mode,
                           interpret=True)
    want = gm.grouped_matmul_xla(a, b, sizes, mode)
    assert got.shape == want.shape and got.dtype == want.dtype
    live = slice(None) if mode == "tn" else slice(0, int(sizes.sum()))
    got, want = (np.asarray(t, np.float32)[live] for t in (got, want))
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-2 * float(np.abs(want).max()))
    if mode == "tn":
        assert not got[1].any()   # the empty group's cotangent is zeros


# (tokens, top-k, held, router width, hidden, expert width): the
# softmax-routed decoder's and the short-convolution decoder's cells
WIDTHS = {"mellum": (8192, 8, 16, 64, 2304, 896),
          "lfm2": (8192, 4, 8, 32, 2048, 1792)}
# a rung's products have six distinct shapes: gate / up forward and
# recomputed, down, the two input cotangent forms, two weight cotangents
MOSAIC_KERNELS = 6


@pytest.mark.parametrize("cell", sorted(WIDTHS))
def test_layer_lowers_to_few_mosaic_kernels(monkeypatch, fresh_traces,
                                            cell):
    """The expert layer's forward and backward at the cell's real widths,
    lowered for the TPU (nothing compiles): the first rung's eleven
    products lower to at most ``MOSAIC_KERNELS`` distinct Mosaic kernels,
    the upper rungs to none. Each distinct kernel is traced and lowered on
    every start of the program, warm or cold, so a copy more shows in
    ``setup_s``."""
    import jax
    import jax.numpy as jnp

    t, k, n, e, h, f = WIDTHS[cell]
    monkeypatch.setattr(kernels, "on_tpu", lambda: True)
    bf = jnp.bfloat16
    shapes = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((t, h), bf), ((e, h), bf), ((e,), jnp.float32), ((n, h, f), bf),
        ((n, h, f), bf), ((n, f, h), bf))]

    def loss(*a):
        y, load = pmoe.routed_experts(*a, top_k=k, scale=1.0)
        return y.astype(jnp.float32).sum(), load

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 3, 4, 5), has_aux=True)
                      ).trace(*shapes).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    calls = re.findall(r"stablehlo\.custom_call @tpu_custom_call\(.*?"
                       r'backend_config = "([^"]*)"', text)
    assert calls, "the first rung holds no Mosaic kernel"
    assert len(set(calls)) <= MOSAIC_KERNELS, len(set(calls))
    stats = kernels.dispatch_stats()["grouped_matmul"]
    assert (stats["kernel"], stats["xla"]) == (11, 0)
