"""The expert layer's row buffer sized by the live pairs
(``parallel.moe.routed_experts`` over ``buffer_rungs``): against the form
with a row for every (token, expert) pair, which the layer takes when it
has one rung, at live counts of none, exactly a rung, a rung and one more,
and every pair; the rungs from the shapes alone; no switch where every
expert is held."""
import numpy as np
import pytest

from mxnet_tpu.parallel import moe as pmoe

# 2,048 tokens, top-2 of a router 8 wide, experts 0 and 1 held: 4,096 pairs
T, H, F, E, N, K = 2048, 16, 8, 8, 2, 2
RUNGS = (2048, 3072, 4096)


def _routed(both, one, seed=0):
    """Inputs whose router sends ``both`` tokens to both held experts,
    ``one`` to one held and one absent, the rest to two absent: ``2 *
    both + one`` live pairs. The router's rows are one-hot, a token's x
    points at its two experts."""
    import jax.numpy as jnp

    rs = np.random.RandomState(seed)
    x = 0.05 * rs.randn(T, H).astype(np.float32)
    for t in range(T):
        absent = 2 + t % 6, 2 + (t + 1) % 6
        pick = ((0, 1) if t < both else (0, absent[0]) if t < both + one
                else absent)
        x[t, list(pick)] += 4.0
    router = np.eye(E, H, dtype=np.float32)
    weights = [0.3 * rs.randn(*s).astype(np.float32)
               for s in ((N, H, F), (N, H, F), (N, F, H))]
    cot = rs.randn(T, H).astype(np.float32)
    return [jnp.asarray(a) for a in (x, router, np.zeros(E, np.float32),
                                     *weights, cot)]


def _layer_and_grads(args):
    import jax

    *inputs, cot = args

    def loss(x, router, bias, w_gate, w_up, w_down):
        y, load = pmoe.routed_experts(x, router, bias, w_gate, w_up,
                                      w_down, top_k=K, scale=1.5)
        return (y * cot).sum(), (y, load)

    (_, (y, load)), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 3, 4, 5), has_aux=True))(*inputs)
    return y, load, grads


@pytest.mark.parametrize("both, one, live", [
    (0, 0, 0), (1024, 0, 2048), (1024, 1, 2049), (2048, 0, 4096)],
    ids=["none", "one_rung", "one_rung_and_one", "every_pair"])
def test_ladder_matches_the_row_for_every_pair(monkeypatch, both, one,
                                               live):
    """y, the load and every gradient (x, the router, the three weights)
    as the buffer with a row for every pair gives them."""
    import jax

    assert pmoe.buffer_rungs(T, K, N, E) == RUNGS
    args = _routed(both, one)
    y, load, grads = _layer_and_grads(args)
    assert float(load.sum()) == live
    monkeypatch.setattr(pmoe, "buffer_rungs",
                        lambda tokens, top_k, held, experts:
                        (tokens * top_k,))
    jax.clear_caches()
    y0, load0, grads0 = _layer_and_grads(args)
    np.testing.assert_array_equal(np.asarray(load), np.asarray(load0))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0),
                               rtol=1e-5, atol=1e-5)
    for g, g0 in zip(grads, grads0):
        scale = float(np.abs(np.asarray(g0)).max()) or 1.0
        np.testing.assert_allclose(np.asarray(g), np.asarray(g0),
                                   rtol=1e-5, atol=1e-6 * scale)


@pytest.mark.parametrize("shape, rungs", [
    # lfm2_8b_a1b_train_s8192: 8,192 tokens, top-4, 8 of 32 held
    ((8192, 4, 8, 32), (9216, 17408, 32768)),
    # kanana2_30b_a3b_train_s4096: 8,192 tokens, top-6, 16 of 128 held
    ((8192, 6, 16, 128), (7168, 18432, 49152)),
    ((T, K, N, E), RUNGS),
    # every expert held, or too few pairs for a tile: one rung
    ((8192, 4, 32, 32), (32768,)),
    ((64, 2, 2, 8), (128,))])
def test_rungs_come_from_the_shapes(shape, rungs):
    """At most ``MAX_RUNGS``, ascending, the first above the even
    router's count, multiples of the tile below every pair, the last every
    pair."""
    got = pmoe.buffer_rungs(*shape)
    assert got == rungs
    tokens, top_k, held, experts = shape
    assert len(got) <= pmoe.MAX_RUNGS and list(got) == sorted(set(got))
    assert got[-1] == tokens * top_k
    assert all(r % pmoe.ROW_TILE == 0 for r in got[:-1])
    assert got[0] >= tokens * top_k * held / experts or len(got) == 1


@pytest.mark.parametrize("held, switches", [(N, 2), (E, 0)])
def test_a_switch_only_where_experts_are_absent(held, switches):
    """Forward and backward each choose a rung where the device holds
    fewer experts than the router is wide; with all held the layer is the
    single path it was, no switch in its program."""
    import jax
    import jax.numpy as jnp

    x, router, bias, *_ = _routed(0, 0)
    w = jnp.zeros((held, H, F)), jnp.zeros((held, H, F)), \
        jnp.zeros((held, F, H))

    def loss(x, *w):
        y, _ = pmoe.routed_experts(x, router, bias, *w, top_k=K)
        return y.sum()

    text = str(jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1)))(
        x, *w))
    assert text.count(" cond[") == switches
