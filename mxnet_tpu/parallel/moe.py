"""Expert parallelism (ep axis): mixture-of-experts layer.

Beyond the reference (MXNet 1.x has no MoE): experts are partitioned
across the ``ep`` mesh axis — each device owns one expert's parameters
(stacked pytree, leading dim = experts) — inside ONE jitted SPMD program.
Top-1 routing follows the Switch-Transformer recipe: a linear router
scores tokens, each token goes to its argmax expert, the expert output is
scaled by the router probability (keeps routing differentiable), and a
load-balancing auxiliary loss penalizes expert collapse.

Combine strategy: each device computes its expert on the full token set
masked to its assignment, and a `psum` over ep merges the disjoint
results — the dense-dispatch formulation, which on TPU is one all-reduce
over ICI and no host-side gather/scatter. (All-to-all token dispatch is a
bandwidth optimization of the same math for when experts dominate
compute.)

    fn = moe_apply(expert_fn, mesh)
    y, aux_loss = fn(stacked_expert_params, router_w, x)

``moe_apply`` is that top-1, one-expert-per-device formulation. The layer
today's sparse models use (top-k of many experts, several held per
device, work that follows the rows routed here, no capacity and no
dropped token) is :func:`routed_experts` below; ``gluon.nn.SparseMoE`` is
the block over it.
"""
from __future__ import annotations

__all__ = ["moe_apply", "stack_expert_params", "route_topk",
           "routed_experts", "buffer_rungs"]

import functools
import math

from .pipeline import _check_stacked_leading_dim
from .pipeline import stack_stage_params as stack_expert_params


def moe_apply(expert_fn, mesh, axis="ep"):
    """Build the expert-parallel MoE callable (top-1, one expert per
    device, every device computes every token; for top-k over many
    experts with several held per device see :func:`routed_experts` and
    ``gluon.nn.SparseMoE``).

    Parameters
    ----------
    expert_fn : (params_slice, x) -> y — one expert, same output shape.
    mesh : DeviceMesh with an ``ep`` axis; its size = number of experts.

    Returns
    -------
    fn(stacked_params, router_w, x) -> (y, aux_loss) where x is (N, d),
    router_w is (d, E), y is (N, d_out); aux_loss is the Switch
    load-balancing term (scalar, add it to the training loss).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    jmesh = mesh.jax_mesh
    num_experts = mesh.size(axis)

    def local(params, router_w, x):
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        e = jax.lax.axis_index(axis)
        logits = x @ router_w                       # (N, E) replicated
        probs = jax.nn.softmax(logits, axis=-1)
        assigned = jnp.argmax(probs, axis=-1)       # (N,)
        mine = (assigned == e)                      # (N,) this device's tokens
        gate = jnp.where(mine, jnp.max(probs, axis=-1), 0.0)  # (N,)
        y = expert_fn(params, x)                    # (N, d_out)
        y = y * gate[:, None]
        y = jax.lax.psum(y, axis)                   # disjoint merge
        # Switch aux loss: E * sum_e fraction_e * mean_prob_e — each device
        # contributes its own expert's f_e * P_e term, summed over ep
        frac_e = jnp.mean(mine.astype(jnp.float32))
        mean_p_e = jnp.mean(probs, axis=0)[e]
        aux = num_experts * jax.lax.psum(frac_e * mean_p_e, axis)
        return y, aux

    sharded = jax.shard_map(local, mesh=jmesh,
                        in_specs=(P(axis), P(), P()),
                        out_specs=(P(), P()))

    @jax.jit
    def run(stacked_params, router_w, x):
        _check_stacked_leading_dim(stacked_params, num_experts, "ep")
        if router_w.shape[-1] != num_experts:
            # silently-dropped experts otherwise: tokens routed past
            # column E match no device and psum to zero rows
            raise ValueError(
                f"router_w has {router_w.shape[-1]} expert columns but "
                f"the ep axis has {num_experts} devices")
        y, aux = sharded(stacked_params, router_w, x)
        return y, jnp.reshape(aux, ())

    return run


# ---------------------------------------------- sparse top-k expert layer --

def route_topk(x, router_w, bias, top_k, scale, norm_topk=True,
               norm_eps=1e-20, route_counts=False, scoring="sigmoid"):
    """Sigmoid router with a selection bias (the ``noaux_tc`` recipe of
    DeepSeek-V3, one group): ``s = sigmoid(x W^T)`` in float32 over every
    expert; the ``top_k`` experts are chosen by ``s + bias``; their weights
    are ``s`` alone (the bias steers the choice and nothing else),
    renormalised to sum to one where ``norm_topk`` (over ``sum +
    norm_eps``: ``lfm2_moe`` publishes 1e-6), times ``scale``.

    ``scoring="softmax"`` is the Qwen-MoE router: ``p = softmax(x W^T)``
    in float32 over every expert, the ``top_k`` chosen by ``p`` (``bias``
    is not read and may be None), their weights ``p`` over their sum where
    ``norm_topk`` (``norm_topk_prob``, no eps), times ``scale``.

    x (T, h), router_w (E, h), bias (E,) -> (ids (T, k) int32,
    weights (T, k) float32), and with ``route_counts`` a third: (E,) float32, the
    (token, expert) pairs each expert of the WHOLE router got, which is
    what a rule that moves the bias by the load reads."""
    import jax
    import jax.numpy as jnp

    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32).T)
    if scoring == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
        _, ids = jax.lax.top_k(s, top_k)
        w = jnp.take_along_axis(s, ids, axis=-1)
        if norm_topk:
            w = w / w.sum(axis=-1, keepdims=True)
    elif scoring == "sigmoid":
        s = jax.nn.sigmoid(logits)
        _, ids = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
        w = jnp.take_along_axis(s, ids, axis=-1)
        if norm_topk:
            w = w / (w.sum(axis=-1, keepdims=True) + norm_eps)
    else:
        raise ValueError(f"no router scoring {scoring!r} (sigmoid, softmax)")
    ids = ids.astype(jnp.int32)
    if not route_counts:
        return ids, w * scale
    # a compare and a sum (E is tens): no scatter
    pairs = (ids.reshape(-1, 1) == jnp.arange(s.shape[-1], dtype=jnp.int32))
    return ids, w * scale, pairs.sum(axis=0, dtype=jnp.float32)


def _pair_gathers(top_k):
    """The two gathers between token order and expert order, each with a
    gather for its transpose. ``order`` is a permutation of the T * top_k
    (token, choice) pairs and ``inv`` its inverse, so every cotangent row
    is found by index and nothing has to be scatter-added (a row-wise
    scatter-add serialises on the TPU, a gather does not)."""
    import jax

    @jax.custom_vjp
    def to_experts(x, order, inv):
        # sorted row r is pair order[r], of token order[r] // top_k
        return x[order // top_k]

    def to_experts_fwd(x, order, inv):
        return x[order // top_k], inv

    def to_experts_bwd(inv, g):
        import jax.numpy as jnp

        per_token = g[inv].reshape(-1, top_k, g.shape[-1])
        return (per_token.astype(jnp.float32).sum(axis=1).astype(g.dtype),
                None, None)

    to_experts.defvjp(to_experts_fwd, to_experts_bwd)

    @jax.custom_vjp
    def to_tokens(ys, order, inv):
        return ys[inv]

    def to_tokens_fwd(ys, order, inv):
        return ys[inv], order

    def to_tokens_bwd(order, g):
        return g[order], None, None

    to_tokens.defvjp(to_tokens_fwd, to_tokens_bwd)
    return to_experts, to_tokens


# a rung below every pair is a multiple of this many rows (the grouped
# product ran slower a call on rows that are multiples of 128 alone,
# PR 38); every rung is another copy of the layer's kernels in the step's
# executable, which a warm start loads: kanana's warm setup read +1.1 s
# with 3 rungs and +3.6 s with 4 against a 10 % bound (PR 38)
ROW_TILE = 1024
MAX_RUNGS = 3


def buffer_rungs(tokens, top_k, held, experts):
    """The row counts the expert-ordered buffer of :func:`routed_experts`
    may take, smallest first, from the shapes alone: ``tokens * top_k``
    pairs, ``held`` of a router ``experts`` wide.

    An even router sends ``e = tokens * top_k * held / experts`` pairs to
    the experts held. The first rung is ``e + e / 16`` (a router that
    balances itself hovers around ``e``, a hair above as often as under),
    the last is every pair, and the ones between grow by one factor, so a
    live count anywhere between them fills at least ``1 / factor`` of its
    rung; each is rounded up to ``ROW_TILE``. With every expert held every
    pair is live: one rung.

    At the expert cells' shapes: ``lfm2`` (8,192 tokens, top-4, 8 of 32)
    9,216 17,408 32,768; ``kanana`` (8,192, top-6, 16 of 128) 7,168
    18,432 49,152.
    """
    rows = tokens * top_k
    even = -(-rows * held // experts)
    first = even + even // 16
    if first >= rows:
        return (rows,)
    factor = (rows / first) ** (1 / (MAX_RUNGS - 1))
    rungs = {min(rows, -(-math.ceil(first * factor ** i) // ROW_TILE)
                 * ROW_TILE) for i in range(MAX_RUNGS - 1)}
    return tuple(sorted(rungs | {rows}))


def _slots(rows, top_k, inv, live):
    """Where each (token, choice) pair sits in a buffer of the first
    ``rows`` pairs in expert order, (T, top_k), and whether it is live
    there: a dead pair, or one ranked at or past ``rows``, reads the last
    row through the clamped index and is selected away."""
    import jax.numpy as jnp

    return (jnp.minimum(inv, rows - 1).reshape(-1, top_k),
            (inv < live).reshape(-1, top_k))


def _kernel_products(a, b, sizes, mode):
    """The grouped products of the first rung: the ``grouped_matmul``
    family, whose kernel side is traced once a distinct shape."""
    from .. import kernels

    return kernels.dispatch("grouped_matmul", a, b, sizes, mode=mode)


def _rows_forward(rows, top_k, grouped, x, wk, w_gate, w_up, w_down, order,
                  inv, sizes):
    """The held experts' part of the layer, (T, h) float32, with the
    expert-ordered buffer ``rows`` long (``order[:rows]``): every array
    between the gather into expert order and the combine has ``rows`` rows.
    ``grouped(a, b, sizes, mode)`` is the grouped product
    (``kernels.grouped_matmul``'s three modes). It leaves the rows past the
    live count undefined and nothing reads them. The combine sums each
    token's ``top_k`` rows with their weights in float32, reading them
    where they lie: no (T * top_k, h) copy in token order."""
    import jax
    import jax.numpy as jnp

    xs = x[order[:rows] // top_k]
    gate = grouped(xs, w_gate, sizes, "nn")
    up = grouped(xs, w_up, sizes, "nn")
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(xs.dtype) * up
    ys = grouped(act, w_down, sizes, "nn")
    at, kept = _slots(rows, top_k, inv, sizes.sum())
    return sum(jnp.where(kept[:, j, None],
                         wk[:, j, None] * ys[at[:, j]].astype(jnp.float32),
                         0.0) for j in range(top_k))


def _rows_backward(rows, top_k, grouped, g, x, wk, w_gate, w_up, w_down,
                   order, inv, sizes):
    """The cotangents of :func:`_rows_forward`'s ``(x, wk, w_gate, w_up,
    w_down)`` for ``g`` (T, h), from the two products recomputed up to the
    activation. The down product's input cotangent ``u = g W_down^T`` is
    taken once, row by row in expert order, and gives both the
    activation's (``wk u``) and the router weights' (``<act, u>``, which is
    ``<act W_down, g>``): the down product itself is not recomputed. Every
    gather reads by index, none scatters (a row-wise scatter-add
    serialises on the TPU); x's cotangent sums each token's live rows."""
    import jax
    import jax.numpy as jnp

    f32, dt = jnp.float32, x.dtype

    def product_t(a, w):
        return grouped(a, w, sizes, "nt")

    def weight_t(a, b):
        return grouped(a, b, sizes, "tn")

    pair = order[:rows]
    tok = pair // top_k
    xs = x[tok]
    gate = grouped(xs, w_gate, sizes, "nn").astype(f32)
    up = grouped(xs, w_up, sizes, "nn")
    sig = jax.nn.sigmoid(gate)
    silu = (gate * sig).astype(dt)
    act = silu * up
    # the cotangent of a result routed_experts rounds to x's type: gathered
    # in that type, exactly
    gr = g.astype(dt)[tok]
    w_r = wk.reshape(-1)[pair][:, None]
    u = product_t(gr, w_down).astype(f32)
    dact = w_r * u
    d_up = (dact * silu.astype(f32)).astype(dt)
    d_gate = (dact * up.astype(f32) * sig * (1 + gate * (1 - sig))
              ).astype(dt)
    dxs = product_t(d_gate, w_gate) + product_t(d_up, w_up)
    at, kept = _slots(rows, top_k, inv, sizes.sum())
    dx = sum(jnp.where(kept[:, j, None], dxs[at[:, j]].astype(f32), 0.0)
             for j in range(top_k))
    dw = (act.astype(f32) * u).sum(-1)
    return (dx.astype(dt), jnp.where(kept, dw[at], 0.0),
            weight_t(xs, d_gate), weight_t(xs, d_up),
            weight_t(act, (w_r * gr.astype(f32)).astype(dt)))


@functools.lru_cache(maxsize=None)
def _ladder(top_k, rungs):
    """``layer(x, wk, w_gate, w_up, w_down, order, inv, sizes)``:
    :func:`_rows_forward` at the smallest of ``rungs`` that holds
    ``sizes.sum()`` rows, one branch of a ``jax.lax.switch`` a rung. Like
    ``jax.checkpoint`` it keeps its inputs alone; the backward pass runs
    the chosen rung's :func:`_rows_backward` in a second switch.
    Differentiating the switch itself would make every branch hand out the
    residuals of all the others, zeros at every rung's size. Each rung is
    jitted: traced and lowered once a program, not once a layer.

    The first rung's grouped products are the ``grouped_matmul`` family
    (on the TPU a Pallas kernel, traced and lowered once a distinct shape);
    the rungs above it take the overflow of an uneven router and keep
    ``jax.lax.ragged_dot``, which adds no kernel a start has to lower: a
    rung of Pallas kernels adds its own tracing and lowering to every warm
    start (PERF.md section 6)."""
    import jax
    import jax.numpy as jnp

    from ..kernels.grouped_matmul import grouped_matmul_xla

    def rung_of(fn, rows):
        grouped = _kernel_products if rows == rungs[0] else grouped_matmul_xla
        body = functools.partial(fn, rows, top_k, grouped)
        # inlined into the caller's program, never an executable of its own
        return jax.jit(body)  # noqa: raw-jit

    forwards = [rung_of(_rows_forward, rows) for rows in rungs]
    backwards = [rung_of(_rows_backward, rows) for rows in rungs]

    def rung(sizes):
        return (sizes.sum() > jnp.asarray(rungs[:-1], jnp.int32)).sum()

    def forward(*args):
        return jax.lax.switch(rung(args[-1]), forwards, *args)

    def backward(args, g):
        return (*jax.lax.switch(rung(args[-1]), backwards, g, *args),
                None, None, None)

    layer = jax.custom_vjp(forward)
    layer.defvjp(lambda *args: (forward(*args), args), backward)
    return layer


def routed_experts(x, router_w, bias, w_gate, w_up, w_down, *, top_k,
                   first_expert=0, scale=1.0, norm_topk=True,
                   norm_eps=1e-20, route_counts=False, scoring="sigmoid"):
    """The part of a sparse expert layer that the experts held here give.

    ``x`` is (T, h). The router is as wide as the model (``router_w``
    (E, h), ``bias`` (E,), None under ``scoring="softmax"``:
    :func:`route_topk`); this device holds the ``n`` experts
    ``first_expert .. first_expert + n - 1`` as stacked gated-SiLU MLPs:
    ``w_gate``/``w_up`` (n, h, f), ``w_down`` (n, f, h). Every token is
    routed over all E experts; of its ``top_k`` (token, expert) pairs the
    ones that fall on a held expert are computed and summed with their
    router weights, the others are left to the devices that hold them
    (with every expert held this is the whole layer). On one device
    nothing is exchanged.

    The pairs are sorted by expert, held groups first, and multiplied
    group by group (a grouped-matmul kernel whose work follows the rows
    of each group: ``kernels.grouped_matmul`` in the buffer's first rung,
    ``jax.lax.ragged_dot`` elsewhere). There is no
    capacity and no token is ever dropped, however uneven the routing.
    With every expert held every pair is live and the buffer has T *
    top_k rows. Otherwise the buffer is the smallest rung of
    :func:`buffer_rungs` that holds the live pairs, chosen in the step
    (``jax.lax.switch``), the last rung being every pair; the gathers, the
    activation and the combine follow its rows, and the grouped products
    skip the rows past the live pairs. The combine reads each token's
    rows where they lie (:func:`_rows_forward`).

    Returns ``(y (T, h) in x's type, load (n,) float32)``: ``load[e]`` is
    the number of pairs routed to held expert ``e`` in this call; with
    ``route_counts`` a third, (E,) float32: the pairs every expert of the
    router got (``route_topk``), held here or not.
    """
    import jax
    import jax.numpy as jnp

    t, h = x.shape
    n = w_gate.shape[0]
    rungs = buffer_rungs(t, top_k, n, router_w.shape[0])
    with jax.named_scope("moe.route"):
        ids, w, *counts = route_topk(x, router_w, bias, top_k, scale,
                                     norm_topk, norm_eps, route_counts,
                                     scoring)
        local = ids - first_expert
        held = (local >= 0) & (local < n)
        # pairs on absent experts sort behind every held group
        group = jnp.where(held, local, n).reshape(-1)          # (T*k,)
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=jnp.int32))
        # the held groups' sizes: the whole router's counts hold them
        sizes = (counts[0][first_expert:first_expert + n] if counts
                 else jnp.bincount(group, length=n + 1)[:n]
                 ).astype(jnp.int32)
    if len(rungs) > 1:
        with jax.named_scope("moe.experts"):
            y = _ladder(top_k, rungs)(
                x, jnp.where(held, w, 0.0), w_gate, w_up, w_down, order,
                inv, sizes)
        return (y.astype(x.dtype), sizes.astype(jnp.float32), *counts)
    with jax.named_scope("moe.route"):
        live = (jnp.arange(t * top_k) < sizes.sum())[:, None]
    to_experts, to_tokens = _pair_gathers(top_k)

    def experts(x, w_gate, w_up, w_down):
        # what the grouped product leaves in the rows it skips is not
        # defined, forward or backward: masked on the way in (for the
        # cotangent of x) and on the way out
        xs = jnp.where(live, to_experts(x, order, inv), 0)
        gate = jax.lax.ragged_dot(xs, w_gate, sizes)
        up = jax.lax.ragged_dot(xs, w_up, sizes)
        act = jax.nn.silu(gate.astype(jnp.float32)).astype(xs.dtype) * up
        ys = jax.lax.ragged_dot(act, w_down, sizes)
        return to_tokens(jnp.where(live, ys, 0), order, inv)

    with jax.named_scope("moe.experts"):
        # the expert-ordered copies (T * top_k rows of h, and of f thrice)
        # are rebuilt in the backward pass from x and the permutation
        # rather than kept: they would be most of the layer's activations
        ys = jax.checkpoint(experts)(x, w_gate, w_up, w_down)
        wk = jnp.where(held, w, 0.0)[:, :, None]
        y = (ys.reshape(t, top_k, h).astype(jnp.float32) * wk).sum(axis=1)
    return (y.astype(x.dtype), sizes.astype(jnp.float32), *counts)
