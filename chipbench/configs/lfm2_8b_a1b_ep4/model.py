"""LFM2-8B-A1B (``model_type: lfm2_moe``), one chip's share of a 4-way
expert-parallel deployment, on the training path: published layers 0, 2,
3, 4, 5 (``deployment.layers_kept``), 8 of a layer's 32 experts, a quarter
of the vocabulary.

``build`` takes the model from the package (``gluon.model_zoo.text``, built
from the published keys) with the deployment's ``layers_kept`` and
``experts_held``: the router keeps its 32 outputs, 8 experts' weights live
here. ``num_hidden_layers`` and ``num_experts`` in ``config.json`` count
what is held (both are listed in ``reduced``); the model is told the
published counts. ``reference`` is the same share in plain float32
``jax.numpy`` and shares nothing with ``mxnet_tpu``; the two meet only
through ``layout``.

A batch is ``x = (B, S)`` int32 token ids and ``y = (B, S)`` the ids that
follow them, drawn by a Zipf law whose exponent is the traffic's
``token_zipf_exponent``; the loss is the mean next-token cross-entropy.

The selection bias (``expert_bias``) balances itself in training
(``nn.SparseMoE(bias_update_rate=)``); ``build`` settles it first, as a
checkpoint's would be (``settle``; ``assumed.settling`` in ``config.json``
says why), and the comparison with the reference replaces it by
``check.routing``. What else the published config does not carry is under
``assumed`` there too.
"""
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

_built = None   # the network ``build`` made last, for ``expert_load``
_HLO_TYPE = {"bfloat16": "bf16", "float32": "f32"}
_NOT_THE_MODELS = ("name", "source", "source_detail", "dtype",
                   "initializer_range")   # keys of this file, not published
RECENT_CALLS = 128   # rows of the expert layer's ``route_recent``
FAULTS = ("weights_float8", "expert_dropped", "gate_c_dropped",
          "taps_reversed", "rope_theta_1e4", "qk_norm_dropped",
          "embedding_norm_dropped")


# ------------------------------------------------------------- layout ----

def model_config(cfg):
    """The published keys as the package's model takes them (the
    published depth and router width, ``layer_types`` whole), and the
    share beside them: ``(published, layers_kept, experts_held)``."""
    dep = cfg["deployment"]
    out = {k: v for k, v in cfg.items()
           if not (isinstance(v, (dict, list)) or k in _NOT_THE_MODELS)}
    out["layer_types"] = list(cfg["layer_types"])
    out["num_hidden_layers"] = cfg["published"]["num_hidden_layers"]
    out["num_experts"] = dep["router_width"]
    return out, list(dep["layers_kept"]), tuple(dep["experts_held"])


def _kind(cfg, i):
    return cfg["layer_types"][i]


def _sparse(cfg, i):
    return i >= cfg["num_dense_layers"]


def _sizes(cfg):
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return h, heads, cfg["num_key_value_heads"], h // heads


def layout(cfg):
    """``[(name, shape, init)]`` in the order gluon lists the parameters (a
    block's own before its children's). ``normal`` is N(0,
    initializer_range**2), ``taps`` the convolution's U(-1/sqrt(K),
    1/sqrt(K)); ``bias`` the router's selection bias, ``rate`` the rule's
    rate and ``counter`` the expert layer's load counters: float32
    buffers outside the gradient."""
    h, heads, kv_heads, d = _sizes(cfg)
    f, width = cfg["moe_intermediate_size"], cfg["deployment"]["router_width"]
    held = cfg["deployment"]["experts_held"][1]
    spec = [("embed.weight", (cfg["vocab_size"], h), "normal")]
    for i in cfg["deployment"]["layers_kept"]:
        p = f"layer{i}"
        spec += [(f"{p}.operator_norm.gamma", (h,), "ones")]
        if _kind(cfg, i) == "conv":
            spec += [(f"{p}.conv.taps", (h, cfg["conv_L_cache"]), "taps"),
                     (f"{p}.conv.in_proj.weight", (3 * h, h), "normal"),
                     (f"{p}.conv.out_proj.weight", (h, h), "normal")]
        else:
            spec += [(f"{p}.attn.qkv_proj.weight",
                      ((heads + 2 * kv_heads) * d, h), "normal"),
                     (f"{p}.attn.q_norm.gamma", (d,), "ones"),
                     (f"{p}.attn.k_norm.gamma", (d,), "ones"),
                     (f"{p}.attn.o_proj.weight", (h, h), "normal")]
        spec += [(f"{p}.ffn_norm.gamma", (h,), "ones")]
        if not _sparse(cfg, i):
            inter = cfg["intermediate_size"]
            spec += [(f"{p}.mlp.gate.weight", (inter, h), "normal"),
                     (f"{p}.mlp.up.weight", (inter, h), "normal"),
                     (f"{p}.mlp.down.weight", (h, inter), "normal")]
            continue
        spec += [(f"{p}.moe.router.weight", (width, h), "normal"),
                 (f"{p}.moe.router.bias", (width,), "bias"),
                 (f"{p}.moe.experts.gate", (held, h, f), "normal"),
                 (f"{p}.moe.experts.up", (held, h, f), "normal"),
                 (f"{p}.moe.experts.down", (held, f, h), "normal"),
                 (f"{p}.moe.load_pairs", (held,), "counter"),
                 (f"{p}.moe.load_peak", (1,), "counter"),
                 (f"{p}.moe.load_calls", (1,), "counter"),
                 (f"{p}.moe.route_pairs", (width,), "counter"),
                 (f"{p}.moe.route_recent", (RECENT_CALLS, width), "counter"),
                 (f"{p}.moe.bias_rate", (1,), "rate")]
    return spec + [("embedding_norm.gamma", (h,), "ones")]


def check_bias(cfg, nth):
    """The selection bias of the ``nth`` expert layer (0-based) for the
    comparison with the reference (``check.routing`` in ``config.json``):
    +1 on two of the held experts, another two in each layer, -1 on the
    other held ones, 0 on the absent."""
    first, held = cfg["deployment"]["experts_held"]
    bias = np.zeros(cfg["deployment"]["router_width"], np.float32)
    bias[first:first + held] = -1.0
    bias[[first + (2 * nth + j) % held for j in range(2)]] = 1.0
    return bias


def make_params(cfg, seed, check=False):
    """Every weight made on the device in ONE jitted call from the seed, in
    the type it is trained in: ``{layout name: array}``, without the
    counters, the rate and, unless ``check`` (which sets it to
    ``check_bias``), the selection bias: those three are the network's
    own state."""
    spec = [(n, s, i) for n, s, i in layout(cfg)
            if i in ("normal", "taps", "ones") or (check and i == "bias")]
    dt = jnp.dtype(cfg["dtype"])
    biases = [n for n, _, i in spec if i == "bias"]

    def make(key):
        out = {}
        for j, (name, shape, init) in enumerate(spec):
            k = jax.random.fold_in(key, j)
            if init == "normal":
                out[name] = (jax.random.normal(k, shape, jnp.float32)
                             * cfg["initializer_range"]).astype(dt)
            elif init == "taps":
                bound = shape[1] ** -0.5
                out[name] = jax.random.uniform(
                    k, shape, jnp.float32, -bound, bound).astype(dt)
            elif init == "ones":
                out[name] = jnp.ones(shape, dt)
            else:
                out[name] = jnp.asarray(check_bias(cfg, biases.index(name)))
        return out

    return jax.jit(make)(jax.random.PRNGKey(seed))


# ----------------------------------------------- the system under test ---

def build(cfg, ctx, seed):
    """The package's model with seeded weights on ``ctx``, the selection
    bias settled (``settle``), the load counters zero."""
    global _built
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import text

    published, kept, held = model_config(cfg)
    net = text.get_model(
        published["model_type"], layers_kept=kept, experts_held=held,
        bias_update_rate=cfg["job"]["bias_update_rate"], **published)
    net.cast(cfg["dtype"])
    net.initialize(mx.init.Zero(), ctx=ctx)
    _set_params(net, cfg, make_params(cfg, seed))
    print("# routing: " + json.dumps(settle(net, cfg, seed, ctx)),
          flush=True)
    _built = net
    return net


def _set_params(net, cfg, arrays):
    from mxnet_tpu.ndarray import NDArray

    params = list(net.collect_params().values())
    spec = layout(cfg)
    if len(params) != len(spec):
        raise AssertionError(
            f"the layout lists {len(spec)} parameters, the gluon network "
            f"has {len(params)}")
    for p, (name, shape, _init) in zip(params, spec):
        if tuple(p.shape) != tuple(shape):
            raise AssertionError(f"{name}: layout {shape}, network {p.shape}")
        if name in arrays:
            p.set_data(NDArray(arrays[name]))


def imbalance(route_pairs):
    """The busiest expert's pairs over the mean expert's."""
    total = float(sum(route_pairs))
    return max(route_pairs) * len(route_pairs) / total if total else None


def settle(net, cfg, seed, ctx):
    """Move every expert layer's selection bias by the program's own rule
    until the load is even: forward passes in training mode (no gradient
    is taken) over fresh batches of the cell's generator, the rule's rate
    stepping down from ``settling.rate_first`` by ``settling.rate_decay`` a
    pass to the job's; from ``passes_min`` on, every eighth pass is read:
    settled when the busiest of each layer's experts was within
    ``settling.target`` of the mean on the batch just seen (which moved
    the bias only after it was routed: a batch not used for settling).
    Leaves the rate at the job's and the counters zero. Returns what the
    ``# routing`` line prints."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd

    st, t0 = cfg["settling"], time.perf_counter()
    u = float(cfg["job"]["bias_update_rate"])
    s, b = int(cfg["job"]["max_seq_length"]), int(st["global_batch"])
    key = jax.random.fold_in(jax.random.PRNGKey(seed), int(st["fold"]))
    layers = net.moe_layers()

    def one_pass(i, rate):
        for _, moe in layers:
            moe.bias_rate.set_data(mx.nd.array([rate], ctx=ctx))
        ids = _tokens(cfg, jax.random.fold_in(key, i), b, s,
                      st["token_zipf_exponent"])
        with autograd.train_mode():
            net(mx.nd.array(ids, ctx=ctx, dtype="int32"))

    def last_call():
        return [imbalance(rec["route_recent"][-1])
                for rec in net.expert_load().values()]

    net.hybridize()                 # one compiled forward, then as it was
    one_pass(0, 0.0)                # the load the seeded weights start from
    first, reached, passes = last_call(), None, 1
    while passes < int(st["passes_max"]):
        rate = max(u, float(st["rate_first"])
                   * float(st["rate_decay"]) ** (passes - 1))
        one_pass(passes, rate)
        passes += 1
        if passes >= int(st["passes_min"]) and passes % 8 == 0:
            reached = last_call()
            if max(reached) <= float(st["target"]):
                break
    net.hybridize(False)
    for _, moe in layers:
        moe.bias_rate.set_data(mx.nd.array([u], ctx=ctx))
    net.zero_expert_load()
    return {"passes": passes, "seconds": time.perf_counter() - t0,
            "imbalance_first": first,
            "imbalance_reached": reached or last_call(),
            "target": float(st["target"]), "rate_last": rate}


def seed_params(net, cfg, seed):
    """Set every weight of ``net`` to its seeded value and the selection
    bias to ``check_bias``, for the comparison with ``reference``. The
    load counters are statistics and stay as the window left them; on the
    way (the harness hands the network over here and nowhere else after
    the window) the first and the last ten training steps' routing is
    printed: ``routing_window``."""
    print("# routing_window: " + json.dumps(routing_window(net)), flush=True)
    _set_params(net, cfg, make_params(cfg, seed, check=True))


def routing_window(net, steps=10):
    """Per expert layer, over the first and over the last ``steps``
    training calls since the counters were zeroed (as far as the layer's
    ``route_recent`` reaches back): the calls counted, the busiest of ALL
    the router's experts over the mean, and the mean pairs a held expert
    got a call."""
    out = {}
    for i, rec in net.expert_load().items():
        recent = np.asarray(rec["route_recent"], np.float64)
        first, held = rec["first_expert"], len(rec["pairs"])
        # what fell out of the ring is not there to read
        dropped = int(rec["calls"]) - len(recent)

        def over(rows):
            if not len(rows):
                return None
            total = rows.sum(axis=0)
            return {"calls": len(rows), "imbalance": imbalance(total),
                    "pairs_per_held_expert":
                        float(total[first:first + held].mean() / len(rows)),
                    "route_pairs": total.tolist()}

        out[str(i)] = {"calls": int(rec["calls"]),
                       "first": over(recent[:max(0, steps - dropped)]),
                       "last": over(recent[-steps:])}
    return out


def loss(cfg):
    from mxnet_tpu.gluon import loss as gloss

    return gloss.CausalLMLoss()


def export_params(net, cfg):
    """``{layout name: float32 numpy array}`` of the network as it is."""
    from chipbench.harness import params

    return params.export(net, [name for name, _, _ in layout(cfg)])


def expert_load():
    """The load counters of the network ``build`` made last, by published
    layer (``Lfm2MoeForCausalLM.expert_load``); ``None`` before any. The
    mode lets go of its network when it returns and the readers run after
    that, so the module keeps it."""
    return _built.expert_load() if _built is not None else None


def _tokens(cfg, key, b, s, exponent):
    """(b, s) token ids over the vocabulary slice, id ``i`` with
    probability proportional to ``(i + 1) ** -exponent`` (Zipf's law;
    0 is uniform), by inverting the cumulative distribution."""
    p = np.arange(1, cfg["vocab_size"] + 1, dtype=np.float64) \
        ** -float(exponent)
    cdf = jnp.asarray(np.cumsum(p) / p.sum(), jnp.float32)
    ids = jnp.searchsorted(cdf, jax.random.uniform(key, (b, s)),
                           side="right")
    return jnp.minimum(ids, cfg["vocab_size"] - 1).astype(jnp.int32)


def make_batch(cfg, traffic, key):
    """One seeded training batch ``(x, y)``: x (B, S) int32 ids, y (B, S)
    the ids that follow them."""
    b, s = int(traffic["global_batch"]), int(traffic["seq_len"])
    ids = _tokens(cfg, key, b, s + 1, traffic["token_zipf_exponent"])
    return ids[:, :-1], ids[:, 1:]


def check_inputs(cfg, seed, n, seq_len=None):
    """``n`` seeded sequences for the comparison with ``reference``."""
    s = int(seq_len or cfg["job"]["max_seq_length"])
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 0xC4EC)
    return np.asarray(_tokens(cfg, key, n, s,
                              cfg["check"]["token_zipf_exponent"]))


# ------------------------------------------------------------ operations -

def forward_macs_per_token(cfg, seq_len):
    """Multiply-accumulates of one token's forward pass, by part, over the
    layers held. Causal attention is counted as the half it is (a token
    sees ``seq_len / 2`` keys on average, each a 64-wide key and a 64-wide
    value product for every query head), routed work at its expectation
    for the experts held under an even router (``top_k * held /
    router_width`` experts a token). Norms, rotary, softmax, the router's
    sigmoid and the short convolution's gates and taps left out."""
    h, heads, kv_heads, d = _sizes(cfg)
    dep = cfg["deployment"]
    kept = dep["layers_kept"]
    n_conv = sum(_kind(cfg, i) == "conv" for i in kept)
    n_attn = len(kept) - n_conv
    n_sparse = sum(_sparse(cfg, i) for i in kept)
    routed = (cfg["num_experts_per_tok"] * dep["experts_held"][1]
              / dep["router_width"]) * 3 * h * cfg["moe_intermediate_size"]
    return {
        "conv_projections": n_conv * (h * 3 * h + h * h),
        "attention_projections": n_attn * (h * (heads + 2 * kv_heads) * d
                                           + h * h),
        "attention": n_attn * heads * 2 * d * seq_len / 2,
        "dense_mlp": (len(kept) - n_sparse) * 3 * h
        * cfg["intermediate_size"],
        "routed_experts": n_sparse * routed,
        "router": n_sparse * h * dep["router_width"],
        "head": h * cfg["vocab_size"]}


def flops_per_sample(cfg, traffic):
    """Model operations per sequence: two per multiply-accumulate; a
    training step is forward plus backward (twice the forward), nothing
    recomputed (the flash backward's and the expert layer's recomputation
    are the program's choice and do not count)."""
    s = int(traffic["seq_len"])
    passes = 3 if traffic.get("kind", "train") == "train" else 1
    return 2 * sum(forward_macs_per_token(cfg, s).values()) * s * passes


def attention_kernel_cost(cfg, traffic):
    """``{"flops", "bytes", "shape"}`` of the Pallas attention FORWARD
    calls of one training step, one an attention layer held. Operations:
    the S (S + 1) / 2 (query, key) pairs at or under the diagonal, a
    64-wide key and a 64-wide value product each for every query head,
    two per multiply-accumulate, whatever the kernel's blocks. Bytes:
    every operand read and the output written once (grouped keys: a key
    head is counted once, not once a query head). ``shape`` is the result
    shape of one call as the device trace names it; the backward's first
    result is dK, (B x key heads, S, 64)."""
    b, s = int(traffic["global_batch"]), int(traffic["seq_len"])
    _, heads, kv_heads, d = _sizes(cfg)
    item = jnp.dtype(cfg["dtype"]).itemsize
    layers = sum(_kind(cfg, i) == "full_attention"
                 for i in cfg["deployment"]["layers_kept"])
    return {
        "flops": 2 * (s * (s + 1) // 2) * 2 * d * b * heads * layers,
        "bytes": (2 * heads + 2 * kv_heads) * d * s * item * b * layers,
        "shape": f"{_HLO_TYPE[cfg['dtype']]}[{b * heads},{s},{d}]"}


def short_conv_cost(cfg, traffic):
    """``{"bytes", "scope"}`` of what lies between a short convolution's
    two projections (gate, taps, gate: the program's scope ``sconv.gate``)
    over ONE training step and the conv layers held. A few products an
    element and no matmul, and ``peaks.json`` has no vector peak, so BYTES
    bound it: the forward reads the three streams b, c, x and writes the
    gated result (4 arrays of (B, S, hidden)); the backward reads the
    result's cotangent and the three streams and writes their three
    cotangents (7); each once, in the configuration's type. The taps (3 a
    channel) and their gradient are not counted."""
    b, s = int(traffic["global_batch"]), int(traffic["seq_len"])
    item = jnp.dtype(cfg["dtype"]).itemsize
    layers = sum(_kind(cfg, i) == "conv"
                 for i in cfg["deployment"]["layers_kept"])
    return {"bytes": (4 + 7) * b * s * cfg["hidden_size"] * item * layers,
            "scope": "sconv.gate"}


# -------------------------------------------------------- the reference --

def _rms(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gamma


def _rotate_half(x, theta):
    """Rotary positions over (S, H, D): with (a, b) the two halves of the
    last axis and angle ``pos * theta**(-2i/D)``, ``[a cos - b sin, b cos +
    a sin]``."""
    s, d = x.shape[0], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (jnp.arange(s, dtype=jnp.float32)[:, None] * freq)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def _gated(x, gate, up, down):
    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def _short_conv(cfg, p, pre, u, fault):
    """One sample ``u`` (S, hidden): the convolution written out tap by
    tap."""
    h, taps = cfg["hidden_size"], cfg["conv_L_cache"]
    bcx = u @ p[f"{pre}.in_proj.weight"].T
    b, c, x = bcx[:, :h], bcx[:, h:2 * h], bcx[:, 2 * h:]
    z = jnp.pad(b * x, ((taps - 1, 0), (0, 0)))
    w = p[f"{pre}.taps"]
    if fault == "taps_reversed":
        w = w[:, ::-1]
    v = sum(z[k:k + u.shape[0]] * w[:, k] for k in range(taps))
    if fault != "gate_c_dropped":
        v = c * v
    return v @ p[f"{pre}.out_proj.weight"].T


def _attention(cfg, p, pre, u, head_block, fault):
    """Grouped-query attention of one sample ``u`` (S, hidden), masked
    dense softmaxes ``head_block`` query heads at a time."""
    s = u.shape[0]
    _, heads, kv_heads, d = _sizes(cfg)
    theta = 1e4 if fault == "rope_theta_1e4" else float(cfg["rope_theta"])
    qkv = u @ p[f"{pre}.qkv_proj.weight"].T
    q = qkv[:, :heads * d].reshape(s, heads, d)
    k = qkv[:, heads * d:(heads + kv_heads) * d].reshape(s, kv_heads, d)
    v = qkv[:, (heads + kv_heads) * d:].reshape(s, kv_heads, d)
    if fault != "qk_norm_dropped":
        q = _rms(q, p[f"{pre}.q_norm.gamma"], cfg["norm_eps"])
        k = _rms(k, p[f"{pre}.k_norm.gamma"], cfg["norm_eps"])
    q, k = _rotate_half(q, theta), _rotate_half(k, theta)
    # key head j serves query heads j g .. j g + g - 1: one copy each
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    mask = jnp.tril(jnp.ones((s, s), bool))

    def block(qkv_):
        qb, kb, vb = qkv_                                  # (hb, S, d)
        sc = jnp.einsum("hqd,hkd->hqk", qb, kb) / math.sqrt(d)
        pr = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", pr, vb)

    def blocks(t):                                         # (S, H, d)
        return t.transpose(1, 0, 2).reshape(
            heads // head_block, head_block, s, d)

    out = jax.lax.map(block, (blocks(q), blocks(k), blocks(v)))
    out = out.reshape(heads, s, d).transpose(1, 0, 2).reshape(s, heads * d)
    return out @ p[f"{pre}.o_proj.weight"].T


def expert_layer(cfg, p, pre, h, experts_held, fault=None):
    """The expert layer's output for ``h`` (T, hidden) as the experts
    ``experts_held = (first, count)`` give it (there is no shared expert):
    sigmoid scores over every expert, the top-k chosen by score + bias,
    weights the chosen scores over (their sum + 1e-6), scaled, one dense
    pass over every token for each held expert. ``expert_dropped`` leaves
    out the first of them."""
    first, held = experts_held
    scores = jax.nn.sigmoid(h @ p[f"{pre}.router.weight"].T)
    _, chosen = jax.lax.top_k(scores + p[f"{pre}.router.bias"],
                              cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-6)
    w = w * cfg["routed_scaling_factor"]
    # (T, E): a token's weight on each expert, zero where not chosen
    per_expert = (jax.nn.one_hot(chosen, scores.shape[-1])
                  * w[..., None]).sum(axis=1)

    def one(acc, e):
        out = _gated(h, p[f"{pre}.experts.gate"][e].T,
                     p[f"{pre}.experts.up"][e].T,
                     p[f"{pre}.experts.down"][e].T)
        return acc + out * per_expert[:, first + e][:, None], None

    start = 1 if fault == "expert_dropped" else 0
    routed, _ = jax.lax.scan(one, jnp.zeros_like(h),
                             jnp.arange(start, held))
    return routed


def reference(cfg, params, batch, train=False, experts_held=None,
              head_block=4, fault=None):
    """Logits (B, S, V) (and, with labels, the mean next-token
    cross-entropy) of ``batch = (x, y | None)`` in float32 at the highest
    matmul precision, one sample and ``head_block`` query heads at a time
    so that the (S, S) scores fit. ``experts_held=(first, count)``
    (default: the configuration's) gives the share of the expert layer
    that is computed; the router is as wide as its weight. The selection
    bias is read as the parameters give it and never moved. ``fault`` (one
    of ``FAULTS``) computes one thing wrong, for the readings that set the
    tolerance: ``weights_float8`` rounds every matrix and the experts to
    float8 (e4m3), ``expert_dropped`` leaves a held expert out of every
    layer, ``gate_c_dropped`` the short convolution's second gate,
    ``taps_reversed`` turns its taps around, ``rope_theta_1e4`` rotates by
    theta 10,000, ``qk_norm_dropped`` and ``embedding_norm_dropped`` leave
    out the norms they name."""
    del train   # no dropout anywhere; the bias's rule is the system's
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no fault {fault!r} (known: {FAULTS})")
    x, y = batch
    experts_held = tuple(experts_held or cfg["deployment"]["experts_held"])
    eps = cfg["norm_eps"]
    heads = cfg["num_attention_heads"]
    head_block = max(b for b in range(1, min(head_block, heads) + 1)
                     if heads % b == 0)

    def one_sample(p, ids):
        h = p["embed.weight"][ids]
        for i in cfg["deployment"]["layers_kept"]:
            lp = f"layer{i}"
            u = _rms(h, p[f"{lp}.operator_norm.gamma"], eps)
            if _kind(cfg, i) == "conv":
                h = h + _short_conv(cfg, p, f"{lp}.conv", u, fault)
            else:
                h = h + _attention(cfg, p, f"{lp}.attn", u, head_block,
                                   fault)
            n = _rms(h, p[f"{lp}.ffn_norm.gamma"], eps)
            if _sparse(cfg, i):
                h = h + expert_layer(cfg, p, f"{lp}.moe", n, experts_held,
                                     fault)
            else:
                h = h + _gated(n, p[f"{lp}.mlp.gate.weight"],
                               p[f"{lp}.mlp.up.weight"],
                               p[f"{lp}.mlp.down.weight"])
        if fault != "embedding_norm_dropped":
            h = _rms(h, p["embedding_norm.gamma"], eps)
        return h @ p["embed.weight"].T

    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        if fault == "weights_float8":
            p = {k: v.astype(jnp.float8_e4m3fn).astype(jnp.float32)
                 if v.ndim >= 2 and not k.endswith(("taps", "route_recent"))
                 else v for k, v in p.items()}
        logits = jax.lax.map(lambda ids: one_sample(p, ids), jnp.asarray(x))
        out = {"logits": logits}
        if y is not None:
            picked = jnp.take_along_axis(
                logits, jnp.asarray(y).astype(jnp.int32)[..., None],
                axis=-1)[..., 0]
            out["loss"] = (jax.nn.logsumexp(logits, axis=-1) - picked).mean()
        return out
