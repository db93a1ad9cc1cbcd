"""kanana-2-30b-a3b-instruct-2601 (``model_type: deepseek_v3``), one chip's
share of an 8-way expert-parallel deployment, on the training path.

``build`` takes the model from the package (``gluon.model_zoo.text``,
built from the published keys) with ``experts_held`` of the configuration's
``deployment``: the router keeps its 128 outputs, 16 experts' weights live
here. ``n_routed_experts`` in ``config.json`` counts the experts held (it
is listed in ``reduced``); the published count is
``deployment.router_width``. ``reference`` is the same share in plain
float32 ``jax.numpy`` and shares nothing with ``mxnet_tpu``; the two meet
only through ``layout``.

A batch is ``x = (B, S)`` int32 token ids and ``y = (B, S)`` the ids that
follow them, drawn by a Zipf law whose exponent is the traffic's
``token_zipf_exponent``; the loss is the mean next-token cross-entropy.

Departures from the published model, also under ``assumed`` in
``config.json``: the selection bias is a buffer, zero in training and set
to ``check.routing`` for the comparison with the reference; every sequence
is full length. Before that comparison ``seed_params`` makes a second one
under the window's own routing (``routing_check``) and prints it.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np

_built = None   # the network ``build`` made last, for ``expert_load``
_HLO_TYPE = {"bfloat16": "bf16", "float32": "f32"}
_NOT_THE_MODELS = ("name", "source", "source_detail", "dtype",
                   "initializer_range")   # keys of this file, not published


# ------------------------------------------------------------- layout ----

def model_config(cfg):
    """The published keys as the package's model takes them: the router
    as wide as published, the experts held beside it."""
    dep = cfg["deployment"]
    out = {k: v for k, v in cfg.items()
           if not (isinstance(v, (dict, list)) or k in _NOT_THE_MODELS)}
    out["n_routed_experts"] = dep["router_width"]
    return out, tuple(dep["experts_held"])


def _sparse(cfg, i):
    return i >= cfg["first_k_dense_replace"]


def layout(cfg):
    """``[(name, shape, init)]`` in the order gluon lists the parameters.
    ``normal`` is N(0, initializer_range**2); ``bias`` the router's
    selection bias and ``counter`` the expert layer's load counters, both
    float32 buffers outside the gradient."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    rank, f = cfg["kv_lora_rank"], cfg["moe_intermediate_size"]
    width = cfg["deployment"]["router_width"]
    held = cfg["deployment"]["experts_held"][1]
    shared = cfg["n_shared_experts"] * f

    def mlp(prefix, inter):
        return [(f"{prefix}.gate.weight", (inter, h), "normal"),
                (f"{prefix}.up.weight", (inter, h), "normal"),
                (f"{prefix}.down.weight", (h, inter), "normal")]

    spec = [("embed.weight", (cfg["vocab_size"], h), "normal")]
    for i in range(cfg["num_hidden_layers"]):
        p = f"layer{i}"
        spec += [
            (f"{p}.input_norm.gamma", (h,), "ones"),
            (f"{p}.attn.q_proj.weight", (heads * (nope + rope), h), "normal"),
            (f"{p}.attn.kv_a_proj.weight", (rank + rope, h), "normal"),
            (f"{p}.attn.kv_a_norm.gamma", (rank,), "ones"),
            (f"{p}.attn.kv_b_proj.weight", (heads * (nope + dv), rank),
             "normal"),
            (f"{p}.attn.o_proj.weight", (h, heads * dv), "normal"),
            (f"{p}.post_attn_norm.gamma", (h,), "ones")]
        if not _sparse(cfg, i):
            spec += mlp(f"{p}.mlp", cfg["intermediate_size"])
            continue
        spec += [(f"{p}.moe.router.weight", (width, h), "normal"),
                 (f"{p}.moe.router.bias", (width,), "bias"),
                 (f"{p}.moe.experts.gate", (held, h, f), "normal"),
                 (f"{p}.moe.experts.up", (held, h, f), "normal"),
                 (f"{p}.moe.experts.down", (held, f, h), "normal"),
                 (f"{p}.moe.load_pairs", (held,), "counter"),
                 (f"{p}.moe.load_peak", (1,), "counter"),
                 (f"{p}.moe.load_calls", (1,), "counter")]
        spec += mlp(f"{p}.moe.shared", shared)
    return spec + [("norm.gamma", (h,), "ones"),
                   ("head.weight", (cfg["vocab_size"], h), "normal")]


def check_bias(cfg, nth):
    """The selection bias of the ``nth`` expert layer (0-based) for the
    comparison with the reference (``check.routing`` in ``config.json``):
    +1 on four of the held experts, another four in each layer, -1 on the
    other held ones, 0 on the absent."""
    first, held = cfg["deployment"]["experts_held"]
    bias = np.zeros(cfg["deployment"]["router_width"], np.float32)
    bias[first:first + held] = -1.0
    lead = first + (4 * nth) % held
    bias[[first + (lead - first + j) % held for j in range(4)]] = 1.0
    return bias


def make_params(cfg, seed, check=False):
    """Every weight and the selection bias (not the counters), made on
    the device in ONE jitted call from the seed, in the type it is trained
    in: ``{layout name: array}``. ``check`` sets the bias to
    ``check_bias``, else it is zero."""
    spec = [(n, s, i) for n, s, i in layout(cfg) if i != "counter"]
    dt = jnp.dtype(cfg["dtype"])
    biases = [n for n, _, i in spec if i == "bias"]

    def make(key):
        out = {}
        for j, (name, shape, init) in enumerate(spec):
            if init == "normal":
                out[name] = (jax.random.normal(jax.random.fold_in(key, j),
                                               shape, jnp.float32)
                             * cfg["initializer_range"]).astype(dt)
            elif init == "ones":
                out[name] = jnp.ones(shape, dt)
            else:
                out[name] = jnp.asarray(
                    check_bias(cfg, biases.index(name)) if check
                    else np.zeros(shape, np.float32))
        return out

    return jax.jit(make)(jax.random.PRNGKey(seed))


# ----------------------------------------------- the system under test ---

def build(cfg, ctx, seed):
    """The package's model with seeded weights on ``ctx``: selection bias
    and load counters zero."""
    global _built
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import text

    published, held = model_config(cfg)
    net = text.get_model(published["model_type"], experts_held=held,
                         **published)
    net.cast(cfg["dtype"])
    net.initialize(mx.init.Zero(), ctx=ctx)
    _set_params(net, cfg, make_params(cfg, seed))
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


def seed_params(net, cfg, seed):
    """Set every weight of ``net`` to its seeded value and the selection
    bias to ``check_bias``, for the comparison with ``reference``. The
    load counters are statistics, not weights, and stay as the window
    left them. On the way (the harness hands the network over here and
    nowhere else after the window) the same weights with the bias at zero
    are read against the reference too and printed: ``routing_check``."""
    print("# routing_check: " + json.dumps(routing_check(net, cfg, seed)),
          flush=True)
    _set_params(net, cfg, make_params(cfg, seed, check=True))


def routing_check(net, cfg, seed):
    """The logits under the WINDOW's routing (selection bias zero, every
    token choosing its own six of 128) against the reference, by token.

    The largest error over all tokens is a flipped token's (``check.reason``
    in ``config.json``) and says nothing. So the reference also gives, for
    every token and expert layer, the margin by which its choice keeps or
    leaves out each expert held (``_expert_layer``); a token whose margin
    is above ``check.settled_margin`` in every layer takes the same held
    experts on both sides (no token under half that margin was seen to
    flip), and over those tokens the largest error is rounding's again.
    ``ok`` holds it to ``check.tolerance`` and asks that the settled
    tokens be at least ``check.settled_share_min`` of all. It is printed
    beside the harness's comparison and decides nothing: ``correct`` is
    the harness's."""
    import mxnet_tpu as mx

    check = cfg["check"]
    _set_params(net, cfg, make_params(cfg, seed))
    x = check_inputs(cfg, seed, int(check["samples"]))
    net.hybridize()                 # one compiled forward, then as it was
    got = net(mx.nd.array(x, ctx=_ctx(net), dtype="int32")).asnumpy()
    net.hybridize(False)
    ref = jax.jit(lambda p, xx: reference(cfg, p, (xx, None)))(
        export_params(net, cfg), x)
    return token_errors(np.asarray(got, np.float32),
                        np.asarray(ref["logits"]),
                        np.asarray(ref["route_margin"]), check)


def _ctx(net):
    return next(iter(net.collect_params().values())).list_ctx()[0]


def token_errors(got, want, margin, check):
    """``routing_check``'s reading from the system's logits ``got`` and the
    reference's ``want`` (B, S, V) and margins (B, S, expert layers)."""
    tol = float(check["tolerance"])
    scale = max(float(np.abs(want).max()), 1e-30)
    err = np.abs(got - want).max(axis=-1) / scale            # (B, S)
    settled = margin.min(axis=-1) > float(check["settled_margin"])
    share = float(settled.mean())
    worst = float(err[settled].max()) if settled.any() else None
    return {"max_err_over_scale_settled": worst, "tolerance": tol,
            "settled_margin": float(check["settled_margin"]),
            "settled_share": share,
            "settled_share_min": float(check["settled_share_min"]),
            "tokens": int(err.size),
            "tokens_over_tolerance_share": float((err > tol).mean()),
            "token_err_p50_p90_max": [float(np.quantile(err, q))
                                      for q in (0.5, 0.9, 1.0)],
            "ok": bool(np.isfinite(got).all() and worst is not None
                       and worst <= tol
                       and share >= float(check["settled_share_min"]))}


def loss(cfg):
    from mxnet_tpu.gluon import loss as gloss

    return gloss.CausalLMLoss()


def export_params(net, cfg):
    """``{layout name: float32 numpy array}`` of the network as it is."""
    from chipbench.harness import params

    return params.export(net, [name for name, _, _ in layout(cfg)])


def expert_load():
    """The load counters of the network ``build`` made last, by layer
    (``DeepseekV3ForCausalLM.expert_load``); ``None`` before any. The
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
    """Multiply-accumulates of one token's forward pass, by part. Causal
    attention is counted as the half it is (a token sees ``seq_len / 2``
    keys on average), routed work at its expectation for the experts held
    (``top_k * held / router_width`` experts a token). Norms, rotary,
    softmax and the router's sigmoid left out."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    f, dep = cfg["moe_intermediate_size"], cfg["deployment"]
    n_sparse = sum(_sparse(cfg, i) for i in range(cfg["num_hidden_layers"]))
    n_dense = cfg["num_hidden_layers"] - n_sparse
    mla = (h * heads * qk + h * (rank + cfg["qk_rope_head_dim"])
           + rank * heads * (cfg["qk_nope_head_dim"] + dv) + heads * dv * h)
    attention = heads * (qk + dv) * seq_len / 2
    routed = (cfg["num_experts_per_tok"] * dep["experts_held"][1]
              / dep["router_width"]) * 3 * h * f
    return {
        "mla_projections": cfg["num_hidden_layers"] * mla,
        "attention": cfg["num_hidden_layers"] * attention,
        "dense_mlp": n_dense * 3 * h * cfg["intermediate_size"],
        "shared_experts": n_sparse * 3 * h * cfg["n_shared_experts"] * f,
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
    """``{"flops", "bytes", "shape"}`` of the Pallas attention calls of
    ONE training step: the forward kernel, once a layer (the backward is
    scanned XLA code, not a kernel). Operations: the S (S + 1) / 2 (query,
    key) pairs at or under the diagonal, two per multiply-accumulate,
    whatever the kernel's blocks (what a block on the diagonal computes
    above it is the kernel's cost, not the algorithm's: at 128 x 128
    blocks 3 % more, at 1024 x 1024 25 %). Bytes: every operand read and
    the output written once (a blocked kernel reads K and V again for
    every q block; that too is its cost). ``shape`` is the result shape of
    one call as the device trace names it."""
    b, s = int(traffic["global_batch"]), int(traffic["seq_len"])
    heads, layers = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    item = jnp.dtype(cfg["dtype"]).itemsize
    return {
        "flops": 2 * (s * (s + 1) // 2) * (qk + dv) * b * heads * layers,
        "bytes": (2 * qk + 2 * dv) * s * item * b * heads * layers,
        "shape": f"{_HLO_TYPE[cfg['dtype']]}[{b * heads},{s},{dv}]"}


# -------------------------------------------------------- the reference --

def _rms(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gamma


def _rotary(x, theta):
    """Interleaved rotary over (S, ..., D): pairs (x[2i], x[2i+1]) rotated
    by pos * theta**(-2i/D), written out as [real parts, imaginary
    parts]."""
    s, d = x.shape[0], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (d // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def _gated(x, gate, up, down):
    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def _attention(cfg, p, pre, h, head_block):
    """MLA of one sample ``h`` (S, hidden), ``head_block`` heads at a
    time."""
    s, heads = h.shape[0], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, theta = cfg["kv_lora_rank"], float(cfg["rope_theta"])
    q = (h @ p[f"{pre}.q_proj.weight"].T).reshape(s, heads, nope + rope)
    kv_a = h @ p[f"{pre}.kv_a_proj.weight"].T
    latent = _rms(kv_a[:, :rank], p[f"{pre}.kv_a_norm.gamma"],
                  cfg["rms_norm_eps"])
    kv = (latent @ p[f"{pre}.kv_b_proj.weight"].T).reshape(s, heads, -1)
    k_rope = _rotary(kv_a[:, rank:], theta)               # one head
    q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], theta)],
                        axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_rope[:, None, :], (s, heads, rope))], axis=-1)
    v = kv[..., nope:]
    mask = jnp.tril(jnp.ones((s, s), bool))

    def block(qkv):
        qb, kb, vb = qkv                                   # (hb, S, .)
        sc = jnp.einsum("hqd,hkd->hqk", qb, kb) / np.sqrt(nope + rope)
        pr = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", pr, vb)

    def blocks(t):                                         # (S, H, D)
        return t.transpose(1, 0, 2).reshape(
            heads // head_block, head_block, s, t.shape[-1])

    out = jax.lax.map(block, (blocks(q), blocks(k), blocks(v)))
    out = out.reshape(heads, s, -1).transpose(1, 0, 2).reshape(s, -1)
    return out @ p[f"{pre}.o_proj.weight"].T


def expert_layer(cfg, p, pre, h, experts_held):
    """The expert layer's output for ``h`` (T, hidden) as the experts
    ``experts_held = (first, count)`` give it, plus the shared experts:
    sigmoid scores over every expert, the top-k chosen by score + bias,
    weights the chosen scores renormalised and scaled, one dense pass over
    every token for each held expert."""
    return _expert_layer(cfg, p, pre, h, experts_held)[0]


def _expert_layer(cfg, p, pre, h, experts_held):
    """``expert_layer`` and (T,) how far each token's choice is from
    changing the experts held: a held expert that is chosen stays so while
    its selection score is above the first score left out, one that is not
    while it is below the last score taken; the least of these margins
    over the experts held. (Two absent experts changing places move the
    renormalising sum by their difference and nothing else.)"""
    first, held = experts_held
    top_k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(h @ p[f"{pre}.router.weight"].T)
    select = scores + p[f"{pre}.router.bias"]
    best, chosen = jax.lax.top_k(select, top_k + 1)
    last_in, first_out = best[:, top_k - 1:top_k], best[:, top_k:]
    mine, chosen = select[:, first:first + held], chosen[:, :top_k]
    margin = jnp.where(mine >= last_in, mine - first_out,
                       last_in - mine).min(axis=-1)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    w = w / (w.sum(axis=-1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]
    # (T, E): a token's weight on each expert, zero where not chosen
    per_expert = (jax.nn.one_hot(chosen, scores.shape[-1])
                  * w[..., None]).sum(axis=1)

    def one(acc, e):
        out = _gated(h, p[f"{pre}.experts.gate"][e].T,
                     p[f"{pre}.experts.up"][e].T,
                     p[f"{pre}.experts.down"][e].T)
        return acc + out * per_expert[:, first + e][:, None], None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(held))
    return routed + _gated(h, p[f"{pre}.shared.gate.weight"],
                           p[f"{pre}.shared.up.weight"],
                           p[f"{pre}.shared.down.weight"]), margin


def reference(cfg, params, batch, train=False, experts_held=None,
              head_block=4):
    """Logits (B, S, V) (and, with labels, the mean next-token
    cross-entropy) of ``batch = (x, y | None)`` in float32 at the highest
    matmul precision, one sample and ``head_block`` heads at a time so
    that the (S, S) scores fit. ``experts_held=(first, count)`` (default:
    the configuration's) gives the share of the expert layer that is
    computed; the router is as wide as its weight. ``route_margin`` (B, S,
    expert layers) is the margin of every token's choice (``_expert_layer``)."""
    del train   # no dropout anywhere
    x, y = batch
    experts_held = tuple(experts_held or cfg["deployment"]["experts_held"])
    eps = cfg["rms_norm_eps"]
    head_block = min(head_block, cfg["num_attention_heads"])

    def one_sample(p, ids):
        h, margins = p["embed.weight"][ids], []
        for i in range(cfg["num_hidden_layers"]):
            lp = f"layer{i}"
            h = h + _attention(cfg, p, f"{lp}.attn",
                               _rms(h, p[f"{lp}.input_norm.gamma"], eps),
                               head_block)
            n = _rms(h, p[f"{lp}.post_attn_norm.gamma"], eps)
            if _sparse(cfg, i):
                out, margin = _expert_layer(cfg, p, f"{lp}.moe", n,
                                            experts_held)
                h, margins = h + out, margins + [margin]
            else:
                h = h + _gated(n, p[f"{lp}.mlp.gate.weight"],
                               p[f"{lp}.mlp.up.weight"],
                               p[f"{lp}.mlp.down.weight"])
        return (_rms(h, p["norm.gamma"], eps) @ p["head.weight"].T,
                jnp.stack(margins, axis=-1))

    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        logits, margins = jax.lax.map(lambda ids: one_sample(p, ids),
                                      jnp.asarray(x))
        out = {"logits": logits, "route_margin": margins}
        if y is not None:
            picked = jnp.take_along_axis(
                logits, jnp.asarray(y).astype(jnp.int32)[..., None],
                axis=-1)[..., 0]
            out["loss"] = (jax.nn.logsumexp(logits, axis=-1) - picked).mean()
        return out
