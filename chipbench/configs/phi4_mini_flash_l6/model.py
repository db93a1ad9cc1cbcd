"""Phi-4-mini-flash-reasoning (``model_type: phi4flash``), one pipeline
stage of six layers on the training path: published layers 0, 1, 16, 17,
18, 19 (``layers_kept``), an eighth of the vocabulary.

``build`` takes the model from the package (``gluon.model_zoo.text``, built
from the published keys) with the configuration's ``layers_kept``;
``num_hidden_layers`` in ``config.json`` counts the layers held (it is
listed in ``reduced``) and the model is told the published count, which is
what gives every layer its kind. ``reference`` is the same six layers in
plain float32 ``jax.numpy`` and shares nothing with ``mxnet_tpu``; the two
meet only through ``layout``.

A batch is ``x = (B, S)`` int32 token ids and ``y = (B, S)`` the ids that
follow them, drawn by a Zipf law whose exponent is the traffic's
``token_zipf_exponent``; the loss is the mean next-token cross-entropy.

What the published config does not carry (Mamba's sizes and
initialisation, which heads pair, the attention biases) is under
``assumed`` in ``config.json``.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

_HLO_TYPE = {"bfloat16": "bf16", "float32": "f32"}
_NOT_THE_MODELS = ("name", "source", "source_detail", "dtype",
                   "initializer_range")   # keys of this file, not published
D_STATE, D_CONV, EXPAND = 16, 4, 2          # config.json, assumed.mamba
FAULTS = ("state_bf16", "dt_bf16", "window_off", "lam_dropped",
          "memory_after_gate", "cross_own_kv", "weights_float8")


# ------------------------------------------------------------- layout ----

def model_config(cfg):
    """The published keys as the package's model takes them: the published
    layer count, the layers held beside it."""
    out = {k: v for k, v in cfg.items()
           if not (isinstance(v, (dict, list)) or k in _NOT_THE_MODELS)}
    out["num_hidden_layers"] = cfg["published"]["num_hidden_layers"]
    return out, list(cfg["layers_kept"])


def kind_of(cfg, index):
    """The mixer of PUBLISHED layer ``index``: Mamba up to the middle layer
    (which also makes the memory), window attention between them, full
    attention right after the middle, then gated memory units and
    cross-attention in turn."""
    half = cfg["published"]["num_hidden_layers"] // 2
    if index % 2 == 0:
        return "mamba" if index < half else \
            "mamba_memory" if index == half else "gmu"
    return "window" if index < half else \
        "full" if index == half + 1 else "cross"


def _sizes(cfg):
    h = cfg["hidden_size"]
    d = h // cfg["num_attention_heads"]
    return h, d, cfg["num_key_value_heads"] * d, EXPAND * h, -(-h // 16)


def layout(cfg):
    """``[(name, shape, init)]`` in the order gluon lists the parameters (a
    block's own before its children's). Inits: ``normal`` N(0,
    initializer_range**2); ``lam`` N(0, 0.1**2) float32; ``a_log``, ``dt_w``,
    ``dt_b``, ``conv`` as ``assumed.mamba_init`` says."""
    h, d, kv, inner, rank = _sizes(cfg)
    inter = cfg["intermediate_size"]

    def norm(prefix):
        return [(f"{prefix}.gamma", (h,), "ones"),
                (f"{prefix}.beta", (h,), "zeros")]

    spec = [("embed.weight", (cfg["vocab_size"], h), "normal")]
    for i in cfg["layers_kept"]:
        p, kind = f"layer{i}", kind_of(cfg, i)
        spec += norm(f"{p}.input_norm")
        if kind in ("mamba", "mamba_memory"):
            spec += [(f"{p}.ssm.conv.weight", (inner, D_CONV), "conv"),
                     (f"{p}.ssm.conv.bias", (inner,), "conv"),
                     (f"{p}.ssm.dt.weight", (inner, rank), "dt_w"),
                     (f"{p}.ssm.dt.bias", (inner,), "dt_b"),
                     (f"{p}.ssm.a_log", (inner, D_STATE), "a_log"),
                     (f"{p}.ssm.d_skip", (inner,), "ones32"),
                     (f"{p}.ssm.in_proj.weight", (2 * inner, h), "normal"),
                     (f"{p}.ssm.x_proj.weight", (rank + 2 * D_STATE, inner),
                      "normal"),
                     (f"{p}.ssm.out_proj.weight", (h, inner), "normal")]
        elif kind == "gmu":
            spec += [(f"{p}.gmu.in_proj.weight", (inner, h), "normal"),
                     (f"{p}.gmu.out_proj.weight", (h, inner), "normal")]
        else:
            spec += [(f"{p}.attn.{n}", (d,), "lam")
                     for n in ("lam_q1", "lam_k1", "lam_q2", "lam_k2")]
            spec += [(f"{p}.attn.subln.gamma", (2 * d,), "ones32"),
                     (f"{p}.attn.q_proj.weight", (h, h), "normal"),
                     (f"{p}.attn.q_proj.bias", (h,), "zeros")]
            if kind != "cross":
                spec += [(f"{p}.attn.kv_proj.weight", (2 * kv, h), "normal"),
                         (f"{p}.attn.kv_proj.bias", (2 * kv,), "zeros")]
            spec += [(f"{p}.attn.o_proj.weight", (h, h), "normal"),
                     (f"{p}.attn.o_proj.bias", (h,), "zeros")]
        spec += norm(f"{p}.post_norm")
        spec += [(f"{p}.mlp.gate.weight", (inter, h), "normal"),
                 (f"{p}.mlp.up.weight", (inter, h), "normal"),
                 (f"{p}.mlp.down.weight", (h, inter), "normal")]
    return spec + norm("norm")


def make_params(cfg, seed, check=False):
    """Every parameter, made on the device in ONE jitted call from the
    seed, in the type it is trained in: ``{layout name: array}``. With
    ``check`` the scan's decay rates are ``check.scan.decay_scale`` times
    the initialisation's (a state that remembers four times as long, so
    that what a step rounds away is missed: ``config.json`` says why),
    everything else the same."""
    spec = layout(cfg)
    dt = jnp.dtype(cfg["dtype"])
    f32 = jnp.float32
    rank = _sizes(cfg)[4]
    decay_scale = cfg["check"]["scan"]["decay_scale"] if check else 1.0

    def one(key, shape, init):
        if init == "normal":
            return (jax.random.normal(key, shape, f32)
                    * cfg["initializer_range"]).astype(dt)
        if init == "lam":
            return jax.random.normal(key, shape, f32) * 0.1
        if init == "conv":
            return jax.random.uniform(key, shape, f32, -0.5, 0.5).astype(dt)
        if init == "dt_w":
            bound = rank ** -0.5
            return jax.random.uniform(key, shape, f32, -bound,
                                      bound).astype(dt)
        if init == "dt_b":
            step = jnp.exp(jax.random.uniform(
                key, shape, f32, math.log(1e-3), math.log(1e-1)))
            return (step + jnp.log(-jnp.expm1(-step))).astype(dt)
        if init == "a_log":
            return jnp.broadcast_to(
                jnp.log(jnp.arange(1, shape[1] + 1, dtype=f32)
                        * decay_scale), shape)
        if init == "ones32":
            return jnp.ones(shape, f32)
        return (jnp.ones if init == "ones" else jnp.zeros)(shape, dt)

    def make(key):
        return {name: one(jax.random.fold_in(key, j), shape, init)
                for j, (name, shape, init) in enumerate(spec)}

    return jax.jit(make)(jax.random.PRNGKey(seed))


# ----------------------------------------------- the system under test ---

def build(cfg, ctx, seed):
    """The package's model with seeded weights on ``ctx``."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import text

    published, kept = model_config(cfg)
    net = text.get_model(published["model_type"], layers_kept=kept,
                         **published)
    net.cast(cfg["dtype"])
    net.initialize(mx.init.Zero(), ctx=ctx)
    _set_params(net, cfg, make_params(cfg, seed))
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
        p.set_data(NDArray(arrays[name]))


def seed_params(net, cfg, seed):
    """Set every weight of ``net`` to its seeded value, the scan's decay
    rates scaled by ``check.scan``, for the comparison with
    ``reference``."""
    _set_params(net, cfg, make_params(cfg, seed, check=True))


def loss(cfg):
    from mxnet_tpu.gluon import loss as gloss

    return gloss.CausalLMLoss()


def export_params(net, cfg):
    """``{layout name: float32 numpy array}`` of the network as it is."""
    from chipbench.harness import params

    return params.export(net, [name for name, _, _ in layout(cfg)])


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

def pairs_in_band(seq_len, window=None):
    """(query, key) pairs a causal layer computes over ``seq_len``
    positions: all at or under the diagonal, or with a ``window`` the last
    ``window`` keys of each query."""
    w = seq_len if window is None else min(int(window), seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def _attention_layers(cfg):
    """``[window or None]`` of the kept layers that run attention."""
    return [cfg["sliding_window"] if kind_of(cfg, i) == "window" else None
            for i in cfg["layers_kept"]
            if kind_of(cfg, i) in ("window", "full", "cross")]


def forward_macs_per_token(cfg, seq_len):
    """Multiply-accumulates of one token's forward pass, by part, over the
    layers held. Attention is counted by the pairs inside each layer's
    band: every one of the query heads multiplies a 64-wide key and a
    128-wide value pair for each key it sees. The scan is three products
    an element of the state (decay, input, output); norms, the softmax,
    the convolution's four taps and ``exp`` are left out."""
    h, d, kv, inner, rank = _sizes(cfg)
    kinds = [kind_of(cfg, i) for i in cfg["layers_kept"]]
    n_mamba = sum(k in ("mamba", "mamba_memory") for k in kinds)
    n_own_kv = sum(k in ("window", "full") for k in kinds)
    n_attn = n_own_kv + kinds.count("cross")
    keys_seen = sum(pairs_in_band(seq_len, w)
                    for w in _attention_layers(cfg)) / seq_len
    return {
        "ssm_projections": n_mamba * (h * 2 * inner
                                      + inner * (rank + 2 * D_STATE)
                                      + rank * inner + inner * h),
        "ssm_scan": n_mamba * 3 * inner * D_STATE,
        "attention_projections": n_attn * 2 * h * h + n_own_kv * h * 2 * kv,
        "attention": cfg["num_attention_heads"] * 3 * d * keys_seen,
        "gmu": kinds.count("gmu") * 2 * h * inner,
        "mlp": len(kinds) * 3 * h * cfg["intermediate_size"],
        "head": h * cfg["vocab_size"]}


def flops_per_sample(cfg, traffic):
    """Model operations per sequence: two per multiply-accumulate; a
    training step is forward plus backward (twice the forward), nothing
    recomputed (the flash and scan backwards' recomputation is the
    program's choice and does not count)."""
    s = int(traffic["seq_len"])
    passes = 3 if traffic.get("kind", "train") == "train" else 1
    return 2 * sum(forward_macs_per_token(cfg, s).values()) * s * passes


def attention_kernel_cost(cfg, traffic):
    """``{"flops", "bytes", "shape"}`` of the Pallas attention FORWARD
    calls of one training step: two a layer (the two softmaxes of
    differential attention), each over half the query heads (64 wide),
    half the key heads and the value pairs (128 wide). Operations: the
    (query, key) pairs inside each layer's band (``pairs_in_band``), two
    per multiply-accumulate, whatever the kernel's blocks. Bytes: every
    operand read and the output written once (grouped keys: a key head is
    counted once, not once a query head). ``shape`` is the result shape of
    one call as the device trace names it; the backward's first result is
    dK, another shape."""
    b, s = int(traffic["global_batch"]), int(traffic["seq_len"])
    _, d, _, _, _ = _sizes(cfg)
    q_heads = cfg["num_attention_heads"] // 2
    k_heads = cfg["num_key_value_heads"] // 2
    item = jnp.dtype(cfg["dtype"]).itemsize
    layers = _attention_layers(cfg)
    call_bytes = (q_heads * (d + 2 * d) + k_heads * (d + 2 * d)) * s * item
    return {
        "flops": 2 * sum(pairs_in_band(s, w) for w in layers)
        * 3 * d * b * q_heads * 2,
        "bytes": call_bytes * b * 2 * len(layers),
        "shape": f"{_HLO_TYPE[cfg['dtype']]}[{b * q_heads},{s},{2 * d}]"}


def ssm_scan_kernel_cost(cfg, traffic):
    """``{"bytes", "shape"}`` of the selective-scan calls of ONE training
    step, forward and backward, over the Mamba layers held. The scan has
    no matmul and ``peaks.json`` no vector peak, so bytes bound it: the
    forward reads x (2 bytes), the float32 step and B, C and writes the
    output; the backward reads those and the output's cotangent and writes
    the cotangents of x, the step, B and C; each once (the states a chunk
    starts from, 21 MB a layer written and read, are the kernel's way and
    not the algorithm's; the gate ``silu(z)`` is applied outside the
    kernel, so ``z`` is not among its bytes). ``shape`` is the first result
    of either call as the device trace names it: the forward's is the
    output, the backward's the cotangent of x, both (B, S, channels)."""
    b, s = int(traffic["global_batch"]), int(traffic["seq_len"])
    _, _, _, inner, _ = _sizes(cfg)
    item = jnp.dtype(cfg["dtype"]).itemsize
    n_mamba = sum(kind_of(cfg, i) in ("mamba", "mamba_memory")
                  for i in cfg["layers_kept"])
    wide = b * s * inner          # x, the step, the output and cotangents
    narrow = b * s * D_STATE      # B, C and theirs
    forward = wide * (item + 4 + item) + 2 * narrow * item
    backward = wide * (item + 4 + item + item + 4) + 4 * narrow * item
    return {"bytes": n_mamba * (forward + backward),
            "shape": f"{_HLO_TYPE[cfg['dtype']]}[{b},{s},{inner}]"}


# -------------------------------------------------------- the reference --

def _layer_norm(x, p, pre, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p[f"{pre}.gamma"] \
        + p[f"{pre}.beta"]


def _mlp(x, p, pre):
    return (jax.nn.silu(x @ p[f"{pre}.gate.weight"].T)
            * (x @ p[f"{pre}.up.weight"].T)) @ p[f"{pre}.down.weight"].T


def _as_bfloat16(x):
    """float32 values rounded to the nearest bfloat16 (a convert there and
    back is a pair XLA is free to drop, and on the TPU it does)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _mamba(cfg, p, pre, u, fault):
    """One sample ``u`` (S, hidden) -> (out, scanned): the recurrence as a
    plain ``lax.scan`` over time."""
    h, _, _, inner, rank = _sizes(cfg)
    xz = u @ p[f"{pre}.in_proj.weight"].T
    x, z = xz[:, :inner], xz[:, inner:]
    padded = jnp.pad(x, ((D_CONV - 1, 0), (0, 0)))
    w = p[f"{pre}.conv.weight"]
    x = jax.nn.silu(sum(padded[k:k + x.shape[0]] * w[:, k]
                        for k in range(D_CONV)) + p[f"{pre}.conv.bias"])
    dbc = x @ p[f"{pre}.x_proj.weight"].T
    step = jax.nn.softplus(dbc[:, :rank] @ p[f"{pre}.dt.weight"].T
                           + p[f"{pre}.dt.bias"])
    if fault == "dt_bf16":
        step = _as_bfloat16(step)
    b_t, c_t = dbc[:, rank:rank + D_STATE], dbc[:, rank + D_STATE:]
    a = -jnp.exp(p[f"{pre}.a_log"])                     # (inner, state)

    def one(state, inp):
        x_t, s_t, bb, cc = inp
        state = jnp.exp(s_t[:, None] * a) * state \
            + (s_t * x_t)[:, None] * bb[None, :]
        if fault == "state_bf16":
            state = _as_bfloat16(state)
        return state, state @ cc + p[f"{pre}.d_skip"] * x_t

    _, scanned = jax.lax.scan(one, jnp.zeros((inner, D_STATE)),
                              (x, step, b_t, c_t))
    out = (scanned * jax.nn.silu(z)) @ p[f"{pre}.out_proj.weight"].T
    return out, (scanned * jax.nn.silu(z)
                 if fault == "memory_after_gate" else scanned)


def _project_kv(cfg, p, pre, u):
    kv = u @ p[f"{pre}.kv_proj.weight"].T + p[f"{pre}.kv_proj.bias"]
    half = kv.shape[-1] // 2
    return kv[:, :half], kv[:, half:]


def _diff_attention(cfg, p, pre, u, k, v, index, window, fault, pair_block):
    """Differential attention of one sample: ``u`` (S, hidden), projected
    ``k``, ``v`` (S, kv heads * 64), masked dense softmaxes ``pair_block``
    query pairs at a time."""
    s, heads = u.shape[0], cfg["num_attention_heads"]
    kv_heads = cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // heads
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * index)
    q = (u @ p[f"{pre}.q_proj.weight"].T + p[f"{pre}.q_proj.bias"]) \
        .reshape(s, heads // 2, 2, d)
    group = heads // kv_heads
    # query pair j reads key pair j // group: one copy a query pair
    k = jnp.repeat(k.reshape(s, kv_heads // 2, 2, d), group, axis=1)
    v = jnp.repeat(v.reshape(s, kv_heads // 2, 2 * d), group, axis=1)
    lam = (jnp.exp(p[f"{pre}.lam_q1"] @ p[f"{pre}.lam_k1"])
           - jnp.exp(p[f"{pre}.lam_q2"] @ p[f"{pre}.lam_k2"]) + lam_init)
    if fault == "lam_dropped":
        lam = 0.0
    pos = jnp.arange(s)
    keep = pos[:, None] >= pos[None, :]
    if window is not None:
        keep &= pos[:, None] - pos[None, :] < window

    def block(qkv):
        qb, kb, vb = qkv            # (pb, S, 2, d), (pb, S, 2, d), (pb, S, 2d)

        def soft(which):
            sc = jnp.einsum("pqd,pkd->pqk", qb[:, :, which],
                            kb[:, :, which]) / math.sqrt(d)
            return jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)

        return jnp.einsum("pqk,pkd->pqd", soft(0) - lam * soft(1), vb)

    def blocks(t):                   # (S, pairs, ...) -> (n, pb, S, ...)
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape((t.shape[0] // pair_block, pair_block)
                         + t.shape[1:])

    out = jax.lax.map(block, (blocks(q), blocks(k), blocks(v)))
    out = out.reshape(heads // 2, s, 2 * d)
    out = out * jax.lax.rsqrt((out * out).mean(-1, keepdims=True)
                              + cfg["layer_norm_eps"]) \
        * p[f"{pre}.subln.gamma"] * (1.0 - lam_init)
    out = jnp.moveaxis(out, 0, 1).reshape(s, heads * d)
    return out @ p[f"{pre}.o_proj.weight"].T + p[f"{pre}.o_proj.bias"]


def reference(cfg, params, batch, train=False, pair_block=5, fault=None):
    """Logits (B, S, V) (and, with labels, the mean next-token
    cross-entropy) of ``batch = (x, y | None)`` in float32 at the highest
    matmul precision, one sample and ``pair_block`` query pairs at a time
    so that the (S, S) scores fit. ``fault`` (one of ``FAULTS``) computes
    one thing wrong, for the readings that set the tolerance:
    ``state_bf16`` / ``dt_bf16`` round the scan's state every step / its
    step to bfloat16, ``window_off`` runs the window layers over all
    earlier keys, ``lam_dropped`` sets lam to 0, ``memory_after_gate`` hands
    on the gated scan output, ``cross_own_kv`` lets a cross layer project
    keys and values from its own input (with the full layer's matrix),
    ``weights_float8`` rounds every matrix to float8 (e4m3)."""
    del train   # no dropout anywhere
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no fault {fault!r} (known: {FAULTS})")
    x, y = batch
    eps = cfg["layer_norm_eps"]
    pairs = cfg["num_attention_heads"] // 2
    pair_block = max(b for b in range(1, min(pair_block, pairs) + 1)
                     if pairs % b == 0)

    def one_sample(p, ids):
        h = p["embed.weight"][ids]
        memory = k = v = kv_pre = None
        for i in cfg["layers_kept"]:
            lp, kind = f"layer{i}", kind_of(cfg, i)
            u = _layer_norm(h, p, f"{lp}.input_norm", eps)
            if kind in ("mamba", "mamba_memory"):
                mixed, scanned = _mamba(cfg, p, f"{lp}.ssm", u, fault)
                if kind == "mamba_memory":
                    memory = scanned
            elif kind == "gmu":
                mixed = (memory * jax.nn.silu(
                    u @ p[f"{lp}.gmu.in_proj.weight"].T)) \
                    @ p[f"{lp}.gmu.out_proj.weight"].T
            else:
                window = cfg["sliding_window"] if kind == "window" \
                    and fault != "window_off" else None
                if kind == "cross":
                    kk, vv = _project_kv(cfg, p, kv_pre, u) \
                        if fault == "cross_own_kv" else (k, v)
                else:
                    kk, vv = _project_kv(cfg, p, f"{lp}.attn", u)
                    if kind == "full":
                        k, v, kv_pre = kk, vv, f"{lp}.attn"
                mixed = _diff_attention(cfg, p, f"{lp}.attn", u, kk, vv, i,
                                        window, fault, pair_block)
            h = h + mixed
            h = h + _mlp(_layer_norm(h, p, f"{lp}.post_norm", eps), p,
                         f"{lp}.mlp")
        return _layer_norm(h, p, "norm", eps) @ p["embed.weight"].T

    with jax.default_matmul_precision("highest"):
        p = {k_: jnp.asarray(v_, jnp.float32) for k_, v_ in params.items()}
        if fault == "weights_float8":
            p = {k_: v_.astype(jnp.float8_e4m3fn).astype(jnp.float32)
                 if v_.ndim == 2 and not k_.endswith("a_log") else v_
                 for k_, v_ in p.items()}
        logits = jax.lax.map(lambda ids: one_sample(p, ids), jnp.asarray(x))
        out = {"logits": logits}
        if y is not None:
            picked = jnp.take_along_axis(
                logits, jnp.asarray(y).astype(jnp.int32)[..., None],
                axis=-1)[..., 0]
            out["loss"] = (jax.nn.logsumexp(logits, axis=-1) - picked).mean()
        return out
