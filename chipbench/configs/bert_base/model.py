"""BERT-base (Devlin et al., arXiv:1810.04805; ``google-bert/bert-base-
uncased`` ``config.json``) with the SQuAD span head of ``run_squad.py``.

``build`` composes the published post-LayerNorm encoder layer from the
framework's public blocks (``MultiHeadAttention``, ``LayerNorm``,
``Dense``, ``Dropout``, ``Embedding``), the way GluonNLP sat on MXNet:
the repo's own ``TransformerEncoderCell`` is pre-LN and is not BERT.
Attention therefore goes through ``F.contrib.flash_attention`` and
``kernels.dispatch`` like any user's. ``reference`` is the same network in
plain float32 ``jax.numpy`` and shares nothing with ``mxnet_tpu``; the two
meet only through ``layout``.

``ShardedTrainer.step(x, y)`` takes one array and one label, so a batch is
``x = (B, 2, S)`` int32 (token ids, token types) and ``y = (B, 2)`` (start
and end position); the block splits ``x`` itself and answers ``(B, 2, S)``
logits, so that the span loss (mean of the start and the end
cross-entropy) is ``SoftmaxCrossEntropyLoss`` over the last axis.

Departures from the published model, also under ``assumed`` in
``config.json``: no dropout on the attention probabilities (the flash path
has none), no padding mask (every sequence is full length).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np


# ------------------------------------------------------------- layout ----

def _dense(prefix, out_dim, in_dim):
    return [(f"{prefix}.weight", (out_dim, in_dim), "normal"),
            (f"{prefix}.bias", (out_dim,), "zeros")]


def _ln(prefix, dim):
    return [(f"{prefix}.gamma", (dim,), "ones"),
            (f"{prefix}.beta", (dim,), "zeros")]


def layout(cfg):
    """``[(name, shape, init)]`` in the order gluon lists the parameters;
    ``normal`` is N(0, initializer_range**2)."""
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    spec = [("emb.word", (cfg["vocab_size"], h), "normal"),
            ("emb.position", (cfg["max_position_embeddings"], h), "normal"),
            ("emb.token_type", (cfg["type_vocab_size"], h), "normal")]
    spec += _ln("emb.ln", h)
    for i in range(cfg["num_hidden_layers"]):
        p = f"layer{i}"
        for part in ("query", "key", "value", "out"):
            spec += _dense(f"{p}.attn.{part}", h, h)
        spec += _ln(f"{p}.ln1", h)
        spec += _dense(f"{p}.ffn1", inter, h) + _dense(f"{p}.ffn2", h, inter)
        spec += _ln(f"{p}.ln2", h)
    return spec + _dense("qa", 2, h)


def make_params(cfg, seed):
    """Every parameter, made on the device in ONE jitted call from the
    seed, in the type it is trained in."""
    spec = layout(cfg)
    dt = jnp.dtype(cfg["dtype"])
    n_random = sum(int(np.prod(s)) for _, s, init in spec
                   if init == "normal")

    def make(key):
        flat = jax.random.normal(key, (n_random,), jnp.float32) \
            * cfg["initializer_range"]
        out, off = [], 0
        for _name, shape, init in spec:
            if init == "normal":
                n = int(np.prod(shape))
                out.append(flat[off:off + n].reshape(shape).astype(dt))
                off += n
            else:
                out.append(jnp.full(shape, 1.0 if init == "ones" else 0.0,
                                    dt))
        return tuple(out)

    return jax.jit(make)(jax.random.PRNGKey(seed))


# ----------------------------------------------- the system under test ---

@functools.lru_cache(maxsize=1)
def _blocks():
    """The gluon classes, defined on first use so that the operation
    counts and the reference import without the framework."""
    from mxnet_tpu.gluon import HybridBlock, nn
    from mxnet_tpu.gluon.contrib.nn import MultiHeadAttention

    class BertLayer(HybridBlock):
        """Post-LN encoder layer: x = LN(x + drop(attn(x)));
        x = LN(x + drop(ffn2(gelu(ffn1(x)))))."""

        def __init__(self, cfg, **kwargs):
            super().__init__(**kwargs)
            h, eps = cfg["hidden_size"], cfg["layer_norm_eps"]
            drop = cfg["hidden_dropout_prob"]
            with self.name_scope():
                # its own output projection and dropout are BERT's
                # BertSelfOutput.dense / .dropout
                self.attn = MultiHeadAttention(
                    h, cfg["num_attention_heads"], dropout=drop)
                self.ln1 = nn.LayerNorm(epsilon=eps, in_channels=h)
                self.ffn1 = nn.Dense(cfg["intermediate_size"],
                                     flatten=False, in_units=h)
                self.ffn2 = nn.Dense(h, flatten=False,
                                     in_units=cfg["intermediate_size"])
                self.drop = nn.Dropout(drop)
                self.ln2 = nn.LayerNorm(epsilon=eps, in_channels=h)

        def hybrid_forward(self, F, x):
            x = self.ln1(x + self.attn(x))
            h = F.LeakyReLU(self.ffn1(x), act_type="gelu")  # erf form
            return self.ln2(x + self.drop(self.ffn2(h)))

    class BertForSpans(HybridBlock):
        def __init__(self, cfg, **kwargs):
            super().__init__(**kwargs)
            h = cfg["hidden_size"]
            with self.name_scope():
                self.word = nn.Embedding(cfg["vocab_size"], h)
                self.position = nn.Embedding(
                    cfg["max_position_embeddings"], h)
                self.token_type = nn.Embedding(cfg["type_vocab_size"], h)
                self.emb_ln = nn.LayerNorm(epsilon=cfg["layer_norm_eps"],
                                           in_channels=h)
                self.emb_drop = nn.Dropout(cfg["hidden_dropout_prob"])
                self.layers = nn.HybridSequential()
                for _ in range(cfg["num_hidden_layers"]):
                    self.layers.add(BertLayer(cfg))
                self.qa = nn.Dense(2, flatten=False, in_units=h)

        def hybrid_forward(self, F, x):
            # x: (B, 2, S) integer ids; never cast to the float type
            ids = F.reshape(F.slice_axis(x, axis=1, begin=0, end=1),
                            shape=(0, -1))
            types = F.reshape(F.slice_axis(x, axis=1, begin=1, end=2),
                              shape=(0, -1))
            pos = F.arange(0, x.shape[2])
            e = self.word(ids) + self.position(pos) + self.token_type(types)
            e = self.emb_drop(self.emb_ln(e))
            return F.transpose(self.qa(self.layers(e)), axes=(0, 2, 1))

    return BertForSpans


def build(cfg, ctx, seed):
    """The gluon network with seeded weights on ``ctx``."""
    import mxnet_tpu as mx

    net = _blocks()(cfg)
    net.cast(cfg["dtype"])
    net.initialize(mx.init.Zero(), ctx=ctx)
    seed_params(net, cfg, seed)
    return net


def seed_params(net, cfg, seed):
    """(Re)set every parameter of ``net`` to its seeded value."""
    from chipbench.harness import params

    params.set_all(net, make_params(cfg, seed))


def loss(cfg):
    """Mean of the start and the end cross-entropy: over (B, 2, S)
    logits and (B, 2) positions that is softmax cross-entropy along the
    last axis, averaged over the two."""
    from mxnet_tpu.gluon import loss as gloss

    return gloss.SoftmaxCrossEntropyLoss(axis=-1)


def export_params(net, cfg):
    """``{layout name: float32 numpy array}`` of the network as it is."""
    from chipbench.harness import params

    return params.export(net, [name for name, _, _ in layout(cfg)])


def _tokens(cfg, key, b, s):
    """Token ids uniform over the vocabulary; token types 0 for a
    question of 8..63 tokens, 1 for the rest (the passage)."""
    k_ids, k_q = jax.random.split(key)
    ids = jax.random.randint(k_ids, (b, s), 0, cfg["vocab_size"])
    q_len = jax.random.randint(k_q, (b, 1), 8, 64)
    types = (jnp.arange(s)[None, :] >= q_len).astype(jnp.int32)
    return jnp.stack([ids.astype(jnp.int32), types], axis=1)


def make_batch(cfg, traffic, key):
    """One seeded training batch ``(x, y)`` as jax arrays: x (B, 2, S)
    int32, y (B, 2) float32 start/end positions (start <= end)."""
    b, s = int(traffic["global_batch"]), int(traffic["seq_len"])
    k_tok, k_start, k_len = jax.random.split(key, 3)
    start = jax.random.randint(k_start, (b,), 0, s)
    end = jnp.minimum(start + jax.random.randint(k_len, (b,), 0, 30), s - 1)
    return _tokens(cfg, k_tok, b, s), \
        jnp.stack([start, end], axis=1).astype(jnp.float32)


def check_inputs(cfg, seed, n, seq_len=None):
    """``n`` seeded sequences for the comparison with ``reference``."""
    s = int(seq_len or cfg["job"]["max_seq_length"])
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 0xC4EC)
    return np.asarray(_tokens(cfg, key, n, s))


# ------------------------------------------------------------ operations -

def matmul_params(cfg):
    """Weights that take part in a matrix multiplication per token: the
    encoder's dense layers and the span head (embeddings are lookups)."""
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (4 * h * h + 2 * h * inter) + 2 * h


def forward_macs(cfg, seq_len):
    """Multiply-accumulates of one forward pass of one sequence: every
    dense layer once per token, plus QK^T and PV (seq_len x hidden each,
    per token and layer). LayerNorm, GELU, softmax and biases left out."""
    attn = 2 * seq_len * cfg["hidden_size"] * cfg["num_hidden_layers"]
    return seq_len * (matmul_params(cfg) + attn)


def flops_per_sample(cfg, traffic):
    """Model operations per sequence: two per multiply-accumulate; a
    training step is forward plus backward (twice the forward), nothing
    recomputed (the flash backward's recomputation of the probabilities
    is the kernel's choice and does not count)."""
    passes = 3 if traffic.get("kind", "train") == "train" else 1
    return 2 * forward_macs(cfg, int(traffic["seq_len"])) * passes


# -------------------------------------------------------- the reference --

def _layer_norm(x, gamma, beta, eps):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gamma + beta


def reference(cfg, params, batch, train=False):
    """Span logits (B, 2, S) (and, with labels, the mean of the start
    and end cross-entropies) of ``batch = (x, y | None)`` in float32 at
    the highest matmul precision. There is no dropout here: ``train``
    changes nothing, and a comparison of training gradients sets the
    dropout probability to 0 on the system's side."""
    del train
    x, y = batch
    eps = cfg["layer_norm_eps"]
    heads = cfg["num_attention_heads"]
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        x = jnp.asarray(x)
        ids, types = x[:, 0, :], x[:, 1, :]
        b, s = ids.shape
        h = p["emb.word"][ids] + p["emb.position"][jnp.arange(s)][None] \
            + p["emb.token_type"][types]
        h = _layer_norm(h, p["emb.ln.gamma"], p["emb.ln.beta"], eps)
        d = h.shape[-1] // heads

        def dense(t, name):
            return t @ p[f"{name}.weight"].T + p[f"{name}.bias"]

        def split(t):
            return t.reshape(b, s, heads, d).transpose(0, 2, 1, 3)

        for i in range(cfg["num_hidden_layers"]):
            lp = f"layer{i}"
            q, k, v = (split(dense(h, f"{lp}.attn.{n}"))
                       for n in ("query", "key", "value"))
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
            ctx = jnp.einsum("bhqk,bhkd->bhqd",
                             jax.nn.softmax(scores, axis=-1), v)
            ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, heads * d)
            h = _layer_norm(h + dense(ctx, f"{lp}.attn.out"),
                            p[f"{lp}.ln1.gamma"], p[f"{lp}.ln1.beta"], eps)
            f = jax.nn.gelu(dense(h, f"{lp}.ffn1"), approximate=False)
            h = _layer_norm(h + dense(f, f"{lp}.ffn2"),
                            p[f"{lp}.ln2.gamma"], p[f"{lp}.ln2.beta"], eps)
        logits = dense(h, "qa").transpose(0, 2, 1)
        out = {"logits": logits}
        if y is not None:
            logp = jax.nn.log_softmax(logits, axis=-1)
            picked = jnp.take_along_axis(
                logp, jnp.asarray(y).astype(jnp.int32)[..., None], axis=-1)
            out["loss"] = -picked.mean()
        return out
