"""ResNet-50 v1 (He et al., arXiv:1512.03385, Table 1, 50 layers) as
``gluon.model_zoo.vision.resnet50_v1`` builds it: bottleneck blocks with
the stride on the first 1x1 convolution, biases on the 1x1 convolutions
of the body, none on the 3x3 and on the shortcut projection.

``build`` is the system under test (the model zoo's own ``ResNetV1`` over
``BottleneckV1``, fed the sizes of ``config.json``); ``reference`` is the
same network written out in plain ``jax.numpy``/``lax`` in float32, which
shares nothing with ``mxnet_tpu``. The two meet only through ``layout``:
the ordered list of parameters, which is also the order in which
``collect_params()`` lists them.
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

BN_EPS = 1e-5
BN_MOMENTUM = 0.9


# ------------------------------------------------------------- layout ----

def _bn(prefix, ch):
    return [(f"{prefix}.gamma", (ch,), "ones"), (f"{prefix}.beta", (ch,),
                                                 "zeros"),
            (f"{prefix}.mean", (ch,), "zeros"), (f"{prefix}.var", (ch,),
                                                 "ones")]


def blocks(cfg):
    """``(stage, block, in_ch, out_ch, stride, has_shortcut)`` per
    bottleneck, in forward order."""
    out = []
    chans = cfg["channels"]
    for i, n in enumerate(cfg["layers"]):
        for j in range(n):
            cin = chans[i] if j == 0 else chans[i + 1]
            stride = 2 if (j == 0 and i > 0) else 1
            out.append((i + 1, j, cin, chans[i + 1], stride,
                        j == 0 and chans[i + 1] != chans[i]))
    return out


def layout(cfg):
    """``[(name, shape, init)]`` in the order gluon lists the parameters:
    ``init`` is ``he`` (normal, std sqrt(2 / fan_in)), ``zeros`` or
    ``ones``."""
    c0 = cfg["channels"][0]
    spec = [("stem.conv.weight", (c0, 3, 7, 7), "he")] + _bn("stem.bn", c0)
    for stage, j, cin, cout, _stride, shortcut in blocks(cfg):
        p = f"stage{stage}.block{j}"
        mid = cout // 4
        spec += [(f"{p}.conv1.weight", (mid, cin, 1, 1), "he"),
                 (f"{p}.conv1.bias", (mid,), "zeros")] + _bn(f"{p}.bn1", mid)
        spec += [(f"{p}.conv2.weight", (mid, mid, 3, 3), "he")] \
            + _bn(f"{p}.bn2", mid)
        spec += [(f"{p}.conv3.weight", (cout, mid, 1, 1), "he"),
                 (f"{p}.conv3.bias", (cout,), "zeros")] \
            + _bn(f"{p}.bn3", cout)
        if shortcut:
            spec += [(f"{p}.shortcut.conv.weight", (cout, cin, 1, 1), "he")] \
                + _bn(f"{p}.shortcut.bn", cout)
    spec += [("fc.weight", (cfg["classes"], cfg["channels"][-1]), "he"),
             ("fc.bias", (cfg["classes"],), "zeros")]
    return spec


def _dtype_of(name, cfg):
    # BatchNorm keeps its statistics and affine pair in float32 whatever
    # the network is cast to (gluon.nn.BatchNorm.cast, the AMP rule)
    return jnp.float32 if ".bn" in name else jnp.dtype(cfg["dtype"])


def make_params(cfg, seed):
    """Every parameter, made on the device in ONE jitted call from the
    seed, in the type it is trained and served in."""
    spec = layout(cfg)
    n_random = sum(int(np.prod(s)) for _, s, init in spec if init == "he")

    def make(key):
        # one draw for the whole network, cut into its tensors: one
        # random-number program to compile instead of one per tensor
        flat = jax.random.normal(key, (n_random,), jnp.float32)
        out, off = [], 0
        for name, shape, init in spec:
            dt = _dtype_of(name, cfg)
            if init == "he":
                n = int(np.prod(shape))
                w = flat[off:off + n].reshape(shape)
                off += n
                out.append((w * np.sqrt(2.0 / np.prod(shape[1:])))
                           .astype(dt))
            else:
                out.append(jnp.full(shape, 1.0 if init == "ones" else 0.0,
                                    dt))
        return tuple(out)

    return jax.jit(make)(jax.random.PRNGKey(seed))


# ----------------------------------------------- the system under test ---

def build(cfg, ctx, seed):
    """The gluon network with seeded weights on ``ctx``."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.vision import resnet

    net = resnet.ResNetV1(resnet.BottleneckV1, cfg["layers"],
                          cfg["channels"], classes=cfg["classes"])
    net.cast(cfg["dtype"])
    net.initialize(mx.init.Zero(), ctx=ctx)
    seed_params(net, cfg, seed)
    return net


def seed_params(net, cfg, seed):
    """(Re)set every parameter of ``net`` to its seeded value."""
    from chipbench.harness import params

    params.set_all(net, make_params(cfg, seed))


def loss(cfg):
    from mxnet_tpu.gluon import loss as gloss

    return gloss.SoftmaxCrossEntropyLoss()


def export_params(net, cfg):
    """``{layout name: float32 numpy array}`` of the network as it is."""
    from chipbench.harness import params

    return params.export(net, [name for name, _, _ in layout(cfg)])


def example_shape(cfg):
    return (3, cfg["image_size"], cfg["image_size"])


def make_batch(cfg, traffic, key):
    """One seeded training batch ``(x, y)`` as jax arrays (traceable:
    the mode makes its whole pool in one jitted call): images uniform in
    [0, 1) in the network's type, labels as float32 class indices, the
    way ``SoftmaxCrossEntropyLoss`` takes sparse labels."""
    b = int(traffic["global_batch"])
    kx, ky = jax.random.split(key)
    x = jax.random.uniform(kx, (b,) + example_shape(cfg), jnp.float32)
    y = jax.random.randint(ky, (b,), 0, cfg["classes"])
    return x.astype(cfg["dtype"]), y.astype(jnp.float32)


def check_inputs(cfg, seed, n):
    """``n`` seeded images for the comparison with ``reference``, already
    rounded to the network's type so both sides see the same numbers."""
    rng = np.random.default_rng([int(seed), 0xC4EC])
    x = rng.random((n,) + example_shape(cfg), dtype=np.float32)
    return np.asarray(x.astype(jnp.dtype(cfg["dtype"]))).astype(np.float32)


# ------------------------------------------------------------ operations -

def forward_macs(cfg):
    """Multiply-accumulates of one forward pass of one image, from the
    layer shapes: convolutions and the classifier. Normalization,
    activations, pooling and the residual adds are left out (well under
    1% of the total)."""
    size = cfg["image_size"]
    c0 = cfg["channels"][0]
    h = (size + 2 * 3 - 7) // 2 + 1
    macs = h * h * c0 * 3 * 7 * 7
    h = (h + 2 * 1 - 3) // 2 + 1           # max pool 3x3, stride 2
    for _stage, _j, cin, cout, stride, shortcut in blocks(cfg):
        mid = cout // 4
        h_out = (h - 1) // stride + 1      # the first 1x1 carries the stride
        macs += h_out * h_out * mid * cin            # 1x1, strided
        macs += h_out * h_out * mid * mid * 9        # 3x3
        macs += h_out * h_out * cout * mid           # 1x1
        if shortcut:
            macs += h_out * h_out * cout * cin       # projection, strided
        h = h_out
    return macs + cfg["channels"][-1] * cfg["classes"]


def flops_per_sample(cfg, traffic):
    """Model operations per image: two per multiply-accumulate; a
    training step is forward plus backward (twice the forward), nothing
    recomputed; serving is the forward pass alone."""
    passes = 3 if traffic.get("kind", "train") == "train" else 1
    return 2 * forward_macs(cfg) * passes


# -------------------------------------------------------- the reference --

def _conv(x, w, stride, pad):
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def _bias(x, b):
    return x + b.reshape(1, -1, 1, 1)


def _batchnorm(x, p, prefix, train):
    if train:
        mean = x.mean(axis=(0, 2, 3))
        var = ((x - mean.reshape(1, -1, 1, 1)) ** 2).mean(axis=(0, 2, 3))
    else:
        mean, var = p[f"{prefix}.mean"], p[f"{prefix}.var"]
    x = (x - mean.reshape(1, -1, 1, 1)) / jnp.sqrt(
        var.reshape(1, -1, 1, 1) + BN_EPS)
    return x * p[f"{prefix}.gamma"].reshape(1, -1, 1, 1) \
        + p[f"{prefix}.beta"].reshape(1, -1, 1, 1)


def reference(cfg, params, batch, train=False):
    """Logits (and, with labels, the mean softmax cross-entropy) of
    ``batch = (x, y | None)`` in float32 at the highest matmul precision.
    ``train`` normalizes with the batch's own statistics, as a training
    step does; otherwise with the running ones."""
    x, y = batch
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        x = jnp.asarray(x, jnp.float32)
        x = _conv(x, p["stem.conv.weight"], 2, 3)
        x = jax.nn.relu(_batchnorm(x, p, "stem.bn", train))
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2),
                              [(0, 0), (0, 0), (1, 1), (1, 1)])
        for stage, j, _cin, _cout, stride, shortcut in blocks(cfg):
            b = f"stage{stage}.block{j}"
            r = x
            x = _bias(_conv(x, p[f"{b}.conv1.weight"], stride, 0),
                      p[f"{b}.conv1.bias"])
            x = jax.nn.relu(_batchnorm(x, p, f"{b}.bn1", train))
            x = _conv(x, p[f"{b}.conv2.weight"], 1, 1)
            x = jax.nn.relu(_batchnorm(x, p, f"{b}.bn2", train))
            x = _bias(_conv(x, p[f"{b}.conv3.weight"], 1, 0),
                      p[f"{b}.conv3.bias"])
            x = _batchnorm(x, p, f"{b}.bn3", train)
            if shortcut:
                r = _conv(r, p[f"{b}.shortcut.conv.weight"], stride, 0)
                r = _batchnorm(r, p, f"{b}.shortcut.bn", train)
            x = jax.nn.relu(x + r)
        x = x.mean(axis=(2, 3))
        logits = x @ p["fc.weight"].T + p["fc.bias"]
        out = {"logits": logits}
        if y is not None:
            logp = jax.nn.log_softmax(logits, axis=-1)
            picked = jnp.take_along_axis(
                logp, jnp.asarray(y).astype(jnp.int32)[:, None], axis=-1)
            out["loss"] = -picked.mean()
        return out
