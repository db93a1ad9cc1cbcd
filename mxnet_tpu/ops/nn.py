"""Neural-net ops: FC, Convolution, Pooling, Norms, Softmax, Dropout, RNN.

Parity target: `src/operator/nn/` in the reference (~32k LoC: hand-written
CPU kernels + cuDNN descriptors under `nn/cudnn/`). Here every op is one XLA
expression: convs lower to `lax.conv_general_dilated` (MXU), norms to fused
reduce+elementwise chains, RNN steps to `lax.scan`.

Data layouts keep MXNet semantics (NCHW / NCW / NCDHW, TNC for RNN). XLA's
layout assignment re-tiles for the MXU internally, so we do not hand-pick
NHWC the way cuDNN-era code does.

Stateful ops (BatchNorm running stats, Dropout RNG) are functional here:
BatchNorm returns (out, mean, var) and the Gluon layer carries the running
stats; Dropout takes an explicit PRNG key array (parity for the reference's
`FCreateOpState`/Resource kTempSpace+kRandom machinery,
`include/mxnet/resource.h:38-46`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as _np

from .math import exact_gelu
from .registry import register


# ----------------------------------------------------------------- FC ------

@register("FullyConnected")
def _fully_connected(data, weight, bias=None, num_hidden=None, no_bias=False,
                     flatten=True):
    """parity: src/operator/nn/fully_connected.cc. weight is (num_hidden, in)."""
    if flatten and data.ndim > 2:
        data = data.reshape(data.shape[0], -1)
    # NOTE: no preferred_element_type=f32 here — the TPU MXU already
    # accumulates bf16 matmuls in f32, and an explicit f32 output + astype
    # breaks the vjp transpose (f32 cotangent vs bf16 operand).
    out = jax.lax.dot_general(
        data, weight,
        dimension_numbers=(((data.ndim - 1,), (1,)), ((), ())))
    if bias is not None and not no_bias:
        out = out + bias
    return out


# ------------------------------------------------------------ Convolution --

def _conv_dims(kernel):
    return len(kernel)


def _tuplize(v, n):
    if v is None or v == ():
        return (1,) * n
    if isinstance(v, int):
        return (v,) * n
    return tuple(v)


@register("Convolution")
def _convolution(data, weight, bias=None, kernel=(), stride=(), dilate=(), pad=(),
                 num_filter=1, num_group=1, no_bias=False, layout=None,
                 cudnn_off=False, workspace=1024, cudnn_tune=None):
    """parity: src/operator/nn/convolution.cc (NCHW / NCW / NCDHW).

    weight layout: (num_filter, C/num_group, *kernel) as in the reference.
    """
    n = _conv_dims(kernel)
    stride = _tuplize(stride if stride else 1, n)
    dilate = _tuplize(dilate if dilate else 1, n)
    pad = _tuplize(pad if pad else 0, n)
    spatial = "DHW"[3 - n:]
    dn = jax.lax.conv_dimension_numbers(
        data.shape, weight.shape,
        ("NC" + spatial, "OI" + spatial, "NC" + spatial))
    out = jax.lax.conv_general_dilated(
        data, weight, window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=num_group)
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * n)
    return out


@register("Deconvolution")
def _deconvolution(data, weight, bias=None, kernel=(), stride=(), dilate=(), pad=(),
                   adj=(), target_shape=(), num_filter=1, num_group=1, no_bias=True,
                   layout=None, cudnn_off=False, workspace=1024, cudnn_tune=None):
    """parity: src/operator/nn/deconvolution.cc — transposed conv.

    weight layout (C_in, num_filter/num_group, *kernel) as in the reference.
    """
    n = _conv_dims(kernel)
    stride = _tuplize(stride if stride else 1, n)
    dilate = _tuplize(dilate if dilate else 1, n)
    pad = _tuplize(pad if pad else 0, n)
    adj = _tuplize(adj if adj else 0, n)
    spatial = "DHW"[3 - n:]
    dn = jax.lax.conv_dimension_numbers(
        data.shape, weight.shape, ("NC" + spatial, "IO" + spatial, "NC" + spatial))
    # transposed conv = gradient of conv: lhs_dilation = stride
    pads = [(dilate[i] * (kernel[i] - 1) - pad[i],
             dilate[i] * (kernel[i] - 1) - pad[i] + adj[i]) for i in range(n)]
    # flip kernel spatial dims (transposed conv applies the mirrored filter)
    out = jax.lax.conv_general_dilated(
        data, jnp.flip(weight, axis=tuple(range(2, 2 + n))),
        window_strides=(1,) * n, padding=pads, lhs_dilation=stride,
        rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=num_group)
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * n)
    return out


# --------------------------------------------------------------- Pooling ---

@register("Pooling", param_specs={
    "pool_type": {"choices": ("max", "avg", "sum", "lp"),
                  "doc": "Pooling reduction"},
    "pooling_convention": {"choices": ("valid", "full", "same")}})
def _pooling(data, kernel=(), pool_type="max", stride=(), pad=(),
             global_pool=False, pooling_convention="valid", cudnn_off=False,
             count_include_pad=True, layout=None):
    """parity: src/operator/nn/pooling.cc via lax.reduce_window."""
    n = data.ndim - 2
    if global_pool:
        kernel = data.shape[2:]
        stride = (1,) * n
        pad = (0,) * n
    else:
        kernel = _tuplize(kernel, n)
        stride = _tuplize(stride if stride else 1, n)
        pad = _tuplize(pad if pad else 0, n)
    window = (1, 1) + tuple(kernel)
    strides = (1, 1) + tuple(stride)
    if pooling_convention == "full" and not global_pool:
        # ceil-mode output: pad on the high side so ceil division is achieved
        pads = [(0, 0), (0, 0)]
        for i in range(n):
            in_sz = data.shape[2 + i]
            out_sz = -(-(in_sz + 2 * pad[i] - kernel[i]) // stride[i]) + 1
            needed = (out_sz - 1) * stride[i] + kernel[i] - in_sz - pad[i]
            pads.append((pad[i], max(needed, pad[i])))
    elif pooling_convention == "same" and not global_pool:
        # TF-style SAME: out = ceil(in/stride), asymmetric split padding
        pads = [(0, 0), (0, 0)]
        for i in range(n):
            in_sz = data.shape[2 + i]
            out_sz = -(-in_sz // stride[i])
            needed = max((out_sz - 1) * stride[i] + kernel[i] - in_sz, 0)
            pads.append((needed // 2, needed - needed // 2))
    else:
        pads = [(0, 0), (0, 0)] + [(p, p) for p in pad]
    # init values MUST be python scalar literals: array-valued inits break
    # reduce_window's vjp under jit (jax 0.9 linearization bug)
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) \
            else int(jnp.iinfo(data.dtype).min)
        return jax.lax.reduce_window(data, init, jax.lax.max,
                                     window, strides, pads)
    if pool_type in ("avg", "sum"):
        zero = 0.0 if jnp.issubdtype(data.dtype, jnp.floating) else 0
        summed = jax.lax.reduce_window(data, zero, jax.lax.add,
                                       window, strides, pads)
        if pool_type == "sum":
            return summed
        if count_include_pad:
            denom = float(_np.prod(kernel))
            return summed / jnp.asarray(denom, data.dtype)
        ones = jnp.ones(data.shape, data.dtype)
        counts = jax.lax.reduce_window(ones, zero, jax.lax.add,
                                       window, strides, pads)
        return summed / counts
    if pool_type == "lp":
        raise NotImplementedError("lp pooling")
    raise ValueError(f"unknown pool_type {pool_type}")


@register("_contrib_AdaptiveAvgPooling2D")
def _adaptive_avg_pool2d(data, output_size=(1, 1)):
    if isinstance(output_size, int):
        output_size = (output_size, output_size)
    b, c, h, w = data.shape
    oh, ow = output_size
    # integral-image free path: only exact-divisor or degenerate cases fast
    x = data.reshape(b, c, oh, h // oh, ow, w // ow) if h % oh == 0 and w % ow == 0 \
        else None
    if x is not None:
        return x.mean(axis=(3, 5))
    # general case via interpolation-style gather
    hs = (jnp.arange(oh + 1) * h / oh).astype(jnp.int32)
    ws = (jnp.arange(ow + 1) * w / ow).astype(jnp.int32)
    rows = [data[:, :, hs[i]:hs[i + 1], :].mean(axis=2, keepdims=True) for i in range(oh)]
    x = jnp.concatenate(rows, axis=2)
    cols = [x[:, :, :, ws[j]:ws[j + 1]].mean(axis=3, keepdims=True) for j in range(ow)]
    return jnp.concatenate(cols, axis=3)


# ----------------------------------------------------------------- Norms ---

@register("BatchNorm", num_outputs=3)
def _batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3, momentum=0.9,
                fix_gamma=True, use_global_stats=False, output_mean_var=False,
                axis=1, cudnn_off=False, training=True):
    """parity: src/operator/nn/batch_norm.cc.

    Returns (out, batch_mean, batch_var); running-stat update is done by the
    caller (functional form — keeps the op pure for XLA).
    """
    axis = axis % data.ndim
    red_axes = tuple(i for i in range(data.ndim) if i != axis)
    bshape = tuple(-1 if i == axis else 1 for i in range(data.ndim))
    if training and not use_global_stats:
        mean = jnp.mean(data.astype(jnp.float32), axis=red_axes)
        var = jnp.var(data.astype(jnp.float32), axis=red_axes)
    else:
        mean, var = moving_mean.astype(jnp.float32), moving_var.astype(jnp.float32)
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    inv = jax.lax.rsqrt(var + eps)
    out = (data - mean.reshape(bshape).astype(data.dtype)) \
        * (g * inv.astype(g.dtype)).reshape(bshape) + beta.reshape(bshape)
    return out.astype(data.dtype), mean, var


@register("LayerNorm")
def _layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    mean = jnp.mean(data, axis=axis, keepdims=True)
    var = jnp.var(data, axis=axis, keepdims=True)
    out = (data - mean) * jax.lax.rsqrt(var + eps)
    bshape = [1] * data.ndim
    bshape[axis] = data.shape[axis]
    return out * gamma.reshape(bshape) + beta.reshape(bshape)


@register("GroupNorm")
def _group_norm(data, gamma, beta, num_groups=1, eps=1e-5, output_mean_var=False):
    b, c = data.shape[:2]
    orig = data.shape
    x = data.reshape((b, num_groups, c // num_groups) + orig[2:])
    red = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=red, keepdims=True)
    var = jnp.var(x, axis=red, keepdims=True)
    x = ((x - mean) * jax.lax.rsqrt(var + eps)).reshape(orig)
    bshape = (1, c) + (1,) * (len(orig) - 2)
    return x * gamma.reshape(bshape) + beta.reshape(bshape)


@register("InstanceNorm")
def _instance_norm(data, gamma, beta, eps=1e-3):
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    out = (data - mean) * jax.lax.rsqrt(var + eps)
    bshape = (1, data.shape[1]) + (1,) * (data.ndim - 2)
    return out * gamma.reshape(bshape) + beta.reshape(bshape)


@register("L2Normalization")
def _l2_normalization(data, eps=1e-10, mode="instance"):
    if mode == "instance":
        red = tuple(range(1, data.ndim))
        kd = True
    elif mode == "channel":
        red, kd = (1,), True
    else:  # spatial
        red, kd = tuple(range(2, data.ndim)), True
    norm = jnp.sqrt(jnp.sum(jnp.square(data), axis=red, keepdims=kd) + eps)
    return data / norm


@register("LRN")
def _lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    sq = jnp.square(data)
    half = nsize // 2
    padded = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    windows = sum(padded[:, i:i + data.shape[1]] for i in range(nsize))
    return data / jnp.power(knorm + alpha / nsize * windows, beta)


# --------------------------------------------------------------- Softmax ---

@register("softmax")
def _softmax(data, axis=-1, temperature=None, length=None, use_length=False):
    if temperature:
        data = data / temperature
    if use_length and length is not None:
        steps = jnp.arange(data.shape[axis])
        mask = steps < length[..., None]
        data = jnp.where(mask, data, -jnp.inf)
    return jax.nn.softmax(data, axis=axis)


@register("log_softmax")
def _log_softmax(data, axis=-1, temperature=None):
    if temperature:
        data = data / temperature
    return jax.nn.log_softmax(data, axis=axis)


@register("softmin")
def _softmin(data, axis=-1):
    return jax.nn.softmax(-data, axis=axis)


@register("SoftmaxActivation")
def _softmax_activation(data, mode="instance"):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


import functools as _functools


@_functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7, 8, 9))
def _softmax_output_core(data, label, grad_scale, ignore_label, multi_output,
                         use_ignore, normalization, out_grad, smooth_alpha,
                         axis):
    return jax.nn.softmax(data, axis=axis)


def _softmax_output_fwd(data, label, grad_scale, ignore_label, multi_output,
                        use_ignore, normalization, out_grad, smooth_alpha,
                        axis):
    out = jax.nn.softmax(data, axis=axis)
    return out, (out, label)


def _softmax_output_bwd(grad_scale, ignore_label, multi_output, use_ignore,
                        normalization, out_grad, smooth_alpha, axis, res,
                        cot):
    """The reference's hand-written CE gradient (`softmax_output-inl.h`):
    d(data) = (softmax - onehot(label)) * grad_scale, with ignore-label
    masking and batch/valid normalization; head gradients are ignored
    unless out_grad=True (loss-head semantics)."""
    out, label = res
    num_classes = out.shape[axis]
    onehot = jax.nn.one_hot(label.astype(jnp.int32), num_classes, axis=axis,
                            dtype=out.dtype)
    if smooth_alpha:
        onehot = onehot * (1.0 - smooth_alpha) + smooth_alpha / max(
            num_classes - 1, 1) * (1.0 - onehot)
    g = out - onehot
    valid = None
    if use_ignore:
        valid = (label != ignore_label).astype(out.dtype)
        g = g * jnp.expand_dims(valid, axis=axis)
    if normalization == "batch":
        g = g / label.shape[0]
    elif normalization == "valid":
        count = (jnp.sum(valid) if valid is not None
                 else jnp.asarray(label.size, out.dtype))
        g = g / jnp.maximum(count, 1.0)
    g = g * grad_scale
    if out_grad:
        g = g * cot
    return g.astype(out.dtype), jnp.zeros_like(label)


_softmax_output_core.defvjp(_softmax_output_fwd, _softmax_output_bwd)


@register("SoftmaxOutput", aliases=("Softmax",))
def _softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                    multi_output=False, use_ignore=False, preserve_shape=False,
                    normalization="null", out_grad=False, smooth_alpha=0.0):
    """Forward = softmax; backward = the reference's custom cross-entropy
    gradient (p - onehot(label)) * grad_scale (`softmax_output.cc`), so the
    symbolic Module path trains exactly like the reference."""
    axis = 1 if multi_output else -1
    return _softmax_output_core(data, label, grad_scale, ignore_label,
                                multi_output, use_ignore, normalization,
                                out_grad, smooth_alpha, axis)


@register("Activation", param_specs={
    "act_type": {"choices": ("relu", "sigmoid", "tanh", "softrelu",
                             "softsign"),
                 "doc": "Activation function to apply"}})
def _activation(data, act_type="relu"):
    return {
        "relu": jax.nn.relu,
        "sigmoid": jax.nn.sigmoid,
        "tanh": jnp.tanh,
        "softrelu": jax.nn.softplus,
        "softsign": jax.nn.soft_sign,
    }[act_type](data)


@register("LeakyReLU")
def _leaky_relu(data, gamma=None, key=None, act_type="leaky", slope=0.25,
                lower_bound=0.125, upper_bound=0.334, training=False):
    """parity: src/operator/leaky_relu.cc — multi-mode activation
    (leaky/prelu/elu/selu/gelu/rrelu). `gamma` is the learned PReLU slope.
    rrelu draws U(lower, upper) slopes per element in training (pass a PRNG
    `key`); inference uses the deterministic midpoint slope."""
    if act_type == "rrelu" and training and key is not None:
        slopes = jax.random.uniform(key, data.shape, data.dtype,
                                    lower_bound, upper_bound)
        return jnp.where(data > 0, data, slopes * data)
    if act_type == "leaky":
        return jnp.where(data > 0, data, slope * data)
    if act_type == "prelu":
        g = gamma
        if g.ndim < data.ndim:
            g = g.reshape((1, -1) + (1,) * (data.ndim - 2)) if data.ndim > 1 else g
        return jnp.where(data > 0, data, g * data)
    if act_type == "elu":
        return jnp.where(data > 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        alpha, lam = 1.6732632423543772, 1.0507009873554805
        return lam * jnp.where(data > 0, data, alpha * jnp.expm1(data))
    if act_type == "gelu":
        return exact_gelu(data)
    if act_type == "rrelu":
        mid = (lower_bound + upper_bound) / 2.0
        return jnp.where(data > 0, data, mid * data)
    raise ValueError(f"unknown LeakyReLU act_type {act_type}")


# --------------------------------------------------------------- Dropout ---

@register("Dropout", param_specs={
    "p": {"low": 0.0, "high": 1.0, "doc": "Fraction of units to drop"}})
def _dropout(data, key=None, p=0.5, mode="training", axes=(), training=True,
             cudnn_off=False):
    """parity: src/operator/nn/dropout-inl.h. `key` is a uint32 PRNG key array
    threaded by the caller (imperative: global generator; hybridized: per-call
    key input). Identity when not training or key is None.

    An independent Bernoulli(1 - p) mask (one draw shared along ``axes``),
    kept values divided by ``keep`` in the input's type, zeros elsewhere;
    the backward applies the forward's mask to the cotangent. The mask's
    words are drawn ONCE a call from XLA's bit generator
    (``jax.lax.rng_bit_generator``, the backend's default algorithm) seeded
    by ``key``, and an element is kept iff its word is under ``keep *
    2**32``: the keep probability is exact to 2**-32. Same key, same mask,
    eagerly, under ``jit``, ``lax.scan`` and any mesh layout; reproducible
    on one backend from ``mx.random.seed``; NOT the same stream on CPU and
    TPU (the default algorithm is the backend's; the reference's Dropout
    differed between cuDNN and the CPU too). Under ``vmap`` over keys the
    generator is seeded by the first key and draws the whole batch, as
    jax's own ``rbg`` keys do. Under a ``dp`` mesh every chip draws the
    whole mask and slices its rows (compiled for a described v5e:2x2, PR
    33): the same mask in every layout, not a sharded draw.

    Why not ``jax.random.bernoulli``: to XLA a threefry mask is a cheap
    elementwise function of an ``iota`` and the key (126 integer vector
    operations an element), so it never stores it and copies the hash into
    every fusion that wants the mask, the matmuls' among them: 104
    evaluations over ``u32[32,384,768]`` in ``bert_base``'s step for 25
    Dropout calls. The TPU compiler does not expand ``rng-bit-generator``,
    so it cannot copy it: one op a call, the mask packed to a bit an
    element (``u32[384,768]``) and read by every consumer.

    Device ms from a trace, forward + backward of ``res + Dropout(x @ w +
    b)`` under a sum of squared row means, alone in a jit, bfloat16
    (``benchmark/opperf.py --dropout-sweep``; my chip run, PR 33; TPU v5e),
    as threefry ``bernoulli`` / this form / threefry drawn once and held as
    the residual of a ``custom_vjp`` behind an ``optimization_barrier``:

    ====================  =====  ========  =========  =============
    rows x in x units     p      threefry  generator  threefry once
    ====================  =====  ========  =========  =============
    12,288 x 768 x 768    0.1    1.0792    0.3278     0.4925
    12,288 x 3,072 x 768  0.1    1.7335    1.0766     1.1991
    128 x 4,096 x 4,096   0.5    0.1342    0.1032     0.1142
    ====================  =====  ========  =========  =============

    The forward product with its Dropout epilogue: 0.2809 / 0.0912 / 0.0912
    ms at 768 x 768 and 0.4909 / 0.3221 / 0.3073 at 3,072 x 768 (0.294 is
    the bf16 peak's); the held mask's own fusion (``pred[12288,768]``)
    0.2089 ms, the generator op 0.0125. In ``bert_base_train_s384`` (same
    run): 310.4-312.4 / 388.7-391.6 / 364.6-367.0 samples/s, device busy
    97.14 / 76.59 / 81.67 ms a step: the generator won and is the one form.
    """
    if not training or key is None or p <= 0:
        return data
    shape = list(data.shape)
    for a in axes or ():
        shape[a] = 1
    keep = 1.0 - p
    # the generator's key is 128 bits wide: the call's 64 twice, as jax's own
    # "rbg" keys are seeded
    _, words = jax.lax.rng_bit_generator(
        jnp.concatenate([key, key]), tuple(shape), jnp.uint32)
    mask = words < _np.uint32(min(round(keep * 2 ** 32), 2 ** 32 - 1))
    return jnp.where(mask, data / keep, jnp.zeros((), data.dtype))


# -------------------------------------------------------------- Losses -----

@register("MakeLoss")
def _make_loss(data, grad_scale=1.0, valid_thresh=0.0, normalization="null"):
    return data


@register("smooth_l1")
def _smooth_l1(data, scalar=1.0):
    s2 = scalar * scalar
    return jnp.where(jnp.abs(data) < 1.0 / s2,
                     0.5 * s2 * jnp.square(data),
                     jnp.abs(data) - 0.5 / s2)


@register("CTCLoss", aliases=("ctc_loss",))
def _ctc_loss(data, label, data_lengths=None, label_lengths=None,
              use_data_lengths=False, use_label_lengths=False, blank_label="first"):
    """CTC forward-backward in log space via lax.scan (parity:
    src/operator/nn/ctc_loss.cc; 3rdparty/ctc_include warp-ctc).

    data: (T, B, V) unnormalised activations; label: (B, L) padded with -1
    (or 0 when blank_label='last' semantics match reference defaults).
    """
    T, B, V = data.shape
    logp = jax.nn.log_softmax(data, axis=-1)
    blank = 0 if blank_label == "first" else V - 1
    lab = label.astype(jnp.int32)
    L = lab.shape[1]
    if label_lengths is not None and use_label_lengths:
        lab_len = label_lengths.astype(jnp.int32)
    else:
        lab_len = jnp.sum(lab >= 0, axis=1).astype(jnp.int32)  # -1 padded
    if data_lengths is not None and use_data_lengths:
        dat_len = data_lengths.astype(jnp.int32)
    else:
        dat_len = jnp.full((B,), T, jnp.int32)
    # extended label sequence: blank a1 blank a2 ... blank  (len 2L+1)
    S = 2 * L + 1
    ext = jnp.full((B, S), blank, jnp.int32)
    ext = ext.at[:, 1::2].set(jnp.where(lab >= 0, lab, blank))
    neg_inf = jnp.asarray(-1e30, jnp.float32)
    # alpha recursion
    a0 = jnp.full((B, S), neg_inf)
    a0 = a0.at[:, 0].set(logp[0, :, blank])
    first_lab = ext[:, 1]
    a0 = a0.at[:, 1].set(jnp.take_along_axis(logp[0], first_lab[:, None], axis=1)[:, 0])

    def logaddexp3(a, b, c):
        m = jnp.maximum(jnp.maximum(a, b), c)
        m = jnp.where(jnp.isfinite(m), m, 0.0)
        return m + jnp.log(jnp.exp(a - m) + jnp.exp(b - m) + jnp.exp(c - m))

    same = (ext == jnp.roll(ext, 2, axis=1)) | (ext == blank)

    def step(alpha, lp_t):
        shift1 = jnp.concatenate([jnp.full((B, 1), neg_inf), alpha[:, :-1]], axis=1)
        shift2 = jnp.concatenate([jnp.full((B, 2), neg_inf), alpha[:, :-2]], axis=1)
        shift2 = jnp.where(same, neg_inf, shift2)
        emit = jnp.take_along_axis(lp_t, ext, axis=1)
        new = logaddexp3(alpha, shift1, shift2) + emit
        return new, new

    _, alphas = jax.lax.scan(step, a0, logp[1:])
    alphas = jnp.concatenate([a0[None], alphas], axis=0)  # (T, B, S)
    tidx = (dat_len - 1).reshape(1, B, 1)
    a_last = jnp.take_along_axis(alphas, jnp.broadcast_to(tidx, (1, B, S)), axis=0)[0]
    end1 = jnp.take_along_axis(a_last, (2 * lab_len)[:, None], axis=1)[:, 0]
    end2 = jnp.take_along_axis(a_last, jnp.maximum(2 * lab_len - 1, 0)[:, None], axis=1)[:, 0]
    m = jnp.maximum(end1, end2)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    ll = m + jnp.log(jnp.exp(end1 - m) + jnp.exp(end2 - m))
    return -ll


# ------------------------------------------------------------------- RNN ---

@register("RNN", num_outputs=3)
def _rnn(data, params, state, state_cell=None, state_size=0, num_layers=1,
         mode="lstm", bidirectional=False, p=0.0, state_outputs=False,
         projection_size=None, lstm_state_clip_min=None, lstm_state_clip_max=None,
         lstm_state_clip_nan=False, use_sequence_length=False, sequence_length=None):
    """Fused multi-layer RNN (parity: src/operator/rnn.cc:303, cuDNN RNN).

    data: (T, B, I) — TNC layout like the reference default.
    params: flat vector packed cuDNN-style per layer/direction:
        [W_x, W_h] for all gates, then all biases [b_x, b_h].
    Implemented as lax.scan over time per layer — the XLA-native analogue of
    the fused cuDNN kernel; XLA unrolls/pipelines the gate matmuls on MXU.
    """
    T, B, I = data.shape
    H = state_size
    ndir = 2 if bidirectional else 1
    ngates = {"rnn_relu": 1, "rnn_tanh": 1, "gru": 3, "lstm": 4}[mode]

    def gate_act(x):
        return x

    offset = 0

    def take(n):
        nonlocal offset
        out = jax.lax.dynamic_slice(params, (offset,), (n,))
        offset += n
        return out

    # weights first (all layers), then biases — cuDNN packing order
    weights = []
    for layer in range(num_layers):
        for d in range(ndir):
            in_sz = I if layer == 0 else H * ndir
            wx = take(ngates * H * in_sz).reshape(ngates * H, in_sz)
            wh = take(ngates * H * H).reshape(ngates * H, H)
            weights.append((wx, wh))
    biases = []
    for layer in range(num_layers):
        for d in range(ndir):
            bx = take(ngates * H)
            bh = take(ngates * H)
            biases.append((bx, bh))

    def lstm_cell(carry, x_t, wx, wh, bx, bh):
        h, c = carry
        gates = x_t @ wx.T + h @ wh.T + bx + bh
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
        g = jnp.tanh(g)
        c = f * c + i * g
        if lstm_state_clip_min is not None:
            c = jnp.clip(c, lstm_state_clip_min, lstm_state_clip_max)
        h = o * jnp.tanh(c)
        return (h, c), h

    def gru_cell(carry, x_t, wx, wh, bx, bh):
        (h,) = carry
        gx = x_t @ wx.T + bx
        gh = h @ wh.T + bh
        rx, zx, nx = jnp.split(gx, 3, axis=-1)
        rh, zh, nh = jnp.split(gh, 3, axis=-1)
        r = jax.nn.sigmoid(rx + rh)
        z = jax.nn.sigmoid(zx + zh)
        n = jnp.tanh(nx + r * nh)
        h = (1 - z) * n + z * h
        return (h,), h

    def vanilla_cell(carry, x_t, wx, wh, bx, bh):
        (h,) = carry
        act = jnp.tanh if mode == "rnn_tanh" else jax.nn.relu
        h = act(x_t @ wx.T + h @ wh.T + bx + bh)
        return (h,), h

    cell = {"lstm": lstm_cell, "gru": gru_cell,
            "rnn_relu": vanilla_cell, "rnn_tanh": vanilla_cell}[mode]

    x = data
    out_h, out_c = [], []
    for layer in range(num_layers):
        dir_outs = []
        for d in range(ndir):
            idx = layer * ndir + d
            wx, wh = weights[idx]
            bx, bh = biases[idx]
            h0 = state[idx]
            if mode == "lstm":
                carry0 = (h0, state_cell[idx])
            else:
                carry0 = (h0,)
            seq = jnp.flip(x, axis=0) if d == 1 else x

            def step(c, x_t):
                return cell(c, x_t, wx, wh, bx, bh)

            carry, ys = jax.lax.scan(step, carry0, seq)
            if d == 1:
                ys = jnp.flip(ys, axis=0)
            dir_outs.append(ys)
            out_h.append(carry[0])
            if mode == "lstm":
                out_c.append(carry[1])
        x = jnp.concatenate(dir_outs, axis=-1) if ndir == 2 else dir_outs[0]
    hn = jnp.stack(out_h, axis=0)
    cn = jnp.stack(out_c, axis=0) if mode == "lstm" else jnp.zeros_like(hn)
    return x, hn, cn


@register("UpSampling")
def _upsampling(*args, scale=1, sample_type="nearest", num_filter=0,
                multi_input_mode="concat", num_args=1, workspace=512):
    """parity: src/operator/nn/upsampling.cc — nearest/bilinear spatial
    upsampling. Nearest mode accepts MULTIPLE data inputs: each is scaled
    up to the first input's upsampled spatial size (its own factor =
    out_size / in_size), then channel-concatenated ('concat') or summed
    ('sum'). Bilinear mode takes (data, weight) and ignores the deconv
    weight — XLA's exact interpolation replaces the learned-kernel trick."""
    if sample_type != "nearest":
        data = args[0]
        n, c, h, w = data.shape
        return jax.image.resize(data, (n, c, h * scale, w * scale),
                                method="linear")
    out_h, out_w = args[0].shape[2] * scale, args[0].shape[3] * scale
    ups = []
    for i, d in enumerate(args):
        if out_h % d.shape[2] or out_w % d.shape[3]:
            raise ValueError(
                f"UpSampling: input {i} spatial {d.shape[2:]} does not "
                f"divide the target size ({out_h}, {out_w}) (= first input "
                f"* scale); the reference requires integer per-input scales")
        fh, fw = out_h // d.shape[2], out_w // d.shape[3]
        ups.append(jnp.repeat(jnp.repeat(d, fh, axis=2), fw, axis=3))
    if len(ups) == 1:
        return ups[0]
    if multi_input_mode == "sum":
        return sum(ups[1:], ups[0])
    return jnp.concatenate(ups, axis=1)


@register("Crop")
def _crop(data, like=None, offset=(0, 0), h_w=(0, 0), num_args=1,
          center_crop=False):
    """parity: src/operator/crop.cc — crop to `like`'s spatial size or an
    explicit h_w, at offset (or centered)."""
    if like is not None:
        th, tw = like.shape[2], like.shape[3]
    else:
        th, tw = h_w
    h, w = data.shape[2], data.shape[3]
    if center_crop:
        oy, ox = (h - th) // 2, (w - tw) // 2
    else:
        oy, ox = offset
    return data[:, :, oy:oy + th, ox:ox + tw]


@register("make_loss")
def _make_loss_op(data):
    """parity: make_loss (tensor/elemwise_unary_op_basic.cc) — identity
    marking a loss head."""
    return data


@register("relu6")
def _relu6(data):
    return jnp.clip(data, 0.0, 6.0)


@register("_contrib_BatchNormWithReLU", num_outputs=3)
def _batch_norm_with_relu(data, gamma, beta, moving_mean, moving_var,
                          **kwargs):
    """parity: contrib/batch_norm_relu.cc — BN + fused ReLU (XLA fuses the
    max into the BN elementwise epilogue on its own)."""
    out, mean, var = _batch_norm.fn(data, gamma, beta, moving_mean,
                                    moving_var, **kwargs)
    return jnp.maximum(out, 0), mean, var


def _register_sparse_embedding():
    """contrib/sparse_embedding -> the one Embedding emitter (row-sparse
    gradient handling lives in ndarray/sparse.py + the optimizers)."""
    from .registry import _REGISTRY

    emb = _REGISTRY["Embedding"]
    register("_contrib_SparseEmbedding")(emb.fn)


_register_sparse_embedding()
