"""Gluon basic neural-net layers.

Parity target: `python/mxnet/gluon/nn/basic_layers.py:34-759` — Sequential,
Dense, Dropout, BatchNorm, Embedding, LayerNorm, InstanceNorm, Flatten,
Lambda/HybridLambda — plus `activations.py` (Activation, LeakyReLU, PReLU,
ELU, SELU, Swish, GELU).

All compute goes through registered ops (XLA emitters); layers only manage
parameters and hyper-parameters.
"""
from __future__ import annotations

import numpy as _np

from ... import autograd, initializer as init_mod
from ...cached_op import update_state
from ..block import Block, HybridBlock

__all__ = ["Sequential", "HybridSequential", "Dense", "Dropout", "BatchNorm",
           "Embedding", "LayerNorm", "InstanceNorm", "GroupNorm", "Flatten",
           "Lambda", "HybridLambda", "Activation", "LeakyReLU", "PReLU",
           "ELU", "SELU", "Swish", "GELU"]


class Sequential(Block):
    """Sequentially-stacked blocks (parity: basic_layers.py:34)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)
        return self

    def forward(self, x, *args):
        for block in self._children.values():
            x = block(x)
        return x

    def __len__(self):
        return len(self._children)

    def __getitem__(self, key):
        layers = list(self._children.values())
        if isinstance(key, slice):
            net = type(self)(prefix=self._prefix)
            net.add(*layers[key])
            return net
        return layers[key]

    def __iter__(self):
        return iter(self._children.values())

    def hybridize(self, active=True, **kwargs):
        """parity: Sequential.hybridize warns for non-hybrid children; here
        children hybridize individually (whole-graph capture requires
        HybridSequential)."""
        for child in self._children.values():
            if isinstance(child, HybridBlock):
                child.hybridize(active, **kwargs)


class HybridSequential(HybridBlock):
    """Sequential that traces as one compiled graph (parity:
    basic_layers.py:103)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)
        return self

    def forward(self, x, *args):
        for block in self._children.values():
            x = block(x)
        return x

    def __len__(self):
        return len(self._children)

    def __getitem__(self, key):
        layers = list(self._children.values())
        if isinstance(key, slice):
            net = type(self)(prefix=self._prefix)
            net.add(*layers[key])
            return net
        return layers[key]

    def __iter__(self):
        return iter(self._children.values())


class Dense(HybridBlock):
    """Fully-connected layer (parity: basic_layers.py:152). weight shape
    (units, in_units); in_units=0 → deferred."""

    def __init__(self, units, activation=None, use_bias=True, flatten=True,
                 dtype=_np.float32, weight_initializer=None,
                 bias_initializer="zeros", in_units=0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._units = units
        self._in_units = in_units
        self._flatten = flatten
        self._act_type = activation
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(units, in_units), dtype=dtype,
                init=weight_initializer, allow_deferred_init=True)
            if use_bias:
                self.bias = self.params.get(
                    "bias", shape=(units,), dtype=dtype,
                    init=init_mod.create(bias_initializer),
                    allow_deferred_init=True)
            else:
                self.bias = None

    def infer_shape(self, x, *args):
        in_units = int(_np.prod(x.shape[1:])) if self._flatten else x.shape[-1]
        self.weight.shape = (self._units, in_units)
        if self.bias is not None:
            self.bias.shape = (self._units,)

    def hybrid_forward(self, F, x, weight=None, bias=None):
        if bias is None:
            out = F.invoke("FullyConnected", x, weight, num_hidden=self._units,
                           no_bias=True, flatten=self._flatten)
        else:
            out = F.invoke("FullyConnected", x, weight, bias,
                           num_hidden=self._units, flatten=self._flatten)
        if self._act_type:
            out = F.invoke("Activation", out, act_type=self._act_type)
        return out

    def __repr__(self):
        shape = self.weight.shape
        return (f"Dense({shape[1] if shape and len(shape) > 1 else None} -> "
                f"{self._units}, "
                f"{self._act_type if self._act_type else 'linear'})")


class Dropout(HybridBlock):
    """parity: basic_layers.py:262 — active only in train_mode (autograd
    training flag), scaled at train time. Every call draws one key from the
    global stream (`random.next_key()`; inside a compiled graph the scope's)
    and the op draws the mask once from XLA's bit generator seeded by it:
    reproducible on one backend from `mx.random.seed`, not the same stream
    on CPU and TPU."""

    def __init__(self, rate, axes=(), prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._rate = rate
        self._axes = axes

    def hybrid_forward(self, F, x):
        if self._rate <= 0 or not autograd.is_training():
            return x
        from ... import random as _rand
        from ...ndarray import NDArray

        key = NDArray(_rand.next_key())
        return F.invoke("Dropout", x, key, p=self._rate, axes=self._axes,
                        training=True)

    def __repr__(self):
        return f"Dropout(p = {self._rate}, axes={self._axes})"


class BatchNorm(HybridBlock):
    """parity: basic_layers.py:310 — running stats are aux state updated
    during training forward; functional writeback via update_state keeps the
    compiled graph pure."""

    def __init__(self, axis=1, momentum=0.9, epsilon=1e-5, center=True,
                 scale=True, use_global_stats=False, beta_initializer="zeros",
                 gamma_initializer="ones", running_mean_initializer="zeros",
                 running_variance_initializer="ones", in_channels=0,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._axis = axis
        self._momentum = momentum
        self._epsilon = epsilon
        self._center = center
        self._scale = scale
        self._use_global_stats = use_global_stats
        self._in_channels = in_channels
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", shape=(in_channels,),
                init=gamma_initializer, allow_deferred_init=True,
                differentiable=scale)
            self.beta = self.params.get(
                "beta", shape=(in_channels,),
                init=beta_initializer, allow_deferred_init=True,
                differentiable=center)
            self.running_mean = self.params.get(
                "running_mean", shape=(in_channels,), grad_req="null",
                init=running_mean_initializer, allow_deferred_init=True,
                differentiable=False)
            self.running_var = self.params.get(
                "running_var", shape=(in_channels,), grad_req="null",
                init=running_variance_initializer, allow_deferred_init=True,
                differentiable=False)

    def infer_shape(self, x, *args):
        channels = x.shape[self._axis]
        for p in (self.gamma, self.beta, self.running_mean, self.running_var):
            p.shape = (channels,)

    def cast(self, dtype):
        if str(dtype) in ("float16", "bfloat16"):
            dtype = _np.float32  # stats and affine stay fp32 (AMP rule)
        super().cast(dtype)

    def hybrid_forward(self, F, x, gamma=None, beta=None, running_mean=None,
                       running_var=None):
        training = autograd.is_training() and not self._use_global_stats
        out, mean, var = F.invoke(
            "BatchNorm", x, gamma, beta, running_mean, running_var,
            eps=self._epsilon, momentum=self._momentum,
            fix_gamma=not self._scale,
            use_global_stats=self._use_global_stats, axis=self._axis,
            training=training)
        if training:
            m = self._momentum
            update_state(running_mean,
                         running_mean * m + mean.astype(running_mean.dtype) * (1 - m))
            update_state(running_var,
                         running_var * m + var.astype(running_var.dtype) * (1 - m))
        return out

    def __repr__(self):
        return (f"BatchNorm(axis={self._axis}, eps={self._epsilon}, "
                f"momentum={self._momentum}, in_channels="
                f"{self.gamma.shape[0] if self.gamma.shape else None})")


class Embedding(HybridBlock):
    """parity: basic_layers.py:474."""

    def __init__(self, input_dim, output_dim, dtype=_np.float32,
                 weight_initializer=None, sparse_grad=False, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._input_dim = input_dim
        self._output_dim = output_dim
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(input_dim, output_dim), dtype=dtype,
                init=weight_initializer)

    def hybrid_forward(self, F, x, weight=None):
        return F.invoke("Embedding", x, weight, input_dim=self._input_dim,
                        output_dim=self._output_dim)

    def __repr__(self):
        return f"Embedding({self._input_dim} -> {self._output_dim})"


class LayerNorm(HybridBlock):
    """parity: basic_layers.py:560."""

    def __init__(self, axis=-1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._axis = axis
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True, differentiable=scale)
            self.beta = self.params.get(
                "beta", shape=(in_channels,), init=beta_initializer,
                allow_deferred_init=True, differentiable=center)

    def infer_shape(self, x, *args):
        channels = x.shape[self._axis]
        self.gamma.shape = (channels,)
        self.beta.shape = (channels,)

    def hybrid_forward(self, F, x, gamma=None, beta=None):
        return F.invoke("LayerNorm", x, gamma, beta, axis=self._axis,
                        eps=self._epsilon)


class InstanceNorm(HybridBlock):
    """parity: basic_layers.py:648."""

    def __init__(self, axis=1, epsilon=1e-5, center=True, scale=False,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._axis = axis
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True, differentiable=scale)
            self.beta = self.params.get(
                "beta", shape=(in_channels,), init=beta_initializer,
                allow_deferred_init=True, differentiable=center)

    def infer_shape(self, x, *args):
        channels = x.shape[self._axis]
        self.gamma.shape = (channels,)
        self.beta.shape = (channels,)

    def hybrid_forward(self, F, x, gamma=None, beta=None):
        return F.invoke("InstanceNorm", x, gamma, beta, eps=self._epsilon)


class GroupNorm(HybridBlock):
    """parity: gluon/nn/basic_layers.py GroupNorm (num_groups over channel
    axis 1)."""

    def __init__(self, num_groups=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._num_groups = num_groups
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True, differentiable=scale)
            self.beta = self.params.get(
                "beta", shape=(in_channels,), init=beta_initializer,
                allow_deferred_init=True, differentiable=center)

    def infer_shape(self, x, *args):
        channels = x.shape[1]
        self.gamma.shape = (channels,)
        self.beta.shape = (channels,)

    def hybrid_forward(self, F, x, gamma=None, beta=None):
        return F.invoke("GroupNorm", x, gamma, beta,
                        num_groups=self._num_groups, eps=self._epsilon)


class Flatten(HybridBlock):
    """parity: basic_layers.py:736."""

    def hybrid_forward(self, F, x):
        return F.invoke("Flatten", x)

    def __repr__(self):
        return "Flatten"


class Lambda(Block):
    """parity: basic_layers.py:755 — wrap a function as a Block."""

    def __init__(self, function, prefix=None):
        super().__init__(prefix=prefix)
        if isinstance(function, str):
            from ... import ndarray as F

            fn = getattr(F, function, None)
            if fn is None:
                fn = lambda *a, _n=function, **k: F.invoke(_n, *a, **k)
            self._fn = fn
        else:
            self._fn = function

    def forward(self, *args):
        return self._fn(*args)


class HybridLambda(HybridBlock):
    """parity: basic_layers.py HybridLambda."""

    def __init__(self, function, prefix=None):
        super().__init__(prefix=prefix)
        if isinstance(function, str):
            self._fn_name = function
            self._fn = None
        else:
            self._fn = function
            self._fn_name = None

    def hybrid_forward(self, F, *args):
        if self._fn is not None:
            return self._fn(F, *args)
        fn = getattr(F, self._fn_name, None)
        if fn is None:
            return F.invoke(self._fn_name, *args)
        return fn(*args)


# ------------------------------------------------------------ activations --

class Activation(HybridBlock):
    """parity: gluon/nn/activations.py:30."""

    def __init__(self, activation, prefix=None, params=None):
        self._act_type = activation  # before super(): _alias() needs it
        super().__init__(prefix=prefix, params=params)

    def _alias(self):
        return self._act_type

    def hybrid_forward(self, F, x):
        return F.invoke("Activation", x, act_type=self._act_type)

    def __repr__(self):
        return f"Activation({self._act_type})"


class LeakyReLU(HybridBlock):
    def __init__(self, alpha=0.01, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._alpha = alpha

    def hybrid_forward(self, F, x):
        return F.invoke("LeakyReLU", x, act_type="leaky", slope=self._alpha)


class PReLU(HybridBlock):
    def __init__(self, alpha_initializer=init_mod.Constant(0.25),
                 in_channels=1, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.alpha = self.params.get("alpha", shape=(in_channels,),
                                         init=alpha_initializer)

    def hybrid_forward(self, F, x, alpha=None):
        return F.invoke("LeakyReLU", x, alpha, act_type="prelu")


class ELU(HybridBlock):
    def __init__(self, alpha=1.0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._alpha = alpha

    def hybrid_forward(self, F, x):
        return F.invoke("LeakyReLU", x, act_type="elu", slope=self._alpha)


class SELU(HybridBlock):
    def hybrid_forward(self, F, x):
        return F.invoke("LeakyReLU", x, act_type="selu")


class Swish(HybridBlock):
    def __init__(self, beta=1.0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._beta = beta

    def hybrid_forward(self, F, x):
        return x * F.invoke("sigmoid", x * self._beta)


class GELU(HybridBlock):
    def hybrid_forward(self, F, x):
        return F.invoke("LeakyReLU", x, act_type="gelu")
