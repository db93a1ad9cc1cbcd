"""Blocks of decoder language models (beyond the reference, whose newest
text model is a post-LN encoder): RMS norm, the gated SiLU feed-forward,
multi-head latent attention, grouped-query attention with rotary
positions and q/k norms, the sparse expert layer, LFM2's gated short
convolution, and the mixers of a hybrid decoder: a Mamba-1 state-space
layer, differential attention (windowed, full, or reading another layer's
keys and values) and the gated memory unit. The ops under them are in
``ops/text_ops.py``; ``gluon.model_zoo.text`` builds models of them.

The ``jax.named_scope`` names here (``mla.project``, ``mla.attention``,
``moe.shared``, ``moe.balance``; ``moe.route`` and ``moe.experts`` inside
``parallel.moe.routed_experts``; ``ssm.project``, ``ssm.conv``,
``ssm.scan``, ``gmu``, ``attn.window``, ``attn.full``, ``attn.cross``,
``attn.project``, ``sconv.project``, ``sconv.gate``) are what a join of
the device trace with the HLO will group by.
"""
from __future__ import annotations

import numpy as _np

from ... import autograd, initializer
from ...cached_op import update_state
from ..block import HybridBlock
from .basic_layers import Dense

__all__ = ["RMSNorm", "GatedMLP", "MLAttention", "GQAttention", "SparseMoE",
           "ShortConv", "LayerNormF32", "MambaMixer", "DiffAttention",
           "GatedMemoryUnit"]


def _scope(name):
    import jax

    return jax.named_scope(name)


class RMSNorm(HybridBlock):
    """``gamma * x / sqrt(mean(x^2) + eps)`` over the last axis, no
    centring and no bias; statistics in float32."""

    def __init__(self, in_channels, epsilon=1e-6, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(in_channels,),
                                         init="ones")

    def hybrid_forward(self, F, x, gamma=None):
        return F.invoke("_contrib_rms_norm", x, gamma, eps=self._epsilon)

    def __repr__(self):
        return f"RMSNorm({self.gamma.shape[0]}, eps={self._epsilon})"


class GatedMLP(HybridBlock):
    """``down(silu(gate(x)) * up(x))``, no biases."""

    def __init__(self, units, hidden_size, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.gate = Dense(hidden_size, use_bias=False, flatten=False,
                              in_units=units)
            self.up = Dense(hidden_size, use_bias=False, flatten=False,
                            in_units=units)
            self.down = Dense(units, use_bias=False, flatten=False,
                              in_units=hidden_size)

    def hybrid_forward(self, F, x):
        return self.down(F.invoke("_contrib_gated_silu", self.gate(x),
                                  self.up(x)))


class MLAttention(HybridBlock):
    """Multi-head latent attention (DeepSeek-V2/V3) without the query's
    low-rank step, causal, over (B, S, units).

    ``q = W_q x`` gives ``num_heads`` heads of ``qk_nope + qk_rope``;
    ``[c, k_r] = W_kva x`` a latent of ``kv_lora_rank`` and ONE rotary key
    of ``qk_rope``; ``[k_n, v] = W_kvb RMSNorm(c)`` the heads' keys without
    position (``qk_nope``) and values (``v_head_dim``). Rotary positions go
    on ``q``'s last ``qk_rope`` and on ``k_r``, which every head shares;
    ``k = [k_n, k_r]``. Softmax of ``q k^T / sqrt(qk_nope + qk_rope)``
    through ``F.contrib.flash_attention``: keys are wider than values, so
    the flash kernel runs with a value width of its own, in blocks it
    picks from the shape.
    """

    def __init__(self, units, num_heads, kv_lora_rank, qk_nope_head_dim,
                 qk_rope_head_dim, v_head_dim, rope_theta=10000.0,
                 rope_interleave=True, epsilon=1e-6, interpret=False,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._heads = num_heads
        self._rank = kv_lora_rank
        self._nope, self._rope, self._dv = \
            qk_nope_head_dim, qk_rope_head_dim, v_head_dim
        self._rope_kwargs = {"theta": float(rope_theta),
                             "interleave": bool(rope_interleave)}
        self._interpret = interpret   # the kernel in the interpreter (CPU)
        with self.name_scope():
            self.q_proj = Dense(num_heads * (self._nope + self._rope),
                                use_bias=False, flatten=False,
                                in_units=units)
            self.kv_a_proj = Dense(kv_lora_rank + self._rope,
                                   use_bias=False, flatten=False,
                                   in_units=units)
            self.kv_a_norm = RMSNorm(kv_lora_rank, epsilon=epsilon)
            self.kv_b_proj = Dense(num_heads * (self._nope + self._dv),
                                   use_bias=False, flatten=False,
                                   in_units=kv_lora_rank)
            self.o_proj = Dense(units, use_bias=False, flatten=False,
                                in_units=num_heads * self._dv)

    def hybrid_forward(self, F, x):
        heads, nope, rope = self._heads, self._nope, self._rope

        def split_heads(t):  # (B, S, H * D) -> (B, H, S, D)
            return F.transpose(F.reshape(t, shape=(0, 0, heads, -1)),
                               axes=(0, 2, 1, 3))

        def rotary(t):
            return F.invoke("_contrib_rotary_embedding", t,
                            **self._rope_kwargs)

        with _scope("mla.project"):
            q = split_heads(self.q_proj(x))
            kv_a = self.kv_a_proj(x)
            latent = self.kv_a_norm(
                F.slice_axis(kv_a, axis=-1, begin=0, end=self._rank))
            k_rope = F.expand_dims(
                F.slice_axis(kv_a, axis=-1, begin=self._rank, end=None),
                axis=1)                                     # (B, 1, S, rope)
            kv = split_heads(self.kv_b_proj(latent))
            q = F.concat(
                F.slice_axis(q, axis=-1, begin=0, end=nope),
                rotary(F.slice_axis(q, axis=-1, begin=nope, end=None)),
                dim=-1)
            k = F.concat(
                F.slice_axis(kv, axis=-1, begin=0, end=nope),
                F.broadcast_axis(rotary(k_rope), axis=(1,), size=(heads,)),
                dim=-1)
            v = F.slice_axis(kv, axis=-1, begin=nope, end=None)
        with _scope("mla.attention"):
            out = F.contrib.flash_attention(
                q, k, v, scale=float((nope + rope) ** -0.5), causal=True,
                interpret=self._interpret)
        with _scope("mla.project"):
            out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                            shape=(0, 0, -1))
            return self.o_proj(out)

    def __repr__(self):
        return (f"MLAttention(heads={self._heads}, rank={self._rank}, "
                f"qk={self._nope}|{self._rope}, v={self._dv}, "
                f"rope={self._rope_kwargs})")


class GQAttention(HybridBlock):
    """Causal grouped-query attention with rotary positions and q/k norms
    (the ``full_attention`` operator of ``lfm2_moe``) over (B, S, units),
    no biases: ``[q; k; v] = W_qkv x`` gives ``num_heads`` query heads and
    ``num_kv_heads`` key and value heads of ``units / num_heads``; every
    head's q and k go through an RMS norm of that width (``q_norm``,
    ``k_norm``: one gain each, shared by the heads), then rotate-half
    rotary positions 0..S-1 (``rope_theta``); key head ``j`` serves query
    heads ``j g .. j g + g - 1`` (``g = num_heads / num_kv_heads``);
    softmax of ``q k^T / sqrt(width)`` through
    ``F.contrib.flash_attention``, whose kernels take grouped keys as they
    are; ``out = W_o attention``."""

    def __init__(self, units, num_heads, num_kv_heads, rope_theta=10000.0,
                 epsilon=1e-6, interpret=False, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if units % num_heads or num_heads % num_kv_heads:
            raise ValueError(
                f"{num_heads} query heads over {num_kv_heads} key heads "
                f"do not divide {units} units")
        self._heads, self._kv_heads = int(num_heads), int(num_kv_heads)
        self._d = units // num_heads
        self._theta = float(rope_theta)
        self._interpret = interpret   # the kernel in the interpreter (CPU)
        with self.name_scope():
            self.qkv_proj = Dense((num_heads + 2 * num_kv_heads) * self._d,
                                  use_bias=False, flatten=False,
                                  in_units=units)
            self.q_norm = RMSNorm(self._d, epsilon=epsilon)
            self.k_norm = RMSNorm(self._d, epsilon=epsilon)
            self.o_proj = Dense(units, use_bias=False, flatten=False,
                                in_units=units)

    def hybrid_forward(self, F, x):
        d, q_end = self._d, self._heads * self._d
        k_end = q_end + self._kv_heads * d

        def heads(t, norm=None):  # (B, S, H * d) -> (B, H, S, d)
            t = F.reshape(t, shape=(0, 0, -1, d))
            t = F.transpose(t if norm is None else norm(t),
                            axes=(0, 2, 1, 3))
            return t if norm is None else F.invoke(
                "_contrib_rotary_embedding", t, theta=self._theta)

        with _scope("attn.project"):
            qkv = self.qkv_proj(x)
            q = heads(F.slice_axis(qkv, axis=-1, begin=0, end=q_end),
                      self.q_norm)
            k = heads(F.slice_axis(qkv, axis=-1, begin=q_end, end=k_end),
                      self.k_norm)
            v = heads(F.slice_axis(qkv, axis=-1, begin=k_end, end=None))
        with _scope("attn.full"):
            out = F.contrib.flash_attention(
                q, k, v, scale=float(d ** -0.5), causal=True,
                interpret=self._interpret)
        with _scope("attn.project"):
            return self.o_proj(F.reshape(
                F.transpose(out, axes=(0, 2, 1, 3)), shape=(0, 0, -1)))

    def __repr__(self):
        return (f"GQAttention(heads={self._heads}|{self._kv_heads} x "
                f"{self._d}, theta={self._theta})")


class ShortConv(HybridBlock):
    """LFM2's double-gated short convolution over (B, S, units), no
    biases: ``[b; c; x] = W_in u`` (three streams of ``units``, in that
    order); ``z = b * x``; a causal depthwise convolution of ``taps``
    taps over positions (``v_t = sum_k w[:, k] z_(t - taps + 1 + k)``,
    zero before the first); ``out = W_out (c * v)``. What lies between the
    projections is one op (``_contrib_gated_short_conv``)."""

    def __init__(self, units, taps=3, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.in_proj = Dense(3 * units, use_bias=False, flatten=False,
                                 in_units=units)
            self.conv_weight = self.params.get("conv_weight",
                                               shape=(units, taps))
            self.out_proj = Dense(units, use_bias=False, flatten=False,
                                  in_units=units)

    def hybrid_forward(self, F, u, conv_weight=None):
        with _scope("sconv.project"):
            bcx = self.in_proj(u)
        with _scope("sconv.gate"):
            gated = F.invoke("_contrib_gated_short_conv", bcx, conv_weight)
        with _scope("sconv.project"):
            return self.out_proj(gated)

    def __repr__(self):
        return (f"ShortConv({self.conv_weight.shape[0]}, "
                f"taps={self.conv_weight.shape[1]})")


class _KeepsFloat32(HybridBlock):
    """A block some of whose own parameters (``_FLOAT32``) stay float32
    under ``cast``."""

    _FLOAT32 = ()

    def cast(self, dtype):
        self._clear_cached_op()
        for child in self._children.values():
            child.cast(dtype)
        for name, p in self._reg_params.items():
            p.cast(_np.float32 if name in self._FLOAT32 else dtype)


class SparseMoE(_KeepsFloat32):
    """Sparse expert layer, DeepSeek-V3 style: a sigmoid router over
    ``num_experts`` with a selection bias (``e_score_correction_bias``, a
    buffer outside the gradient), ``top_k`` experts a token, their weights
    renormalised and scaled; every expert a gated SiLU MLP of
    ``hidden_size``; ``num_shared`` shared experts (one MLP of
    ``num_shared * hidden_size``) that see every token.

    ``experts_held=(first, count)`` makes this the share of an
    expert-parallel layer that one device holds: the router keeps its
    width, only ``count`` experts' weights exist here, and the output is
    their part of the routed sum plus the shared experts' (which every
    device computes alike). The default holds all: the whole layer.

    In training each call adds to three counters (auxiliary state, like
    BatchNorm's running statistics): ``load_pairs`` (count,) the (token,
    expert) pairs each held expert got, ``load_peak`` the busiest held
    expert's pairs, summed over calls, and ``load_calls``. ``expert_load``
    reads them.

    ``bias_update_rate`` above 0 makes the selection bias balance the load
    itself (the auxiliary-loss-free rule of arXiv:2408.15664, which
    DeepSeek-V3 trains the same buffer by): after a training call, with
    ``c`` (num_experts,) the pairs each expert of the WHOLE router got
    from the call's tokens, ``bias += rate * sign(mean(c) - c)``, in
    float32, outside the gradient, like the counters. The layer then has
    three more buffers: ``route_pairs`` (num_experts,), ``c`` summed over
    calls (what ``load_pairs`` is for the experts held, for all);
    ``route_recent`` (``RECENT_CALLS``, num_experts), ``c`` of each of the
    last calls, call ``n`` in row ``n mod RECENT_CALLS`` (whether the load
    is even at the end of a run as at its start); and ``bias_rate`` (1,),
    the rate: a traced scalar, so a schedule sets it and compiles
    nothing. On one device ``c`` is local: nothing stands in
    for other ranks' counts. The default 0 builds the layer without the
    rule or either buffer, the program it was. ``norm_eps`` is what the
    renormalising sum is kept from zero by.
    """

    RECENT_CALLS = 128

    def __init__(self, units, hidden_size, num_experts, top_k,
                 num_shared=0, routed_scaling_factor=1.0, norm_topk=True,
                 experts_held=None, bias_update_rate=0.0, norm_eps=1e-20,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        first, count = experts_held or (0, num_experts)
        if not (0 <= first and count > 0 and first + count <= num_experts):
            raise ValueError(
                f"experts_held {experts_held} lies outside the "
                f"{num_experts} experts")
        self._held = (int(first), int(count))
        self._num_experts = num_experts
        self._route_kwargs = {
            "top_k": int(top_k), "first_expert": int(first),
            "scale": float(routed_scaling_factor),
            "norm_topk": bool(norm_topk), "norm_eps": float(norm_eps),
            "route_counts": bias_update_rate > 0}
        with self.name_scope():
            self.router_weight = self.params.get(
                "router_weight", shape=(num_experts, units))
            self.router_bias = self.params.get(
                "router_bias", shape=(num_experts,), init="zeros",
                grad_req="null", differentiable=False)
            self.gate_weight = self.params.get(
                "gate_weight", shape=(count, units, hidden_size))
            self.up_weight = self.params.get(
                "up_weight", shape=(count, units, hidden_size))
            self.down_weight = self.params.get(
                "down_weight", shape=(count, hidden_size, units))
            self.load_pairs = self.params.get(
                "load_pairs", shape=(count,), init="zeros",
                grad_req="null", differentiable=False)
            self.load_peak = self.params.get(
                "load_peak", shape=(1,), init="zeros", grad_req="null",
                differentiable=False)
            self.load_calls = self.params.get(
                "load_calls", shape=(1,), init="zeros", grad_req="null",
                differentiable=False)
            if bias_update_rate > 0:
                self.route_pairs = self.params.get(
                    "route_pairs", shape=(num_experts,), init="zeros",
                    grad_req="null", differentiable=False)
                self.route_recent = self.params.get(
                    "route_recent", shape=(self.RECENT_CALLS, num_experts),
                    init="zeros", grad_req="null", differentiable=False)
                self.bias_rate = self.params.get(
                    "bias_rate", shape=(1,), grad_req="null",
                    init=initializer.Constant(float(bias_update_rate)),
                    differentiable=False)
            self.shared = GatedMLP(units, num_shared * hidden_size) \
                if num_shared else None

    # the selection bias and the counters stay float32 (the router scores
    # are float32; a bfloat16 counter stops counting at 256)
    _FLOAT32 = ("router_bias", "load_pairs", "load_peak", "load_calls",
                "route_pairs", "route_recent", "bias_rate")

    def hybrid_forward(self, F, x, router_weight=None, router_bias=None,
                       gate_weight=None, up_weight=None, down_weight=None,
                       load_pairs=None, load_peak=None, load_calls=None,
                       route_pairs=None, route_recent=None, bias_rate=None):
        y, load, *counts = F.invoke(
            "_contrib_sparse_moe", x, router_weight, router_bias,
            gate_weight, up_weight, down_weight, **self._route_kwargs)
        if autograd.is_training():
            if counts:
                with _scope("moe.balance"):
                    c = counts[0]
                    update_state(router_bias, router_bias
                                 + bias_rate * F.sign(c.mean() - c))
                    update_state(route_pairs, route_pairs + c)
                    # row ``load_calls`` mod R, before the call is counted
                    update_state(route_recent, F.invoke(
                        "_contrib_ring_write", route_recent, c, load_calls))
            update_state(load_pairs, load_pairs + load)
            update_state(load_peak, load_peak + load.max())
            update_state(load_calls, load_calls + 1)
        if self.shared is not None:
            with _scope("moe.shared"):
                y = y + self.shared(x)
        return y

    def expert_load(self):
        """``{"first_expert", "pairs": [per held expert], "peak",
        "calls"}`` since the counters were last zeroed (one read of the
        device), and ``"route_pairs": [per expert of the router]`` where
        the layer balances itself."""
        names = ["load_pairs", "load_peak", "load_calls"] + [
            n for n in ("route_pairs", "route_recent")
            if n in self._reg_params]
        pairs, peak, calls, *routed = (
            self._reg_params[n].data().asnumpy().astype(float)
            for n in names)
        out = {"first_expert": self._held[0], "pairs": pairs.tolist(),
               "peak": float(peak[0]), "calls": float(calls[0])}
        if routed:
            total, ring = routed
            n = int(calls[0])
            # oldest call first
            rows = [i % len(ring) for i in range(max(0, n - len(ring)), n)]
            out.update(route_pairs=total.tolist(),
                       route_recent=ring[rows].tolist())
        return out

    def zero_load(self):
        """Zero the counters (the bias stays)."""
        for name in ("load_pairs", "load_peak", "load_calls", "route_pairs",
                     "route_recent"):
            p = self._reg_params.get(name)
            if p is not None:
                p.set_data(p.data() * 0)

    def __repr__(self):
        return (f"SparseMoE(experts={self._num_experts}, "
                f"held={self._held}, {self._route_kwargs}, "
                f"shared={self.shared!r})")


class LayerNormF32(HybridBlock):
    """Layer norm over the last axis with gain and bias, the statistics in
    float32 whatever the input's type (``nn.LayerNorm`` takes them in the
    input's)."""

    def __init__(self, in_channels, epsilon=1e-5, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(in_channels,),
                                         init="ones")
            self.beta = self.params.get("beta", shape=(in_channels,),
                                        init="zeros")

    def hybrid_forward(self, F, x, gamma=None, beta=None):
        return F.invoke("_contrib_layer_norm", x, gamma, beta,
                        eps=self._epsilon)

    def __repr__(self):
        return f"LayerNormF32({self.gamma.shape[0]}, eps={self._epsilon})"


class MambaMixer(_KeepsFloat32):
    """The Mamba-1 mixer (arXiv:2312.00752) over (B, S, units), returning
    ``(out, scanned)``:

    ``[x; z] = W_in u`` (2 x ``expand * units``); ``x = silu(conv(x))``, a
    causal depthwise convolution of ``d_conv`` taps with bias; ``[d; B; C]
    = W_x x`` (``dt_rank`` | ``d_state`` | ``d_state``); the selective scan
    with step ``softplus(W_dt d + b_dt)``, decay ``-exp(A_log)`` and skip
    ``D`` (op ``_contrib_selective_scan``: kernel family
    ``selective_scan``; step, decay and state float32); ``out = W_out
    (scanned * silu(z))``. ``scanned`` (B, S, ``expand * units``) is the
    scan's output BEFORE the gate: what a decoder-hybrid-decoder hands on
    as its memory. ``A_log`` and ``D`` stay float32 under ``cast``."""

    _FLOAT32 = ("a_log", "d_skip")

    def __init__(self, units, d_state=16, d_conv=4, expand=2, dt_rank=None,
                 interpret=False, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        inner = expand * units
        self._inner, self._state = inner, d_state
        self._rank = dt_rank or -(-units // 16)
        self._interpret = interpret   # the kernel in the interpreter (CPU)
        with self.name_scope():
            self.in_proj = Dense(2 * inner, use_bias=False, flatten=False,
                                 in_units=units)
            self.conv_weight = self.params.get("conv_weight",
                                               shape=(inner, d_conv))
            self.conv_bias = self.params.get("conv_bias", shape=(inner,),
                                             init="zeros")
            self.x_proj = Dense(self._rank + 2 * d_state, use_bias=False,
                                flatten=False, in_units=inner)
            self.dt_weight = self.params.get("dt_weight",
                                             shape=(inner, self._rank))
            self.dt_bias = self.params.get("dt_bias", shape=(inner,),
                                           init="zeros")
            self.a_log = self.params.get("a_log", shape=(inner, d_state),
                                         init="zeros")
            self.d_skip = self.params.get("d_skip", shape=(inner,),
                                          init="ones")
            self.out_proj = Dense(units, use_bias=False, flatten=False,
                                  in_units=inner)

    def hybrid_forward(self, F, u, conv_weight=None, conv_bias=None,
                       dt_weight=None, dt_bias=None, a_log=None,
                       d_skip=None):
        inner, state, rank = self._inner, self._state, self._rank

        def part(t, begin, end):
            return F.slice_axis(t, axis=-1, begin=begin, end=end)

        with _scope("ssm.project"):
            xz = self.in_proj(u)
            x, z = part(xz, 0, inner), part(xz, inner, None)
        with _scope("ssm.conv"):
            x = F.invoke("_contrib_causal_conv1d", x, conv_weight, conv_bias,
                         activation="silu")
        with _scope("ssm.project"):
            dbc = self.x_proj(x)
            # the step's bias goes in with the softplus, in float32
            dt = F.invoke("FullyConnected", part(dbc, 0, rank), dt_weight,
                          num_hidden=inner, no_bias=True, flatten=False)
        with _scope("ssm.scan"):
            scanned = F.invoke(
                "_contrib_selective_scan", x, dt, a_log,
                part(dbc, rank, rank + state), part(dbc, rank + state, None),
                d_skip, dt_bias, interpret=self._interpret)
        with _scope("ssm.project"):
            return self.out_proj(
                F.invoke("_contrib_gated_silu", z, scanned)), scanned

    def __repr__(self):
        return (f"MambaMixer(inner={self._inner}, state={self._state}, "
                f"dt_rank={self._rank})")


class DiffAttention(_KeepsFloat32):
    """Causal differential attention with grouped keys (op
    ``_contrib_diff_attention``) over (B, S, units), returning ``(out, k,
    v)``: ``q = W_q u + b_q``, ``[k; v] = W_kv u + b_kv`` (``num_kv_heads``
    heads each), ``out = W_o attention + b_o``. ``window`` is the number
    of keys a position sees (None: all before it). ``cross=True`` builds
    no key/value projection: the call takes another layer's projected ``k``
    and ``v`` and hands them on. ``lam_init`` is the layer's (0.8 - 0.6
    exp(-0.3 depth) in the paper); the four ``lam_*`` vectors and the
    sub-norm's gain stay float32 under ``cast``."""

    _FLOAT32 = ("lam_q1", "lam_k1", "lam_q2", "lam_k2", "subln_gamma")

    def __init__(self, units, num_heads, num_kv_heads, lam_init,
                 window=None, cross=False, epsilon=1e-5, interpret=False,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if num_heads % 2 or num_kv_heads % 2 or \
                num_heads % num_kv_heads or units % num_heads:
            raise ValueError(
                f"differential attention pairs its heads: {num_heads} "
                f"query and {num_kv_heads} key heads over {units} units")
        d = units // num_heads
        self._span = "attn.cross" if cross else \
            "attn.full" if window is None else "attn.window"
        self._kwargs = {
            "num_heads": int(num_heads), "num_kv_heads": int(num_kv_heads),
            "lam_init": float(lam_init), "eps": float(epsilon),
            "interpret": bool(interpret),
            **({} if window is None else {"window": int(window)})}
        self._kv_units = num_kv_heads * d
        with self.name_scope():
            self.q_proj = Dense(units, use_bias=True, flatten=False,
                                in_units=units)
            self.kv_proj = None if cross else Dense(
                2 * self._kv_units, use_bias=True, flatten=False,
                in_units=units)
            self.o_proj = Dense(units, use_bias=True, flatten=False,
                                in_units=units)
            for name in self._FLOAT32[:4]:
                setattr(self, name, self.params.get(name, shape=(d,),
                                                    init="zeros"))
            self.subln_gamma = self.params.get(
                "subln_gamma", shape=(2 * d,), init="ones")

    def hybrid_forward(self, F, u, k=None, v=None, lam_q1=None, lam_k1=None,
                       lam_q2=None, lam_k2=None, subln_gamma=None):
        with _scope(self._span):
            if self.kv_proj is not None:
                kv = self.kv_proj(u)
                k = F.slice_axis(kv, axis=-1, begin=0, end=self._kv_units)
                v = F.slice_axis(kv, axis=-1, begin=self._kv_units, end=None)
            out = F.invoke("_contrib_diff_attention", self.q_proj(u), k, v,
                           lam_q1, lam_k1, lam_q2, lam_k2, subln_gamma,
                           **self._kwargs)
            return self.o_proj(out), k, v

    def __repr__(self):
        return f"DiffAttention({self._span}, {self._kwargs})"


class GatedMemoryUnit(HybridBlock):
    """``W_2 (memory * silu(W_1 u))`` (arXiv:2507.06607): ``u`` (B, S,
    units) gates, element by element, a memory (B, S, ``memory_units``)
    that an earlier layer made."""

    def __init__(self, units, memory_units, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.in_proj = Dense(memory_units, use_bias=False, flatten=False,
                                 in_units=units)
            self.out_proj = Dense(units, use_bias=False, flatten=False,
                                  in_units=memory_units)

    def hybrid_forward(self, F, u, memory):
        with _scope("gmu"):
            return self.out_proj(F.invoke("_contrib_gated_silu",
                                          self.in_proj(u), memory))


def mirror_expert_load(moe_layers):
    """``{layer: SparseMoE.expert_load()}`` of ``[(layer, SparseMoE)]``,
    each mirrored as ``mxtpu_moe_*`` gauges of ``telemetry.registry``."""
    from ...telemetry import registry

    pairs_g = registry.gauge(
        "mxtpu_moe_expert_pairs",
        "(token, expert) pairs routed to a held expert since the "
        "counters were zeroed", ("layer", "expert"))
    peak_g = registry.gauge(
        "mxtpu_moe_peak_pairs",
        "pairs of the busiest held expert, summed over training calls",
        ("layer",))
    calls_g = registry.gauge(
        "mxtpu_moe_calls", "training calls counted", ("layer",))
    route_g = registry.gauge(
        "mxtpu_moe_route_pairs",
        "(token, expert) pairs routed to each expert of the whole router "
        "(a layer that balances itself) since the counters were zeroed",
        ("layer", "expert"))
    out = {}
    for i, moe in moe_layers:
        load = out[i] = moe.expert_load()
        for e, n in enumerate(load["pairs"]):
            pairs_g.set(n, str(i), str(load["first_expert"] + e))
        for e, n in enumerate(load.get("route_pairs", ())):
            route_g.set(n, str(i), str(e))
        peak_g.set(load["peak"], str(i))
        calls_g.set(load["calls"], str(i))
    return out
