"""The ``lfm2_moe`` decoder family (LiquidAI LFM2-8B-A1B; the layer
equations are those of the Hugging Face ``lfm2_moe`` modelling code), built
from the keys of a published ``config.json``.

Pre-norm blocks ``h = x + Operator(RMSNorm(x))``, ``y = h +
FFN(RMSNorm(h))``, no bias anywhere. ``layer_types[l]`` names the operator
of PUBLISHED layer ``l``: ``conv``, the double-gated short convolution
(``nn.ShortConv``, ``conv_L_cache`` taps), or ``full_attention``, causal
grouped-query attention with an RMS norm of every head's q and k and
rotate-half rotary positions (``nn.GQAttention``). The first
``num_dense_layers`` layers have a dense gated SiLU feed-forward
(``intermediate_size``), the rest a sparse expert layer
(``nn.SparseMoE``): sigmoid scores over ``num_experts``,
``num_experts_per_tok`` chosen by score + ``expert_bias`` (a buffer
outside the gradient, ``use_expert_bias``), their weights the scores
alone over ``sum + 1e-6`` (``norm_topk_prob``) times
``routed_scaling_factor``, no shared expert. A final RMS norm
(``embedding_norm``); the head is the embedding, transposed. Input (B, S)
token ids, output (B, S, vocab) logits; trains on next-token labels under
``gluon.loss.CausalLMLoss``.

``layers_kept`` names the published indices that are built (default:
all): each takes its operator and its feed-forward from its PUBLISHED
index, so a cut in depth is the layers a pipeline stage would hold.
``experts_held=(first, count)`` builds one device's share of an
expert-parallel deployment (``nn.SparseMoE``). ``bias_update_rate`` above
0 makes ``expert_bias`` follow the load in training (the published config
carries the buffer, not the recipe that trains it: ``nn.SparseMoE`` says
which rule this is).

Not built: an untied head, a convolution bias, rope scaling, a router
without its bias or with another scoring function.
"""
from __future__ import annotations

from ...block import HybridBlock
from ...nn import (GatedMLP, GQAttention, HybridSequential, RMSNorm,
                   ShortConv, SparseMoE)
from ...nn.text_layers import _scope, mirror_expert_load

__all__ = ["Lfm2MoeBlock", "Lfm2MoeForCausalLM", "lfm2_moe"]


class Lfm2MoeBlock(HybridBlock):
    """One layer, with the operator and the feed-forward its published
    ``index`` gives it."""

    def __init__(self, cfg, index, experts_held=None, bias_update_rate=0.0,
                 interpret=False, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        h, eps = cfg["hidden_size"], cfg["norm_eps"]
        self.kind = cfg["layer_types"][index]
        with self.name_scope():
            self.operator_norm = RMSNorm(h, epsilon=eps)
            if self.kind == "conv":
                self.operator = ShortConv(h, taps=cfg["conv_L_cache"])
            elif self.kind == "full_attention":
                self.operator = GQAttention(
                    h, cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], rope_theta=cfg["rope_theta"],
                    epsilon=eps, interpret=interpret)
            else:
                raise NotImplementedError(
                    f"lfm2_moe with a layer of type {self.kind!r} is not "
                    f"built (only 'conv' and 'full_attention')")
            self.ffn_norm = RMSNorm(h, epsilon=eps)
            if index < cfg["num_dense_layers"]:
                self.ffn = GatedMLP(h, cfg["intermediate_size"])
            else:
                self.ffn = SparseMoE(
                    h, cfg["moe_intermediate_size"], cfg["num_experts"],
                    cfg["num_experts_per_tok"],
                    routed_scaling_factor=cfg["routed_scaling_factor"],
                    norm_topk=cfg["norm_topk_prob"],
                    experts_held=experts_held,
                    bias_update_rate=bias_update_rate, norm_eps=1e-6)

    def hybrid_forward(self, F, x):
        x = x + self.operator(self.operator_norm(x))
        return x + self.ffn(self.ffn_norm(x))


class Lfm2MoeForCausalLM(HybridBlock):
    """``cfg`` holds the published keys (``num_hidden_layers`` and
    ``layer_types`` the PUBLISHED ones); ``layers_kept``, ``experts_held``
    and ``bias_update_rate`` as the module says. ``interpret`` runs the
    attention kernel in the Pallas interpreter (tests on the CPU)."""

    def __init__(self, cfg, layers_kept=None, experts_held=None,
                 bias_update_rate=0.0, interpret=False, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        n = cfg["num_hidden_layers"]
        for key, want in (("tie_word_embeddings", True),
                          ("conv_bias", False), ("use_expert_bias", True),
                          ("rope_scaling", None)):
            if cfg.get(key, want) != want:
                raise NotImplementedError(
                    f"lfm2_moe with {key}={cfg[key]!r} is not built (only "
                    f"{want!r})")
        if len(cfg["layer_types"]) != n:
            raise ValueError(
                f"layer_types names {len(cfg['layer_types'])} layers, "
                f"num_hidden_layers is {n}")
        kept = list(range(n)) if layers_kept is None else \
            [int(i) for i in layers_kept]
        if kept != sorted(set(kept)) or not kept or \
                not all(0 <= i < n for i in kept):
            raise ValueError(
                f"layers_kept {layers_kept} is no ascending choice of the "
                f"{n} published layers")
        self.layers_kept = tuple(kept)
        self._vocab, self._units = cfg["vocab_size"], cfg["hidden_size"]
        with self.name_scope():
            self.embed_weight = self.params.get(
                "embed_weight", shape=(self._vocab, self._units))
            self.layers = HybridSequential()
            for i in kept:
                self.layers.add(Lfm2MoeBlock(cfg, i, experts_held,
                                             bias_update_rate, interpret))
            self.embedding_norm = RMSNorm(self._units,
                                          epsilon=cfg["norm_eps"])

    def hybrid_forward(self, F, ids, embed_weight=None):
        x = F.invoke("Embedding", ids, embed_weight, input_dim=self._vocab,
                     output_dim=self._units)
        x = self.embedding_norm(self.layers(x))
        with _scope("lm.head_loss"):
            return F.invoke("FullyConnected", x, embed_weight,
                            num_hidden=self._vocab, no_bias=True,
                            flatten=False)

    def moe_layers(self):
        """``[(published layer index, its SparseMoE)]``."""
        return [(i, blk.ffn) for i, blk in zip(self.layers_kept, self.layers)
                if isinstance(blk.ffn, SparseMoE)]

    def expert_load(self):
        """Every expert layer's load counters (``SparseMoE.expert_load``)
        by published layer index, mirrored as ``mxtpu_moe_*`` gauges."""
        return mirror_expert_load(self.moe_layers())

    def zero_expert_load(self):
        """Zero every expert layer's counters; the biases stay."""
        for _i, moe in self.moe_layers():
            moe.zero_load()


def lfm2_moe(layers_kept=None, experts_held=None, bias_update_rate=0.0,
             interpret=False, **config):
    """Build from the keys of a published ``lfm2_moe`` config.json."""
    return Lfm2MoeForCausalLM(
        config, layers_kept=layers_kept, experts_held=experts_held,
        bias_update_rate=bias_update_rate, interpret=interpret)
