"""The ``deepseek_v3`` decoder family (DeepSeek-V3, arXiv:2412.19437; the
layer equations are those of the Hugging Face ``deepseek_v3`` modelling
code), built from the keys of a published ``config.json``.

Pre-norm blocks ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``
with multi-head latent attention; the first ``first_k_dense_replace``
layers have a dense gated MLP, the rest a sparse expert layer with shared
experts; final RMS norm and an untied head. Input (B, S) token ids, output
(B, S, vocab) logits; trains on next-token labels (B, S) under
``gluon.loss.CausalLMLoss``.

Not built: the query's low-rank step (``q_lora_rank`` must be null),
grouped routing (``n_group`` and ``topk_group`` must be 1), rope scaling.
"""
from __future__ import annotations

from ...block import HybridBlock
from ...nn import (Dense, Embedding, GatedMLP, HybridSequential, MLAttention,
                   RMSNorm, SparseMoE)
from ...nn.text_layers import mirror_expert_load

__all__ = ["DeepseekV3Block", "DeepseekV3ForCausalLM", "deepseek_v3"]


class DeepseekV3Block(HybridBlock):
    def __init__(self, cfg, sparse, experts_held=None, interpret=False,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        h, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        with self.name_scope():
            self.input_norm = RMSNorm(h, epsilon=eps)
            self.attn = MLAttention(
                h, cfg["num_attention_heads"], cfg["kv_lora_rank"],
                cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                cfg["v_head_dim"], rope_theta=cfg["rope_theta"],
                rope_interleave=cfg.get("rope_interleave", False),
                epsilon=eps, interpret=interpret)
            self.post_attn_norm = RMSNorm(h, epsilon=eps)
            if sparse:
                self.ffn = SparseMoE(
                    h, cfg["moe_intermediate_size"],
                    cfg["n_routed_experts"], cfg["num_experts_per_tok"],
                    num_shared=cfg["n_shared_experts"],
                    routed_scaling_factor=cfg["routed_scaling_factor"],
                    norm_topk=cfg["norm_topk_prob"],
                    experts_held=experts_held)
            else:
                self.ffn = GatedMLP(h, cfg["intermediate_size"])

    def hybrid_forward(self, F, x):
        x = x + self.attn(self.input_norm(x))
        return x + self.ffn(self.post_attn_norm(x))


class DeepseekV3ForCausalLM(HybridBlock):
    """``cfg`` holds the published keys; ``experts_held=(first, count)``
    builds one device's share of an expert-parallel deployment (see
    ``nn.SparseMoE``), the default the whole model. ``interpret`` runs the
    attention kernel in the Pallas interpreter (tests on the CPU)."""

    def __init__(self, cfg, experts_held=None, interpret=False, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        for key, want in (("q_lora_rank", None), ("rope_scaling", None),
                          ("n_group", 1), ("topk_group", 1),
                          ("scoring_func", "sigmoid"),
                          ("hidden_act", "silu")):
            if cfg.get(key, want) != want:
                raise NotImplementedError(
                    f"deepseek_v3 with {key}={cfg[key]!r} is not built "
                    f"(only {want!r})")
        h = cfg["hidden_size"]
        with self.name_scope():
            self.embed = Embedding(cfg["vocab_size"], h)
            self.layers = HybridSequential()
            for i in range(cfg["num_hidden_layers"]):
                sparse = i >= cfg["first_k_dense_replace"] and \
                    i % cfg.get("moe_layer_freq", 1) == 0
                self.layers.add(DeepseekV3Block(cfg, sparse, experts_held,
                                                interpret))
            self.norm = RMSNorm(h, epsilon=cfg["rms_norm_eps"])
            self.head = Dense(cfg["vocab_size"], use_bias=False,
                              flatten=False, in_units=h)

    def hybrid_forward(self, F, ids):
        return self.head(self.norm(self.layers(self.embed(ids))))

    def moe_layers(self):
        """``[(layer index, its SparseMoE)]``."""
        return [(i, blk.ffn) for i, blk in enumerate(self.layers)
                if isinstance(blk.ffn, SparseMoE)]

    def expert_load(self):
        """Every expert layer's load counters (``SparseMoE.expert_load``)
        by layer index, mirrored as ``mxtpu_moe_*`` gauges."""
        return mirror_expert_load(self.moe_layers())


def deepseek_v3(experts_held=None, interpret=False, **config):
    """Build from the keys of a published ``deepseek_v3`` config.json."""
    return DeepseekV3ForCausalLM(config, experts_held=experts_held,
                                 interpret=interpret)
