"""The ``phi4flash`` family (Phi-4-mini-flash-reasoning: the SambaY
decoder-hybrid-decoder of arXiv:2507.06607 with the differential attention
of arXiv:2410.05258), built from the keys of a published ``config.json``.

Every block is ``h = x + Mixer(LN(x))``, ``y = h + MLP(LN(h))``: layer norm
with bias, a gated SiLU MLP, no positional encoding anywhere. With ``n =
num_hidden_layers`` the mixer of PUBLISHED layer ``l`` is

* ``l`` even, ``l <= n / 2``: a Mamba-1 state-space layer; layer ``n / 2``
  also hands its scan's output, before the gate, on as the memory;
* ``l`` odd, ``l < n / 2``: differential attention over a window of
  ``sliding_window`` keys;
* ``l = n / 2 + 1``: the same over all earlier keys; its projected keys
  and values are kept;
* ``l`` odd, ``l >= n / 2 + 3``: a query projection only, attending over
  layer ``n / 2 + 1``'s keys and values;
* ``l`` even, ``l >= n / 2 + 2``: a gated memory unit over the memory.

Final layer norm; the head is the embedding, transposed. Input (B, S)
token ids, output (B, S, vocab) logits; trains on next-token labels under
``gluon.loss.CausalLMLoss``. This is the first model here whose layers
hand state to later layers (the memory; layer ``n / 2 + 1``'s keys and
values) beside the residual stream.

``layers_kept`` names the published indices that are built (default: all):
each built layer takes its kind, its window and its ``lam_init = 0.8 - 0.6
exp(-0.3 l)`` from its PUBLISHED index, so a cut in depth is the same
layers a pipeline stage would hold.

Not built: ``mb_per_layer`` other than 2, a layer count that is no
multiple of 4 (the two halves would not end on their attention layers),
an untied head, dropout, a gated memory unit or a cross layer kept
without the layer that feeds it.
"""
from __future__ import annotations

import math

from ...block import HybridBlock
from ...nn import (DiffAttention, GatedMemoryUnit, GatedMLP, HybridSequential,
                   LayerNormF32, MambaMixer)

__all__ = ["Phi4FlashBlock", "Phi4FlashForCausalLM", "phi4flash",
           "layer_kind"]


def layer_kind(index, num_layers):
    """The kind of mixer PUBLISHED layer ``index`` of ``num_layers`` has."""
    half = num_layers // 2
    if index % 2 == 0:
        return "mamba" if index < half else \
            "mamba_memory" if index == half else "gmu"
    return "window" if index < half else \
        "full" if index == half + 1 else "cross"


class Phi4FlashBlock(HybridBlock):
    """One layer, of the kind its published ``index`` gives it. Called as
    its kind needs and returning what it makes: ``mamba``, ``window``:
    ``x -> y``; ``mamba_memory``: ``x -> (y, memory)``; ``full``: ``x ->
    (y, k, v)``; ``gmu``: ``(x, memory) -> y``; ``cross``: ``(x, k, v) ->
    y``."""

    def __init__(self, cfg, index, interpret=False, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        h, eps = cfg["hidden_size"], cfg["layer_norm_eps"]
        self.kind = kind = layer_kind(index, cfg["num_hidden_layers"])
        with self.name_scope():
            self.input_norm = LayerNormF32(h, epsilon=eps)
            if kind in ("mamba", "mamba_memory"):
                # the family's sizes (16 states, 4 taps, 2 x hidden
                # channels, a step of rank hidden / 16): the published
                # config has no key for them
                self.mixer = MambaMixer(h, interpret=interpret)
            elif kind == "gmu":
                self.mixer = GatedMemoryUnit(h, 2 * h)  # the memory's width
            else:
                self.mixer = DiffAttention(
                    h, cfg["num_attention_heads"],
                    cfg["num_key_value_heads"],
                    lam_init=0.8 - 0.6 * math.exp(-0.3 * index),
                    window=cfg["sliding_window"] if kind == "window"
                    else None,
                    cross=kind == "cross", epsilon=eps, interpret=interpret)
            self.post_norm = LayerNormF32(h, epsilon=eps)
            self.mlp = GatedMLP(h, cfg["intermediate_size"])

    def hybrid_forward(self, F, x, *state):
        made = self.mixer(self.input_norm(x), *state)
        mixed, *made = made if isinstance(made, tuple) else (made,)
        x = x + mixed
        y = x + self.mlp(self.post_norm(x))
        # only these two layers' state is read again
        return (y, *made) if self.kind in ("mamba_memory", "full") else y


class Phi4FlashForCausalLM(HybridBlock):
    """``cfg`` holds the published keys (``num_hidden_layers`` the
    PUBLISHED count); ``layers_kept`` the published indices built here, in
    order (default: all). ``interpret`` runs the attention and scan kernels
    in the Pallas interpreter (tests on the CPU)."""

    def __init__(self, cfg, layers_kept=None, interpret=False, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        n = cfg["num_hidden_layers"]
        for key, want in (("mb_per_layer", 2), ("hidden_act", "silu"),
                          ("tie_word_embeddings", True), ("mlp_bias", False),
                          ("lm_head_bias", False), ("embd_pdrop", 0),
                          ("resid_pdrop", 0)):
            if cfg.get(key, want) != want:
                raise NotImplementedError(
                    f"phi4flash with {key}={cfg[key]!r} is not built "
                    f"(only {want!r})")
        if n % 4:
            raise NotImplementedError(
                f"phi4flash with {n} layers is not built (only a multiple "
                f"of 4: each half ends on its attention layer)")
        kept = list(range(n)) if layers_kept is None else \
            [int(i) for i in layers_kept]
        if kept != sorted(set(kept)) or not kept or \
                not all(0 <= i < n for i in kept):
            raise ValueError(
                f"layers_kept {layers_kept} is no ascending choice of the "
                f"{n} published layers")
        kinds = [layer_kind(i, n) for i in kept]
        for kind, feeder in (("gmu", "mamba_memory"), ("cross", "full")):
            if kind in kinds and feeder not in kinds:
                raise ValueError(
                    f"layers_kept {kept} keeps a {kind} layer without the "
                    f"{feeder} layer that feeds it")
        self.layers_kept = tuple(kept)
        self._vocab, self._units = cfg["vocab_size"], cfg["hidden_size"]
        with self.name_scope():
            self.embed_weight = self.params.get(
                "embed_weight", shape=(self._vocab, self._units))
            self.layers = HybridSequential()
            for i in kept:
                self.layers.add(Phi4FlashBlock(cfg, i, interpret))
            self.norm = LayerNormF32(self._units,
                                     epsilon=cfg["layer_norm_eps"])

    def hybrid_forward(self, F, ids, embed_weight=None):
        x = F.invoke("Embedding", ids, embed_weight, input_dim=self._vocab,
                     output_dim=self._units)
        memory = k = v = None
        for blk in self.layers:
            if blk.kind == "mamba_memory":
                x, memory = blk(x)
            elif blk.kind == "full":
                x, k, v = blk(x)
            elif blk.kind == "gmu":
                x = blk(x, memory)
            elif blk.kind == "cross":
                x = blk(x, k, v)
            else:
                x = blk(x)
        return F.invoke("FullyConnected", self.norm(x), embed_weight,
                        num_hidden=self._vocab, no_bias=True, flatten=False)


def phi4flash(layers_kept=None, interpret=False, **config):
    """Build from the keys of a published ``phi4flash`` config.json."""
    return Phi4FlashForCausalLM(config, layers_kept=layers_kept,
                                interpret=interpret)
