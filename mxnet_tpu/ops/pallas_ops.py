"""Pallas-backed attention ops — the op-registration shim over the
kernel layer.

The flash attention kernel itself lives in
``mxnet_tpu/kernels/flash.py`` (PR 16 moved it into the kernel
registry); this module keeps the *op* surface — ``_contrib_flash_
attention`` and the serving-decode ``_contrib_decode_attention`` — and
routes through :func:`mxnet_tpu.kernels.dispatch`, which picks kernel
vs dense-XLA per (backend, shape bucket) from the autotuned dispatch
table and LATCHES the Pallas-unavailable fallback (one ``log.warning``
+ ``mxtpu_kernels_fallback_total{family}`` per process, never a silent
per-call re-probe — the old behavior here was exactly that bug).

parity role: contrib transformer attention + the long-context machinery
of SURVEY §5.7 (composes with parallel/ring_attention for the sharded
case: ring over devices, flash within a device).
"""
from __future__ import annotations

from .registry import register

# Re-exported for callers and tests that treat this module as the home
# of the attention numerics (tests/test_pallas.py imports both).
from ..kernels.flash import flash_attention_reference  # noqa: F401
from ..kernels.flash import flash_forward as _flash_forward  # noqa: F401, unused-import
from ..kernels.decode_attention import decode_attention_reference  # noqa: F401

__all__ = ["flash_attention_reference", "decode_attention_reference"]


@register("_contrib_flash_attention")
def _contrib_flash_attention(q, k, v, scale=None, causal=False,
                             block_q=None, block_k=None, interpret=False,
                             window=None):
    """Fused attention over (B, H, S, D) tensors.

    Dispatches to the Pallas flash kernel (registry family
    ``flash_attention``) when the shape passes the statically checkable
    Mosaic constraints AND the dispatch table (or the on-TPU default)
    picks it; dense XLA softmax otherwise. `interpret=True` forces the
    kernel through the Pallas interpreter (CPU CI). ``block_q`` /
    ``block_k`` None means the kernel's own, picked from the shape
    (``kernels/flash.py``); a pair forces a tile. Training memory stays
    O(S*block): the kernel's backward is a dispatch of its own (family
    ``flash_attention_bwd``: Pallas calls that recompute the
    probabilities tile by tile from the saved row log-sum-exp), not a
    dense recompute. ``k`` and ``v`` may have fewer heads than ``q``
    (grouped keys: ``H / Hk`` query heads read one key head); ``window``
    (causal only) is how many keys a query sees, its own included."""
    if q.ndim != 4:
        raise ValueError(
            f"flash_attention expects (B, H, S, D) inputs, got rank "
            f"{q.ndim}")
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    from .. import kernels as _kernels

    return _kernels.dispatch(
        "flash_attention", q, k, v, float(scale), causal=bool(causal),
        block_q=block_q, block_k=block_k,
        window=None if window is None else int(window),
        interpret=bool(interpret) or None)


@register("_contrib_decode_attention")
def _contrib_decode_attention(q, k, v, lengths, scale=None, block_k=128,
                              interpret=False):
    """Single-query decode attention: ``q (B, H, D)`` against a padded
    KV cache ``k/v (B, H, S, D)`` with per-sequence valid ``lengths
    (B,)`` (each >= 1). Registry family ``decode_attention`` — the
    Pallas kernel skips fully-padded cache blocks so decode cost tracks
    the filled cache; dense masked softmax otherwise."""
    if q.ndim != 3 or k.ndim != 4:
        raise ValueError(
            f"decode_attention expects q (B, H, D) and k/v (B, H, S, D),"
            f" got ranks {q.ndim}/{k.ndim}")
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    from .. import kernels as _kernels

    return _kernels.dispatch(
        "decode_attention", q, k, v, lengths, float(scale),
        block_k=int(block_k), interpret=bool(interpret) or None)
