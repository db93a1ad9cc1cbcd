"""Contrib blocks (parity: `python/mxnet/gluon/contrib/nn/basic_layers.py`
— Concurrent :31, HybridConcurrent :64, Identity :97, SparseEmbedding
:118, SyncBatchNorm :165, PixelShuffle{1,2,3}D :249+)."""
from __future__ import annotations

from .... import ndarray as nd
from ....ndarray.sparse import row_sparse_array
from ...block import Block, HybridBlock
from ...nn.basic_layers import BatchNorm, HybridSequential, Sequential

__all__ = ["Concurrent", "HybridConcurrent", "Identity", "SparseEmbedding",
           "SyncBatchNorm", "PixelShuffle1D", "PixelShuffle2D",
           "PixelShuffle3D", "MultiHeadAttention", "TransformerEncoderCell"]


class Concurrent(Sequential):
    """Run children on the same input, concat outputs (parity: :31)."""

    def __init__(self, axis=-1, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self.axis = axis

    def forward(self, x):
        out = [block(x) for block in self._children.values()]
        return nd.concat(*out, dim=self.axis)


class HybridConcurrent(HybridSequential):
    """Hybridizable Concurrent (parity: :64)."""

    def __init__(self, axis=-1, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self.axis = axis

    def hybrid_forward(self, F, x):
        out = [block(x) for block in self._children.values()]
        return nd.concat(*out, dim=self.axis)

    # HybridSequential's eager forward chains children; Concurrent fans out
    def forward(self, x):
        out = [block(x) for block in self._children.values()]
        return nd.concat(*out, dim=self.axis)


class Identity(HybridBlock):
    """parity: :97."""

    def hybrid_forward(self, F, x):
        return x


class SparseEmbedding(Block):
    """Embedding designed for huge vocabularies (parity: :118).

    The gradient w.r.t. the weight only touches the looked-up rows. The
    tape accumulates into the (zero-off-rows) dense buffer; `grad_rows`
    extracts the row_sparse view for the sparse SGD / kvstore row-update
    paths, which then never materialize the full table's update."""

    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, **kwargs):
        super().__init__(**kwargs)
        self._kwargs = {"input_dim": input_dim, "output_dim": output_dim}
        self.weight = self.params.get(
            "weight", shape=(input_dim, output_dim), dtype=dtype,
            init=weight_initializer)

    def forward(self, x):
        return nd.Embedding(x, self.weight.data(), **self._kwargs)

    def grad_rows(self, x):
        """The row_sparse view of the current weight gradient restricted
        to the rows used by `x`."""
        import numpy as _np

        rows = _np.unique(_np.asarray(x.asnumpy()).astype(_np.int64))
        g = self.weight.grad()
        return row_sparse_array((g.asnumpy()[rows], rows),
                                shape=tuple(g.shape))


class SyncBatchNorm(BatchNorm):
    """Cross-device synchronized BatchNorm (parity: :165).

    On TPU the sharded trainer compiles BatchNorm under GSPMD, where the
    batch statistics of a dp-sharded batch are computed with global
    reductions automatically — XLA inserts the cross-replica psum the
    reference implements by hand in `sync_batch_norm-inl.h`. This class
    therefore only pins the op; semantics under `ShardedTrainer` are
    synchronized by construction."""

    def __init__(self, in_channels=0, num_devices=None, momentum=0.9,
                 epsilon=1e-5, **kwargs):
        super().__init__(momentum=momentum, epsilon=epsilon,
                         in_channels=in_channels, **kwargs)
        self._num_devices = num_devices


class _PixelShuffle(HybridBlock):
    _ndim = 2

    def __init__(self, factor):
        super().__init__()
        if isinstance(factor, int):
            factor = (factor,) * self._ndim
        self._factor = tuple(factor)

    def __repr__(self):
        return f"{type(self).__name__}({self._factor})"


class PixelShuffle1D(_PixelShuffle):
    """(N, C*f, W) -> (N, C, W*f) (parity: :249)."""

    _ndim = 1

    def hybrid_forward(self, F, x):
        (f,) = self._factor
        n, cf, w = x.shape
        x = nd.reshape(x, shape=(n, cf // f, f, w))
        x = nd.transpose(x, axes=(0, 1, 3, 2))
        return nd.reshape(x, shape=(n, cf // f, w * f))


class PixelShuffle2D(_PixelShuffle):
    """(N, C*f1*f2, H, W) -> (N, C, H*f1, W*f2) (parity: :297)."""

    _ndim = 2

    def hybrid_forward(self, F, x):
        f1, f2 = self._factor
        n, c, h, w = x.shape
        co = c // (f1 * f2)
        x = nd.reshape(x, shape=(n, co, f1, f2, h, w))
        x = nd.transpose(x, axes=(0, 1, 4, 2, 5, 3))
        return nd.reshape(x, shape=(n, co, h * f1, w * f2))


class PixelShuffle3D(_PixelShuffle):
    """(N, C*f1*f2*f3, D, H, W) -> (N, C, D*f1, H*f2, W*f3)
    (parity: :359)."""

    _ndim = 3

    def hybrid_forward(self, F, x):
        f1, f2, f3 = self._factor
        n, c, d, h, w = x.shape
        co = c // (f1 * f2 * f3)
        x = nd.reshape(x, shape=(n, co, f1, f2, f3, d, h, w))
        x = nd.transpose(x, axes=(0, 1, 5, 2, 6, 3, 7, 4))
        return nd.reshape(x, shape=(n, co, d * f1, h * f2, w * f3))


class MultiHeadAttention(HybridBlock):
    """Multi-head self/cross attention over the flash kernel.

    Beyond the reference's op-level pieces (`_contrib_interleaved_matmul_
    selfatt_*`, contrib/transformer.cc): a gluon block wired to the
    Pallas flash-attention kernel (`_contrib_flash_attention`) so the
    (S, S) score matrix never materializes in HBM — the building block
    for long-context transformer models. Inputs/outputs are
    (batch, seq, units).
    """

    def __init__(self, units, num_heads, dropout=0.0, causal=False,
                 interpret=False, **kwargs):
        super().__init__(**kwargs)
        if units % num_heads:
            raise ValueError(f"units {units} not divisible by "
                             f"num_heads {num_heads}")
        self._units = units
        self._heads = num_heads
        self._causal = causal
        # interpret=True runs the Pallas kernel in interpreter mode, so
        # the kernel path is testable on CPU CI
        self._interpret = interpret
        with self.name_scope():
            from ...nn import Dense, Dropout

            self.query = Dense(units, flatten=False, use_bias=True)
            self.key = Dense(units, flatten=False, use_bias=True)
            self.value = Dense(units, flatten=False, use_bias=True)
            self.proj = Dense(units, flatten=False, use_bias=True)
            self.drop = Dropout(dropout)

    def hybrid_forward(self, F, x, mem=None):
        """`mem=None` -> self attention; else cross attention with keys/
        values from `mem` (B, S_kv, U). Uses F + shape special values
        throughout, so the block traces to Symbol (export) unchanged."""
        if mem is not None and self._causal:
            raise ValueError(
                "causal masking has no valid interpretation for cross "
                "attention (query and memory positions are different "
                "sequences); build the block with causal=False")
        kv = x if mem is None else mem

        def split(t):  # (B, S, U) -> (B, H, S, D)
            t = F.reshape(t, shape=(0, 0, self._heads, -1))
            return F.transpose(t, axes=(0, 2, 1, 3))

        q = split(self.query(x))
        k = split(self.key(kv))
        v = split(self.value(kv))
        out = F.contrib.flash_attention(q, k, v, causal=self._causal,
                                        interpret=self._interpret)
        out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                        shape=(0, 0, -1))
        return self.drop(self.proj(out))


class TransformerEncoderCell(HybridBlock):
    """Pre-LN transformer encoder layer: LN -> MHA -> residual, LN ->
    FFN(GELU) -> residual. (B, S, U) in and out; stack under
    `parallel.pipeline_apply` for pipeline parallelism or feed q/k/v
    through `parallel.ring_attention` for sequence parallelism."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 causal=False, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            from ...nn import Dense, Dropout, LayerNorm

            self.ln1 = LayerNorm()
            self.attn = MultiHeadAttention(units, num_heads,
                                           dropout=dropout, causal=causal)
            self.ln2 = LayerNorm()
            self.ffn1 = Dense(hidden_size, flatten=False)
            self.ffn2 = Dense(units, flatten=False)
            self.drop = Dropout(dropout)

    def hybrid_forward(self, F, x):
        x = x + self.attn(self.ln1(x))
        h = F.LeakyReLU(self.ffn1(self.ln2(x)), act_type="gelu")
        return x + self.drop(self.ffn2(h))
