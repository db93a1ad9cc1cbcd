"""gluon.contrib tests (parity model:
tests/python/unittest/test_gluon_contrib.py + test_gluon_estimator.py)."""
import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu.gluon import Trainer, nn
from mxnet_tpu.gluon import loss as gloss
from mxnet_tpu.gluon.contrib import nn as cnn
from mxnet_tpu.gluon.contrib.estimator import (EarlyStoppingHandler,
                                               Estimator, StoppingHandler)


def test_identity_and_concurrent():
    x = mx.nd.array(onp.random.rand(2, 8, 4, 4).astype("float32"))
    assert (cnn.Identity()(x).asnumpy() == x.asnumpy()).all()
    for cls in (cnn.Concurrent, cnn.HybridConcurrent):
        c = cls(axis=1)
        c.add(cnn.Identity(), cnn.Identity())
        out = c(x)
        assert out.shape == (2, 16, 4, 4)
        onp.testing.assert_allclose(out.asnumpy()[:, :8], x.asnumpy())


def test_pixelshuffle_oracle():
    x = mx.nd.array(onp.arange(2 * 8 * 4 * 4,
                               dtype="float32").reshape(2, 8, 4, 4))
    out = cnn.PixelShuffle2D(2)(x)
    xn = x.asnumpy()
    n, c, h, w = xn.shape
    ref = xn.reshape(n, 2, 2, 2, h, w).transpose(0, 1, 4, 2, 5, 3) \
        .reshape(n, 2, h * 2, w * 2)
    onp.testing.assert_allclose(out.asnumpy(), ref)
    x1 = mx.nd.array(onp.arange(12, dtype="float32").reshape(1, 4, 3))
    assert cnn.PixelShuffle1D(2)(x1).shape == (1, 2, 6)
    x3 = mx.nd.ones((1, 8, 2, 2, 2))
    assert cnn.PixelShuffle3D(2)(x3).shape == (1, 1, 4, 4, 4)


def test_sparse_embedding_grad_rows():
    se = cnn.SparseEmbedding(50, 8)
    se.initialize(mx.init.Xavier())
    idx = mx.nd.array([1, 3, 3], dtype="int32")
    with mx.autograd.record():
        out = se(idx)
        loss = out.sum()
    loss.backward()
    rs = se.grad_rows(idx)
    assert rs.stype == "row_sparse"
    assert rs.indices.asnumpy().tolist() == [1, 3]
    onp.testing.assert_allclose(rs.data.asnumpy()[0], onp.ones(8))
    onp.testing.assert_allclose(rs.data.asnumpy()[1], 2 * onp.ones(8))


def test_sync_batchnorm_forward():
    bn = cnn.SyncBatchNorm(in_channels=4)
    bn.initialize()
    x = mx.nd.array(onp.random.rand(2, 4, 3, 3).astype("float32"))
    out = bn(x)
    assert out.shape == x.shape


def _toy_data(n=256):
    rs = onp.random.RandomState(0)
    X = rs.randn(n, 10).astype("float32")
    y = (X[:, 0] > 0).astype("float32")
    return mx.io.NDArrayIter(X, y, batch_size=32)


def _toy_net():
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(2))
    return net


def test_estimator_fit_and_evaluate():
    mx.random.seed(0)
    net = _toy_net()
    net.initialize(mx.init.Xavier())
    est = Estimator(net, gloss.SoftmaxCrossEntropyLoss(), context=mx.cpu(),
                    trainer=Trainer(net.collect_params(), "adam",
                                    {"learning_rate": 0.01}))
    it = _toy_data()
    est.fit(it, epochs=8)
    res = est.evaluate(_toy_data())
    assert res["accuracy"] > 0.9, res
    assert "val_loss" in res


def test_estimator_early_stopping():
    mx.random.seed(0)
    net = _toy_net()
    net.initialize(mx.init.Xavier())
    est = Estimator(net, gloss.SoftmaxCrossEntropyLoss(), context=mx.cpu())
    handler = EarlyStoppingHandler(monitor=est.train_loss_metric,
                                   patience=1, mode="min")
    est.fit(_toy_data(64), epochs=50, event_handlers=[
        handler, StoppingHandler(max_epoch=50)])
    # either converged loss triggered early stop, or max epochs hit
    assert handler.current_epoch <= 50


def test_estimator_max_batches():
    net = _toy_net()
    net.initialize(mx.init.Xavier())
    est = Estimator(net, gloss.SoftmaxCrossEntropyLoss(), context=mx.cpu())
    stopper = StoppingHandler(max_batch=3)
    est.fit(_toy_data(), batches=3, event_handlers=[stopper])
    assert stopper.current_batch == 3


def test_conv_rnn_cells():
    """Conv1/2/3D RNN/LSTM/GRU cells preserve state spatial shape across
    unroll (parity: gluon/contrib/rnn/conv_rnn_cell.py)."""
    from mxnet_tpu.gluon.contrib import rnn as crnn

    cell = crnn.Conv2DLSTMCell(input_shape=(3, 8, 8), hidden_channels=5,
                               i2h_kernel=3, h2h_kernel=3, i2h_pad=1)
    cell.initialize(mx.init.Xavier())
    seq = [mx.nd.random.uniform(shape=(2, 3, 8, 8)) for _ in range(4)]
    outs, states = cell.unroll(4, seq)
    assert outs[0].shape == (2, 5, 8, 8)
    assert states[0].shape == (2, 5, 8, 8)
    assert states[1].shape == (2, 5, 8, 8)

    g = crnn.Conv1DGRUCell(input_shape=(2, 10), hidden_channels=4,
                           i2h_kernel=3, h2h_kernel=3, i2h_pad=1)
    g.initialize(mx.init.Xavier())
    outs, _ = g.unroll(
        3, [mx.nd.random.uniform(shape=(2, 2, 10)) for _ in range(3)])
    assert outs[0].shape == (2, 4, 10)

    r3 = crnn.Conv3DRNNCell(input_shape=(1, 4, 4, 4), hidden_channels=2,
                            i2h_kernel=3, h2h_kernel=3, i2h_pad=1)
    r3.initialize(mx.init.Xavier())
    outs, _ = r3.unroll(
        2, [mx.nd.random.uniform(shape=(1, 1, 4, 4, 4)) for _ in range(2)])
    assert outs[0].shape == (1, 2, 4, 4, 4)


def test_lstmp_cell():
    from mxnet_tpu.gluon.contrib import rnn as crnn

    p = crnn.LSTMPCell(hidden_size=16, projection_size=6)
    p.initialize(mx.init.Xavier())
    outs, st = p.unroll(
        3, [mx.nd.random.uniform(shape=(4, 10)) for _ in range(3)])
    assert outs[0].shape == (4, 6)
    assert st[0].shape == (4, 6) and st[1].shape == (4, 16)


def test_variational_dropout_cell():
    """Mask sampled once, reused across steps; no dropout at inference
    (parity: gluon/contrib/rnn/rnn_cell.py VariationalDropoutCell)."""
    import numpy as onp

    from mxnet_tpu.gluon.contrib import rnn as crnn

    base = mx.gluon.rnn.RNNCell(8)
    vd = crnn.VariationalDropoutCell(base, drop_inputs=0.5)
    vd.initialize(mx.init.Xavier())
    x = mx.nd.ones((2, 8))
    with mx.autograd.record():
        vd.reset()
        _, s = vd(x, vd.begin_state(2))
        m1 = vd._input_mask.asnumpy()
        vd(x, s)
        m2 = vd._input_mask.asnumpy()
    onp.testing.assert_array_equal(m1, m2)
    vd.reset()
    vd(x, vd.begin_state(2))
    assert vd._input_mask is None


def test_interval_sampler():
    """Reference doctest behavior (gluon/contrib/data/sampler.py:25)."""
    import pytest

    from mxnet_tpu.gluon.contrib.data import IntervalSampler

    assert list(IntervalSampler(13, interval=3)) == \
        [0, 3, 6, 9, 12, 1, 4, 7, 10, 2, 5, 8, 11]
    assert list(IntervalSampler(13, interval=3, rollover=False)) == \
        [0, 3, 6, 9, 12]
    assert len(IntervalSampler(13, interval=3)) == 13
    with pytest.raises(ValueError):
        IntervalSampler(3, interval=5)
    with pytest.raises(ValueError):
        IntervalSampler(3, interval=0)


def test_wikitext_local_file(tmp_path):
    """WikiText2 over a local token file: vocab (EOS-reserved), 1-shifted
    labels, seq_len folding (gluon/contrib/data/text.py)."""
    import os

    import pytest

    from mxnet_tpu.gluon.contrib.data import WikiText2

    root = str(tmp_path)
    txt = " the cat sat \n\n the cat ran \n"
    with open(os.path.join(root, "wiki.train.tokens"), "w") as f:
        f.write(txt)
    ds = WikiText2(root=root, segment="train", seq_len=4)
    # stream: the cat sat <eos> the cat ran <eos> -> 7 usable pairs -> 1 row
    assert len(ds) == 1
    data, label = ds[0]
    v = ds.vocabulary
    assert v.to_tokens(int(data[0].asscalar())) == "the"
    onp.testing.assert_array_equal(label.asnumpy()[:3],
                                   data.asnumpy()[1:])
    assert "<eos>" in v.reserved_tokens
    with pytest.raises(FileNotFoundError, match="token file not found"):
        WikiText2(root=root, segment="test")
    with pytest.raises(ValueError):
        WikiText2(root=root, segment="bogus")


def test_multi_head_attention_matches_oracle():
    """MultiHeadAttention (flash-kernel backed) equals a hand-built
    dense attention oracle with the same projection weights; causal
    masking and cross-attention both work; gradients flow."""
    import math

    from mxnet_tpu.gluon.contrib.nn import MultiHeadAttention

    B, S, U, H = 2, 16, 24, 4
    mx.random.seed(0)
    attn = MultiHeadAttention(U, H, causal=False)
    attn.initialize(mx.init.Xavier())
    x = mx.nd.random.uniform(-1, 1, (B, S, U))
    out = attn(x)
    assert out.shape == (B, S, U)

    # oracle using the block's own projection weights
    def dense_oracle(x):
        q = mx.nd.dot(x, attn.query.weight.data().T) + attn.query.bias.data()
        k = mx.nd.dot(x, attn.key.weight.data().T) + attn.key.bias.data()
        v = mx.nd.dot(x, attn.value.weight.data().T) + attn.value.bias.data()

        def split(t):
            return t.reshape((B, S, H, U // H)).transpose((0, 2, 1, 3))

        q, k, v = split(q), split(k), split(v)
        s = mx.nd.linalg_gemm2(q, k, transpose_b=True) / math.sqrt(U // H)
        p = mx.nd.softmax(s, axis=-1)
        o = mx.nd.linalg_gemm2(p, v)
        o = o.transpose((0, 2, 1, 3)).reshape((B, S, U))
        return mx.nd.dot(o, attn.proj.weight.data().T) + \
            attn.proj.bias.data()

    onp.testing.assert_allclose(out.asnumpy(), dense_oracle(x).asnumpy(),
                                rtol=2e-3, atol=2e-5)

    # causal + grads
    cattn = MultiHeadAttention(U, H, causal=True)
    cattn.initialize(mx.init.Xavier())
    with mx.autograd.record():
        loss = (cattn(x) ** 2).sum()
    loss.backward()
    g = cattn.query.weight.grad()
    assert float(g.abs().sum().asscalar()) > 0
    # cross attention: different kv length
    mem = mx.nd.random.uniform(-1, 1, (B, 8, U))
    assert attn(x, mem).shape == (B, S, U)
    # causal masking is rejected for cross attention
    import pytest

    with pytest.raises(ValueError, match="cross"):
        cattn(x, mem)


def test_transformer_encoder_cell_trains():
    """Pre-LN encoder stack trains on a toy seq task and hybridizes."""
    from mxnet_tpu.gluon.contrib.nn import TransformerEncoderCell

    mx.random.seed(1)
    B, S, U = 4, 8, 16
    net = nn.HybridSequential()
    net.add(TransformerEncoderCell(U, 32, 4, causal=True),
            TransformerEncoderCell(U, 32, 4, causal=True))
    net.initialize(mx.init.Xavier())
    x = mx.nd.random.uniform(-1, 1, (B, S, U))
    y = x * 0.5  # learn a simple map
    tr = Trainer(net.collect_params(), "adam", {"learning_rate": 1e-2})
    loss_fn = gloss.L2Loss()
    losses = []
    for _ in range(20):
        with mx.autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        tr.step(B)
        losses.append(float(loss.mean().asscalar()))
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])
    net.hybridize()
    assert net(x).shape == (B, S, U)


def test_multi_head_attention_names_no_block():
    """The layer hands the op no block and the kernel family picks them
    from the shape; a pair named at the op forces a tile and lands in the
    bucket's key."""
    from mxnet_tpu import kernels
    from mxnet_tpu.gluon.contrib.nn import MultiHeadAttention

    attn = MultiHeadAttention(32, 4, interpret=True)
    attn.initialize(mx.init.Xavier())
    kernels.reset_stats()
    assert attn(mx.nd.random.uniform(-1, 1, (2, 256, 32))).shape \
        == (2, 256, 32)
    assert list(kernels.dispatch_stats()["flash_attention"]["buckets"]) \
        == ["bh8_sq256_sk256_d8_float32_c0_q256k256"]
    kernels.reset_stats()
    q = mx.nd.random.uniform(-1, 1, (1, 2, 128, 8))
    mx.nd.contrib.flash_attention(q, q, q, block_q=64, block_k=32,
                                  interpret=True)
    assert list(kernels.dispatch_stats()["flash_attention"]["buckets"]) \
        == ["bh2_sq128_sk128_d8_float32_c0_q64k32"]


def test_multi_head_attention_kernel_path_and_export(tmp_path):
    """Kernel-friendly shapes through the Pallas interpreter (d%8==0,
    S%block==0) match the dense fallback; the block exports to Symbol
    (F-dispatch tracing) and round-trips."""
    from mxnet_tpu.gluon.contrib.nn import (MultiHeadAttention,
                                            TransformerEncoderCell)

    B, S, U, H = 1, 128, 32, 4  # head dim 8, S == block size
    mx.random.seed(2)
    flash = MultiHeadAttention(U, H, causal=True, interpret=True)
    flash.initialize(mx.init.Xavier())
    x = mx.nd.random.uniform(-1, 1, (B, S, U))
    out_kernel = flash(x)
    dense = MultiHeadAttention(U, H, causal=True)
    dense.initialize()
    # same weights -> the two compute paths must agree
    for dst, src in zip(dense.collect_params().values(),
                        flash.collect_params().values()):
        dst.set_data(src.data())
    onp.testing.assert_allclose(out_kernel.asnumpy(),
                                dense(x).asnumpy(), rtol=2e-3, atol=2e-4)

    # export path: the encoder cell traces to Symbol and round-trips
    net = nn.HybridSequential()
    net.add(TransformerEncoderCell(U, 64, H, causal=True))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    ref = net(x)
    prefix = str(tmp_path / "enc")
    net.export(prefix, epoch=0)
    from mxnet_tpu import gluon

    back = gluon.SymbolBlock.imports(prefix + "-symbol.json", ["data"],
                                     prefix + "-0000.params")
    onp.testing.assert_allclose(back(x).asnumpy(), ref.asnumpy(),
                                rtol=1e-5, atol=1e-6)
