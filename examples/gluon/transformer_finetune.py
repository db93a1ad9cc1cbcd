#!/usr/bin/env python
"""BERT-class transformer fine-tune over flash attention + ShardedTrainer.

Stands in for the reference's GluonNLP BERT fine-tune config
(BASELINE.json; reference capability surface: the contrib transformer
ops, `src/operator/contrib/transformer.cc`, driven by gluon blocks):

1. "Pretrain" a small transformer encoder on a masked-token objective
   over synthetic sequences and checkpoint the backbone.
2. Load the backbone into a classifier (encoder + pooled Dense head) and
   FINE-TUNE on a sequence-classification task with `ShardedTrainer` —
   the whole step (fwd + loss + bwd + AdamW-style update) is ONE sharded
   XLA executable over a dp mesh, attention runs through the Pallas
   flash kernel path (`gluon.contrib.nn.MultiHeadAttention`), and the
   same script runs unchanged on a multi-host mesh (dist semantics come
   from the mesh, not the script).

    python examples/gluon/transformer_finetune.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np


def make_task(num_samples, seq_len, vocab, num_classes, seed=0):
    """Synthetic classification: the class is determined by which marker
    token appears in the sequence — attention must find it."""
    rs = np.random.RandomState(seed)
    x = rs.randint(num_classes, vocab, (num_samples, seq_len))
    y = rs.randint(0, num_classes, num_samples)
    pos = rs.randint(0, seq_len, num_samples)
    x[np.arange(num_samples), pos] = y  # marker token = class id
    return x.astype(np.float32), y.astype(np.float32)


def build_encoder(args, mx, nn, contrib_nn):
    enc = nn.HybridSequential(prefix="encoder_")
    with enc.name_scope():
        enc.add(contrib_nn.SparseEmbedding(args.vocab, args.units))
        for _ in range(args.layers):
            enc.add(contrib_nn.TransformerEncoderCell(
                args.units, args.hidden, args.heads))
    return enc


def main(argv=None):
    p = argparse.ArgumentParser(
        description="transformer fine-tune (BERT-class config)",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--units", type=int, default=32)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--seq-len", type=int, default=16)
    p.add_argument("--num-classes", type=int, default=4)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--pretrain-steps", type=int, default=30)
    p.add_argument("--finetune-epochs", type=int, default=6)
    p.add_argument("--num-examples", type=int, default=512)
    p.add_argument("--lr", type=float, default=2e-3)
    p.add_argument("--dp", type=int, default=0,
                   help="data-parallel mesh size (0 = all devices)")
    p.add_argument("--checkpoint", type=str, default=None,
                   help="backbone checkpoint path (default: tmp)")
    args = p.parse_args(argv)

    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.contrib import nn as contrib_nn
    from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer

    mx.random.seed(0)

    # ---------------------------------------------- 1. pretrain backbone
    class MLMModel(nn.HybridBlock):
        """Encoder + tied-size vocab head (masked-token objective)."""

        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.encoder = build_encoder(args, mx, nn, contrib_nn)
                self.head = nn.Dense(args.vocab, flatten=False)

        def hybrid_forward(self, F, tokens):
            return self.head(self.encoder(tokens))

    x_pre, _ = make_task(args.num_examples, args.seq_len, args.vocab,
                         args.num_classes, seed=1)
    mlm = MLMModel()
    mlm.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(mlm.collect_params(), "adam",
                            {"learning_rate": 1e-2})
    sce = gluon.loss.SoftmaxCrossEntropyLoss()
    rs = np.random.RandomState(3)
    for step in range(args.pretrain_steps):
        sel = rs.randint(0, args.num_examples, args.batch_size)
        tokens = x_pre[sel].copy()
        mask_pos = rs.randint(0, args.seq_len, args.batch_size)
        target = tokens[np.arange(args.batch_size), mask_pos].copy()
        tokens[np.arange(args.batch_size), mask_pos] = 0  # [MASK]=0
        tk, tg = mx.nd.array(tokens), mx.nd.array(target)
        with mx.autograd.record():
            logits = mlm(tk)[np.arange(args.batch_size), mask_pos]
            loss = sce(logits, tg)
        loss.backward()
        trainer.step(args.batch_size)
        if step % 10 == 0:
            print(f"pretrain step {step} "
                  f"mlm-loss={float(loss.mean().asscalar()):.4f}")
    ckpt = args.checkpoint or os.path.join(
        os.environ.get("TMPDIR", "/tmp"), "transformer_backbone.params")
    mlm.encoder.save_parameters(ckpt)
    print(f"backbone checkpoint -> {ckpt}")

    # --------------------------------------- 2. fine-tune the classifier
    class Classifier(nn.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.encoder = build_encoder(args, mx, nn, contrib_nn)
                self.pool = nn.Dense(args.units, activation="tanh",
                                     flatten=False)
                self.out = nn.Dense(args.num_classes)

        def hybrid_forward(self, F, tokens):
            h = self.encoder(tokens)
            # BERT-style pooling over the first position
            first = F.invoke("slice_axis", h, axis=1, begin=0, end=1)
            return self.out(self.pool(F.invoke("Flatten", first)))

    x, y = make_task(args.num_examples, args.seq_len, args.vocab,
                     args.num_classes, seed=5)
    clf = Classifier()
    clf.initialize(mx.init.Xavier())
    clf.encoder.load_parameters(ckpt)  # warm start from pretraining
    clf(mx.nd.array(x[:args.batch_size]))  # materialize shapes

    ndev = args.dp or len(jax.devices())
    mesh = DeviceMesh({"dp": ndev})
    st = ShardedTrainer(clf, gluon.loss.SoftmaxCrossEntropyLoss(),
                        "adam", {"learning_rate": args.lr, "wd": 1e-4},
                        mesh=mesh)
    nbatch = args.num_examples // args.batch_size
    acc = 0.0
    for epoch in range(args.finetune_epochs):
        perm = np.random.RandomState(epoch).permutation(args.num_examples)
        tot = 0.0
        for b in range(nbatch):
            sel = perm[b * args.batch_size:(b + 1) * args.batch_size]
            tot += float(st.step(mx.nd.array(x[sel]),
                                 mx.nd.array(y[sel])).asscalar())
        pred = st.predict(mx.nd.array(x)).asnumpy().argmax(-1)
        acc = float((pred == y).mean())
        print(f"Epoch[{epoch}] finetune-loss={tot / nbatch:.4f} "
              f"accuracy={acc:.4f}")
    return acc


if __name__ == "__main__":
    final = main()
    assert final > 0.9, f"fine-tune failed to learn ({final})"
