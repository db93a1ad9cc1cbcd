#!/usr/bin/env python
"""Transformer language model on the modern TPU stack.

Beyond the reference (MXNet 1.x predates transformer LMs): causal
`TransformerEncoderCell` stack (flash-attention backed) trained with
`parallel.ShardedTrainer` — the whole step (forward+loss+backward+adam)
is ONE compiled SPMD executable over a dp mesh, with optional ZeRO-1
state sharding, rematerialization and gradient accumulation.

Runs anywhere (virtual CPU mesh fallback); synthetic bigram corpus as in
word_lm.py, or --data a local text file.

    python examples/gluon/transformer_lm.py --steps 100
"""
import argparse
import os
import sys

_here = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(_here)))  # repo root


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--data", default=None)
    ap.add_argument("--vocab-size", type=int, default=128)
    ap.add_argument("--corpus-tokens", type=int, default=20000)
    ap.add_argument("--units", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--dp", type=int, default=0,
                    help="dp mesh size (0 = all devices)")
    ap.add_argument("--zero", action="store_true")
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--accum-steps", type=int, default=1)
    args = ap.parse_args()

    import mxnet_tpu as mx  # applies the MXTPU_PLATFORM pin
    import numpy as np

    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.contrib.nn import TransformerEncoderCell
    from mxnet_tpu.parallel import DeviceMesh, ShardedTrainer

    mx.random.seed(0)

    # ----- corpus (same learnable bigram structure as word_lm.py) ------
    if args.data and os.path.isfile(args.data):
        from mxnet_tpu.contrib import text

        src = open(args.data).read()
        vocab = text.Vocabulary(text.utils.count_tokens_from_str(src),
                                most_freq_count=args.vocab_size)
        ids = np.asarray(vocab.to_indices(src.split()), np.int32)
        args.vocab_size = len(vocab)
    else:
        rng = np.random.RandomState(42)
        ranks = np.arange(1, args.vocab_size)
        probs = (1.0 / ranks) / (1.0 / ranks).sum()
        succ = rng.permutation(args.vocab_size)
        ids = [int(rng.choice(ranks, p=probs))]
        for _ in range(args.corpus_tokens - 1):
            ids.append(int(succ[ids[-1]]) if rng.rand() < 0.8
                       else int(rng.choice(ranks, p=probs)))
        ids = np.asarray(ids, np.int32)

    # ----- model --------------------------------------------------------
    class TransformerLM(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.embed = nn.Embedding(args.vocab_size, args.units)
                self.pos = nn.Embedding(args.seq_len, args.units)
                self.body = nn.HybridSequential()
                for _ in range(args.layers):
                    self.body.add(TransformerEncoderCell(
                        args.units, args.hidden, args.heads, causal=True))
                self.head = nn.Dense(args.vocab_size, flatten=False)

        def hybrid_forward(self, F, tokens, positions):
            h = self.embed(tokens) + self.pos(positions)
            return self.head(self.body(h))

    net = TransformerLM()
    net.initialize(mx.init.Xavier())

    # ----- batches: (B, T) token windows + next-token labels -----------
    T, B = args.seq_len, args.batch_size
    n_win = (len(ids) - 1) // T
    windows = ids[: n_win * T].reshape(n_win, T)
    labels = ids[1: n_win * T + 1].reshape(n_win, T)
    # (T,) position ids -> (T, U) embedding, broadcast over any batch
    # size (gradient accumulation feeds microbatches)
    pos_nd = mx.nd.arange(T)

    class LMLoss(gluon.loss.Loss):
        """Softmax CE over the flattened (B*T, V) logits."""

        def __init__(self):
            super().__init__(weight=None, batch_axis=0)
            self._ce = gluon.loss.SoftmaxCrossEntropyLoss()

        def hybrid_forward(self, F, pred, label):
            return self._ce(pred.reshape((-1, args.vocab_size)),
                            label.reshape((-1,)))

    mesh = DeviceMesh({"dp": args.dp} if args.dp else None)
    net(mx.nd.array(windows[:B].astype(np.float32)), pos_nd)  # shapes

    class WithPos(gluon.HybridBlock):
        """Adapter: ShardedTrainer drives fn(x); positions are constant."""

        def __init__(self, inner, **kw):
            super().__init__(**kw)
            self.inner = inner

        def hybrid_forward(self, F, x):
            return self.inner(x, pos_nd)

    trainer = ShardedTrainer(WithPos(net), LMLoss(), "adam",
                             {"learning_rate": args.lr}, mesh=mesh,
                             zero=args.zero, remat=args.remat,
                             accum_steps=args.accum_steps)
    rng = np.random.RandomState(0)
    for step in range(args.steps):
        sel = rng.randint(0, n_win, B)
        x = mx.nd.array(windows[sel].astype(np.float32))
        y = mx.nd.array(labels[sel].astype(np.float32))
        loss = trainer.step(x, y)
        if step % 20 == 0 or step == args.steps - 1:
            ppl = float(np.exp(min(float(loss.asscalar()), 20.0)))
            print(f"step {step}: loss {float(loss.asscalar()):.3f} "
                  f"ppl {ppl:.1f}")
    print("done")


if __name__ == "__main__":
    main()
