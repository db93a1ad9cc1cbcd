#!/usr/bin/env python
"""Gluon word-level language model (imperative + hybridized).

Parity target: `example/gluon/word_language_model/train.py` — embedding ->
LSTM -> (optionally weight-tied) decoder, truncated-BPTT training with
gradient clipping, perplexity reporting. Data: real text via --data (one
sentence per line) indexed with `mx.contrib.text.Vocabulary`; otherwise
the same deterministic Zipf/bigram synthetic corpus the PTB example uses,
so it runs anywhere.

    python examples/gluon/word_lm.py --num-epochs 3 --ctx tpu
"""
import argparse
import os
import sys

_here = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(_here)))  # repo root

import numpy as np

import mxnet_tpu as mx


from mxnet_tpu import gluon
from mxnet_tpu.contrib import text
from mxnet_tpu.gluon import nn, rnn


class RNNModel(gluon.HybridBlock):
    """embedding -> LSTM -> dropout -> dense decoder; optional weight
    tying (decoder shares the embedding matrix)."""

    def __init__(self, vocab_size, embed_dim, hidden, layers, dropout=0.2,
                 tie_weights=False, **kwargs):
        super().__init__(**kwargs)
        self.hidden = hidden
        with self.name_scope():
            self.drop = nn.Dropout(dropout)
            self.encoder = nn.Embedding(vocab_size, embed_dim)
            self.rnn = rnn.LSTM(hidden, num_layers=layers, dropout=dropout,
                                input_size=embed_dim)
            if tie_weights:
                if embed_dim != hidden:
                    raise ValueError("weight tying needs embed_dim == hidden")
                self.decoder = nn.Dense(vocab_size, flatten=False,
                                        params=self.encoder.params)
            else:
                self.decoder = nn.Dense(vocab_size, flatten=False)

    def hybrid_forward(self, F, inputs, state):
        emb = self.drop(self.encoder(inputs))          # (T, B, E)
        out, state = self.rnn(emb, state)
        out = self.drop(out)
        return self.decoder(out), state

    def begin_state(self, batch_size, ctx):
        return self.rnn.begin_state(batch_size=batch_size, ctx=ctx)


def batchify(ids, batch_size):
    """Fold the token stream into (num_steps, batch_size) columns."""
    n = len(ids) // batch_size
    ids = np.asarray(ids[: n * batch_size], np.float32)
    return ids.reshape(batch_size, n).T


def corpus_tokens(args):
    if args.data and os.path.isfile(args.data):
        source = open(args.data).read()
        counter = text.utils.count_tokens_from_str(source)
        vocab = text.Vocabulary(counter, most_freq_count=args.vocab_size)
        ids = vocab.to_indices(source.split())
        return ids, len(vocab)
    # synthetic corpus with strong bigram structure: most tokens follow a
    # fixed successor map, the rest are Zipf draws — an LSTM learns this
    # quickly, so falling perplexity demonstrates the training loop
    rng = np.random.RandomState(42)
    ranks = np.arange(1, args.vocab_size)
    probs = (1.0 / ranks) / (1.0 / ranks).sum()
    succ = rng.permutation(args.vocab_size)
    ids = [int(rng.choice(ranks, p=probs))]
    for _ in range(args.corpus_tokens - 1):
        if rng.rand() < 0.8:
            ids.append(int(succ[ids[-1]]))
        else:
            ids.append(int(rng.choice(ranks, p=probs)))
    return ids, args.vocab_size


def detach(state):
    if isinstance(state, (list, tuple)):
        return [detach(s) for s in state]
    return state.detach()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--data", default=None, help="text file, one "
                    "sentence per line; synthetic corpus if absent")
    ap.add_argument("--vocab-size", type=int, default=200)
    ap.add_argument("--corpus-tokens", type=int, default=20000)
    ap.add_argument("--embed-dim", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--bptt", type=int, default=16)
    ap.add_argument("--lr", type=float, default=1.0)
    ap.add_argument("--clip", type=float, default=0.25)
    ap.add_argument("--num-epochs", type=int, default=3)
    ap.add_argument("--tied", action="store_true")
    ap.add_argument("--ctx", default="cpu", choices=["cpu", "tpu"])
    args = ap.parse_args()

    ctx = mx.tpu() if args.ctx == "tpu" else mx.cpu()
    mx.random.seed(1)
    ids, vocab_size = corpus_tokens(args)
    data = batchify(ids, args.batch_size)   # (T_total, B)

    model = RNNModel(vocab_size, args.embed_dim, args.hidden, args.layers,
                     tie_weights=args.tied)
    model.initialize(mx.init.Xavier(), ctx=ctx)
    model.hybridize()
    trainer = gluon.Trainer(model.collect_params(), "sgd",
                            {"learning_rate": args.lr})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    for epoch in range(args.num_epochs):
        state = model.begin_state(args.batch_size, ctx)
        total_nll, total_tok = 0.0, 0
        for i in range(0, data.shape[0] - 1 - args.bptt, args.bptt):
            x = mx.nd.array(data[i:i + args.bptt], ctx=ctx)
            y = mx.nd.array(data[i + 1:i + 1 + args.bptt], ctx=ctx)
            state = detach(state)  # truncated BPTT boundary
            with mx.autograd.record():
                out, state = model(x, state)
                loss = loss_fn(out.reshape((-1, vocab_size)),
                               y.reshape((-1,)))
            loss.backward()
            grads = [p.grad(ctx) for p in model.collect_params().values()
                     if p.grad_req != "null"]
            gluon.utils.clip_global_norm(
                grads, args.clip * args.bptt * args.batch_size)
            trainer.step(args.bptt * args.batch_size)
            total_nll += float(loss.sum().asscalar())
            total_tok += loss.size
        ppl = float(np.exp(total_nll / total_tok))
        print(f"epoch {epoch}: perplexity {ppl:.2f}")
    print("done")


if __name__ == "__main__":
    main()
