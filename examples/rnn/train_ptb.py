#!/usr/bin/env python
"""PTB word-level language model with BucketingModule.

Parity target: `example/rnn/bucketing/lstm_bucketing.py` — an LSTM LM
trained with `BucketingModule` over variable-length sentence buckets,
reporting Perplexity. Uses the real PTB files when `--data-dir` has
ptb.train.txt; otherwise a deterministic synthetic corpus with Zipfian
unigram statistics, so the bucketing/perplexity machinery runs anywhere.

    python examples/rnn/train_ptb.py --num-epochs 3 --ctx tpu
"""
import argparse
import os
import sys

_here = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(_here)))  # repo root

import numpy as np

import mxnet_tpu as mx


def tokenize(path, vocab=None):
    sentences = []
    vocab = vocab if vocab is not None else {"<eos>": 0, "<unk>": 1}
    for line in open(path):
        words = line.split() + ["<eos>"]
        ids = []
        for w in words:
            if w not in vocab:
                vocab[w] = len(vocab)
            ids.append(vocab[w])
        sentences.append(ids)
    return sentences, vocab


def synthetic_corpus(num_sentences, vocab_size, seed):
    """Zipf-distributed token sequences with a simple bigram structure so
    the model has something to learn."""
    rng = np.random.RandomState(seed)
    ranks = np.arange(1, vocab_size)
    probs = 1.0 / ranks
    probs /= probs.sum()
    sentences = []
    for _ in range(num_sentences):
        length = int(rng.randint(5, 35))
        toks = [int(rng.choice(ranks, p=probs))]
        for _ in range(length - 1):
            # bigram: next token correlates with previous (learnable)
            prev = toks[-1]
            toks.append((prev * 7 + int(rng.choice(ranks, p=probs)))
                        % (vocab_size - 1) + 1)
        sentences.append(toks + [0])
    return sentences


class BucketSentenceIter(mx.io.DataIter):
    """Bucketed sentence iterator (parity: rnn/bucket_io.py
    BucketSentenceIter) — pads each sentence to its bucket length and
    yields batches tagged with bucket_key."""

    def __init__(self, sentences, batch_size, buckets, vocab_size):
        super().__init__(batch_size)
        self.buckets = sorted(buckets)
        self.data = {b: [] for b in self.buckets}
        for s in sentences:
            for b in self.buckets:
                if len(s) <= b:
                    self.data[b].append(s + [0] * (b - len(s)))
                    break
        self.vocab_size = vocab_size
        self.default_bucket_key = max(self.buckets)
        # sequences feed as (tokens[:-1] -> tokens[1:]): length key-1
        self.provide_data = [mx.io.DataDesc(
            "data", (batch_size, self.default_bucket_key - 1))]
        self.provide_label = [mx.io.DataDesc(
            "softmax_label", (batch_size, self.default_bucket_key - 1))]
        self.reset()

    def reset(self):
        self._plan = []
        for b in self.buckets:
            arr = np.asarray(self.data[b], np.float32)
            for s in range(0, len(arr) - self.batch_size + 1,
                           self.batch_size):
                self._plan.append((b, arr[s:s + self.batch_size]))
        self._cursor = 0

    def next(self):
        if self._cursor >= len(self._plan):
            raise StopIteration
        bucket, chunk = self._plan[self._cursor]
        self._cursor += 1
        data = mx.nd.array(chunk[:, :-1])
        label = mx.nd.array(chunk[:, 1:])
        batch = mx.io.DataBatch(
            data=[data], label=[label], pad=0, index=None)
        batch.bucket_key = bucket
        batch.provide_data = [mx.io.DataDesc("data", data.shape)]
        batch.provide_label = [mx.io.DataDesc("softmax_label", label.shape)]
        return batch


def sym_gen_factory(vocab_size, num_embed, num_hidden, batch_size):
    def sym_gen(bucket_key):
        seq_len = bucket_key - 1
        data = mx.sym.var("data")
        label = mx.sym.var("softmax_label")
        embed = mx.sym.Embedding(data, input_dim=vocab_size,
                                 output_dim=num_embed, name="embed")
        state = mx.sym.var("lstm_init_state", init=mx.init.Zero(),
                           shape=(1, batch_size, num_hidden))
        cell = mx.sym.var("lstm_init_cell", init=mx.init.Zero(),
                          shape=(1, batch_size, num_hidden))
        rnn_out = mx.sym.RNN(mx.sym.transpose(embed, axes=(1, 0, 2)),
                             state=state, state_cell=cell,
                             state_size=num_hidden, num_layers=1,
                             mode="lstm", name="lstm")
        flat = mx.sym.Reshape(rnn_out, shape=(-1, num_hidden))
        pred = mx.sym.FullyConnected(flat, num_hidden=vocab_size,
                                     name="pred")
        lab_flat = mx.sym.Reshape(label, shape=(-1,))
        sm = mx.sym.SoftmaxOutput(pred, lab_flat, name="softmax")
        return sm, ("data",), ("softmax_label",)

    return sym_gen


def main():
    parser = argparse.ArgumentParser(description="PTB LSTM LM")
    parser.add_argument("--data-dir", type=str, default="data/")
    parser.add_argument("--num-epochs", type=int, default=3)
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--num-embed", type=int, default=64)
    parser.add_argument("--num-hidden", type=int, default=128)
    parser.add_argument("--vocab-size", type=int, default=500)
    parser.add_argument("--num-sentences", type=int, default=2000)
    parser.add_argument("--lr", type=float, default=0.01)
    parser.add_argument("--ctx", type=str, default="tpu")
    args = parser.parse_args()

    import logging

    logging.basicConfig(level=logging.INFO)

    ptb = os.path.join(args.data_dir, "ptb.train.txt")
    if os.path.exists(ptb):
        sentences, vocab = tokenize(ptb)
        vocab_size = len(vocab)
    else:
        sentences = synthetic_corpus(args.num_sentences, args.vocab_size,
                                     seed=0)
        vocab_size = args.vocab_size

    buckets = [10, 20, 30, 40]
    it = BucketSentenceIter(sentences, args.batch_size, buckets, vocab_size)
    ctx = mx.tpu() if args.ctx == "tpu" and mx.num_tpus() > 0 else mx.cpu()
    logging.info("--ctx %s runs on %s", args.ctx, ctx.jax_device())
    model = mx.mod.BucketingModule(
        sym_gen_factory(vocab_size, args.num_embed, args.num_hidden,
                        args.batch_size),
        default_bucket_key=it.default_bucket_key, context=ctx)
    model.fit(it,
              eval_metric=mx.metric.Perplexity(),
              optimizer="adam",
              optimizer_params={"learning_rate": args.lr},
              initializer=mx.init.Xavier(),
              num_epoch=args.num_epochs,
              batch_end_callback=mx.callback.Speedometer(args.batch_size,
                                                         20))


if __name__ == "__main__":
    main()
