#!/usr/bin/env python
"""Bucketed LSTM language model (reference: example/rnn/lstm_bucketing.py).

Trains on a synthetic integer-sequence corpus when no PTB file is given.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import argparse
import logging

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.models.lstm_lm import sym_gen_factory


def synthetic_corpus(n_sent=2000, vocab=500, seed=0):
    rng = np.random.RandomState(seed)
    sents = []
    for _ in range(n_sent):
        length = rng.randint(5, 40)
        # Markov-ish chains so there is something to learn
        start = rng.randint(1, vocab)
        s = [start]
        for _ in range(length - 1):
            s.append((s[-1] * 31 + 7) % vocab or 1)
        sents.append(s)
    return sents


def tokenize(fname, vocab=None):
    sentences = []
    vocab = vocab if vocab is not None else {"<pad>": 0}
    with open(fname) as f:
        for line in f:
            words = line.split() + ["<eos>"]
            ids = []
            for w in words:
                if w not in vocab:
                    vocab[w] = len(vocab)
                ids.append(vocab[w])
            sentences.append(ids)
    return sentences, vocab


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--train-file", default=None)
    parser.add_argument("--num-hidden", type=int, default=200)
    parser.add_argument("--num-embed", type=int, default=200)
    parser.add_argument("--num-layers", type=int, default=2)
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--buckets", default="10,20,30,40")
    parser.add_argument("--num-epochs", type=int, default=3)
    parser.add_argument("--lr", type=float, default=0.01)
    parser.add_argument("--fused", action="store_true", default=True)
    args = parser.parse_args()

    logging.basicConfig(level=logging.INFO)

    if args.train_file:
        sentences, vocab = tokenize(args.train_file)
        vocab_size = len(vocab) + 1
    else:
        sentences = synthetic_corpus()
        vocab_size = 512

    buckets = [int(b) for b in args.buckets.split(",")]
    data_train = mx.rnn.BucketSentenceIter(sentences, args.batch_size,
                                           buckets=buckets, invalid_label=0)

    sym_gen, cells = sym_gen_factory(num_hidden=args.num_hidden,
                                     num_layers=args.num_layers,
                                     num_embed=args.num_embed,
                                     vocab_size=vocab_size, fused=args.fused)
    # the example picks its platform itself: mx.tpu() raises without a chip
    import jax

    ctx = mx.cpu() if jax.devices()[0].platform == "cpu" else mx.tpu()
    mod = mx.mod.BucketingModule(sym_gen,
                                 default_bucket_key=data_train.default_bucket_key,
                                 context=ctx)
    mod.fit(data_train, eval_metric=mx.metric.Perplexity(ignore_label=0),
            initializer=mx.initializer.Xavier(),
            optimizer="adam", optimizer_params={"learning_rate": args.lr},
            batch_end_callback=mx.callback.Speedometer(args.batch_size, 20),
            num_epoch=args.num_epochs)


if __name__ == "__main__":
    main()
