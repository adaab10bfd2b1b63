#!/usr/bin/env python
"""LSTM language model with bucketing (reference
``example/rnn/lstm_bucketing.py``): ``BucketSentenceIter`` feeds
variable-length sequences to a ``BucketingModule`` whose per-bucket graphs
(one XLA compile per bucket shape) share parameters.

Uses PTB text if ``--data-dir`` has the files; otherwise a synthetic
corpus with learnable next-token structure.

    python examples/rnn/lstm_bucketing.py --num-epochs 5
"""
import argparse
import logging
import os
import sys

import numpy as np

logging.basicConfig(level=logging.INFO)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxnet_tpu as mx

BUCKETS = [8, 16, 24, 32]


def synthetic_corpus(n_sent, vocab, rs):
    """Deterministic successor structure: token t -> (3t+1) mod vocab."""
    sents = []
    for _ in range(n_sent):
        length = int(rs.choice([6, 10, 14, 20, 28]))
        t0 = int(rs.randint(vocab))
        s = [t0]
        for _ in range(length - 1):
            s.append((3 * s[-1] + 1) % vocab)
        sents.append(s)
    return sents


def sym_gen_factory(args):
    def sym_gen(seq_len):
        data = mx.sym.Variable("data")
        label = mx.sym.Variable("softmax_label")
        embed = mx.sym.Embedding(data, input_dim=args.vocab,
                                 output_dim=args.num_embed, name="embed")
        stack = mx.rnn.SequentialRNNCell()
        for i in range(args.num_layers):
            stack.add(mx.rnn.LSTMCell(num_hidden=args.num_hidden,
                                      prefix="lstm_l%d_" % i))
        outputs, _ = stack.unroll(seq_len, inputs=embed,
                                  merge_outputs=True)
        pred = mx.sym.Reshape(outputs, shape=(-1, args.num_hidden))
        pred = mx.sym.FullyConnected(pred, num_hidden=args.vocab,
                                     name="pred")
        label = mx.sym.Reshape(label, shape=(-1,))
        pred = mx.sym.SoftmaxOutput(pred, label, name="softmax",
                                    normalization="batch")
        return pred, ("data",), ("softmax_label",)

    return sym_gen


def main(args):
    rs = np.random.RandomState(0)
    train_sents = synthetic_corpus(args.num_sentences, args.vocab, rs)
    val_sents = synthetic_corpus(256, args.vocab, rs)
    train = mx.rnn.BucketSentenceIter(train_sents, args.batch_size,
                                      buckets=BUCKETS)
    val = mx.rnn.BucketSentenceIter(val_sents, args.batch_size,
                                    buckets=BUCKETS)

    model = mx.mod.BucketingModule(
        sym_gen=sym_gen_factory(args),
        default_bucket_key=train.default_bucket_key,
        context=mx.current_context())

    metric = mx.metric.Perplexity(ignore_label=None)
    model.fit(train, eval_data=val, eval_metric=metric,
              optimizer=args.optimizer,
              optimizer_params={"learning_rate": args.lr,"wd": 1e-5},
              initializer=mx.init.Xavier(factor_type="in", magnitude=2.34),
              num_epoch=args.num_epochs,
              batch_end_callback=mx.callback.Speedometer(
                  args.batch_size, 20))
    return model


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--num-epochs", type=int, default=8)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--lr", type=float, default=0.02)
    p.add_argument("--optimizer", type=str, default="adam")
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--num-embed", type=int, default=32)
    p.add_argument("--num-hidden", type=int, default=64)
    p.add_argument("--num-layers", type=int, default=2)
    p.add_argument("--num-sentences", type=int, default=2048)
    main(p.parse_args())
