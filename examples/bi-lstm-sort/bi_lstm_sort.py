#!/usr/bin/env python
"""Bidirectional-LSTM sequence sorting (reference
``example/bi-lstm-sort/``: read a sequence of tokens, emit the same
tokens sorted — the classic seq-labeling task showing a BiLSTM sees
the whole sequence at every output position).

Uses the rnn toolkit's ``BidirectionalCell`` over ``LSTMCell``s with
``unroll``, per-position softmax — every output position must name the
k-th smallest input token.

    python examples/bi-lstm-sort/bi_lstm_sort.py
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxnet_tpu as mx


def get_symbol(vocab, seq_len, num_hidden):
    data = mx.sym.Variable("data")          # (N, T) token ids
    label = mx.sym.Variable("softmax_label")  # (N, T) sorted ids
    embed = mx.sym.Embedding(data, input_dim=vocab, output_dim=num_hidden,
                             name="embed")
    cell = mx.rnn.BidirectionalCell(
        mx.rnn.LSTMCell(num_hidden=num_hidden, prefix="l_"),
        mx.rnn.LSTMCell(num_hidden=num_hidden, prefix="r_"))
    outputs, _ = cell.unroll(seq_len, inputs=embed, merge_outputs=True,
                             layout="NTC")
    pred = mx.sym.Reshape(outputs, shape=(-1, 2 * num_hidden))
    pred = mx.sym.FullyConnected(pred, num_hidden=vocab, name="cls")
    label_f = mx.sym.Reshape(label, shape=(-1,))
    return mx.sym.SoftmaxOutput(pred, label_f, name="softmax",
                                normalization="batch")


def synth(n, vocab, seq_len, rs):
    data = rs.randint(0, vocab, (n, seq_len)).astype("float32")
    label = np.sort(data, axis=1).astype("float32")
    return data, label


def main(args):
    # initializers draw from the process-global rng; seed for reproducible CI
    mx.random.seed(0)
    np.random.seed(0)
    rs = np.random.RandomState(0)
    data, label = synth(args.num_examples, args.vocab, args.seq_len, rs)
    it = mx.io.NDArrayIter(data, label, batch_size=args.batch_size)
    mod = mx.mod.Module(get_symbol(args.vocab, args.seq_len,
                                   args.num_hidden),
                        context=mx.current_context())
    mod.fit(it, num_epoch=args.num_epochs, optimizer="adam",
            optimizer_params={"learning_rate": 0.01},
            initializer=mx.init.Xavier(),
            eval_metric=mx.metric.Perplexity(ignore_label=None))
    # per-position accuracy of the sort
    mod.forward(mx.io.DataBatch([mx.nd.array(data)],
                                [mx.nd.array(label)]), is_train=False)
    pred = mod.get_outputs()[0].asnumpy().reshape(
        len(data), args.seq_len, args.vocab)
    acc = float((pred.argmax(-1) == label).mean())
    print("sort accuracy %.4f (per position)" % acc)
    return acc


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--vocab", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=6)
    p.add_argument("--num-hidden", type=int, default=48)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--num-examples", type=int, default=2048)
    p.add_argument("--num-epochs", type=int, default=15)
    main(p.parse_args())
