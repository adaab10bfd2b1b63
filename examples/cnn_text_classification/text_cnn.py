#!/usr/bin/env python
"""CNN for sentence classification (reference
``example/cnn_text_classification/text_cnn.py`` — Kim 2014: embedding,
parallel convolutions with multiple kernel heights over the token
axis, max-over-time pooling, concat, dropout, softmax).

Synthetic task: a sequence is positive iff it contains the trigram
pattern [3, 1, 4] — exactly the local-pattern detection the
multi-width conv + max-over-time architecture exists for.

    python examples/cnn_text_classification/text_cnn.py
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxnet_tpu as mx


def get_symbol(vocab, seq_len, embed=32, filters=(3, 4, 5),
               num_filter=16, dropout=0.3):
    data = mx.sym.Variable("data")
    emb = mx.sym.Embedding(data, input_dim=vocab, output_dim=embed,
                           name="embed")
    x = mx.sym.Reshape(emb, shape=(-1, 1, seq_len, embed))
    pooled = []
    for k in filters:
        c = mx.sym.Convolution(x, num_filter=num_filter,
                               kernel=(k, embed), name="conv%d" % k)
        c = mx.sym.Activation(c, act_type="relu")
        c = mx.sym.Pooling(c, kernel=(seq_len - k + 1, 1),
                           pool_type="max")
        pooled.append(c)
    h = mx.sym.Flatten(mx.sym.Concat(*pooled, dim=1))
    if dropout:
        h = mx.sym.Dropout(h, p=dropout)
    fc = mx.sym.FullyConnected(h, num_hidden=2, name="fc")
    return mx.sym.SoftmaxOutput(fc, name="softmax")


def synth(n, vocab, seq_len, rs):
    data = rs.randint(5, vocab, (n, seq_len)).astype("float32")
    y = rs.randint(0, 2, n).astype("float32")
    pat = [3, 1, 4]
    for i in range(n):
        if y[i] == 1:
            p = rs.randint(0, seq_len - len(pat))
            data[i, p:p + len(pat)] = pat
    return data, y


def main(args):
    # initializers draw from the process-global rng; seed for reproducible CI
    mx.random.seed(0)
    np.random.seed(0)
    rs = np.random.RandomState(0)
    data, y = synth(args.num_examples, args.vocab, args.seq_len, rs)
    it = mx.io.NDArrayIter(data, y, batch_size=args.batch_size)
    mod = mx.mod.Module(get_symbol(args.vocab, args.seq_len),
                        context=mx.current_context())
    mod.fit(it, num_epoch=args.num_epochs, optimizer="adam",
            optimizer_params={"learning_rate": 5e-3},
            initializer=mx.init.Xavier(),
            eval_metric=mx.metric.Accuracy())
    score = dict(mod.score(it, mx.metric.Accuracy()))
    print("train accuracy %.4f" % score["accuracy"])
    return score["accuracy"]


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--vocab", type=int, default=50)
    p.add_argument("--seq-len", type=int, default=24)
    p.add_argument("--num-examples", type=int, default=1024)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--num-epochs", type=int, default=8)
    main(p.parse_args())
