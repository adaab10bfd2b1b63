#!/usr/bin/env python
"""CTC sequence training (reference ``example/warpctc/``: OCR-style
alignment-free sequence labeling over the warpctc plugin's ``CTCLoss``;
here the native ``ctc_loss`` op — a log-domain ``lax.scan`` forward
recursion, gradient by autodiff).

Toy OCR: each 'image' is a T-step signal carrying K < T digit glyphs at
unknown positions; the model (BiLSTM over the signal) must emit the
digit STRING, alignment unsupervised — exactly what CTC exists for.
Greedy-decode exact-string accuracy must exceed 0.9.

    python examples/warpctc/ctc_ocr.py
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxnet_tpu as mx


def get_symbol(seq_len, num_hidden, vocab):
    """(N, T, F) signal -> BiLSTM -> per-step logits (T, N, C) ->
    CTCLoss via MakeLoss (the warpctc example's net shape)."""
    data = mx.sym.Variable("data")
    label = mx.sym.Variable("label")        # (N, L) 0-padded, ids 1..9
    cell = mx.rnn.BidirectionalCell(
        mx.rnn.LSTMCell(num_hidden=num_hidden, prefix="l_"),
        mx.rnn.LSTMCell(num_hidden=num_hidden, prefix="r_"))
    outputs, _ = cell.unroll(seq_len, inputs=data, merge_outputs=True,
                             layout="NTC")
    pred = mx.sym.Reshape(outputs, shape=(-1, 2 * num_hidden))
    pred = mx.sym.FullyConnected(pred, num_hidden=vocab, name="cls")
    pred = mx.sym.Reshape(pred, shape=(-4, -1, seq_len, 0))  # (N,T,C)
    pred = mx.sym.transpose(pred, axes=(1, 0, 2))            # (T,N,C)
    loss = mx.sym.make_loss(mx.sym.mean(
        mx.sym.ctc_loss(pred, label)), name="ctc")
    # expose the softmax for decoding alongside the loss head
    sm = mx.sym.BlockGrad(mx.sym.softmax(pred, axis=-1), name="probs")
    return mx.sym.Group([loss, sm])


def synth(n, seq_len, n_digits, rs):
    """T-step 10-d signal: digit d pulses feature d for 2 steps at a
    random position; label = the digit sequence in order."""
    X = 0.1 * rs.randn(n, seq_len, 10).astype("float32")
    labels = np.zeros((n, n_digits), "float32")
    for i in range(n):
        # distinct, ordered pulse positions with gaps
        pos = np.sort(rs.choice(seq_len // 2 - 1, n_digits,
                                replace=False)) * 2
        digs = rs.randint(0, 9, n_digits)
        for k, (p, d) in enumerate(zip(pos, digs)):
            X[i, p:p + 2, d] += 2.0
            labels[i, k] = d + 1          # CTC ids 1..9 (0 = blank)
    return X, labels


def greedy_decode(probs):
    """(T, N, C) -> list of id sequences (collapse repeats, drop
    blanks)."""
    ids = probs.argmax(-1).T              # (N, T)
    out = []
    for row in ids:
        seq, prev = [], -1
        for t in row:
            if t != prev and t != 0:
                seq.append(int(t))
            prev = t
        out.append(seq)
    return out


def main(args):
    # initializers draw from the process-global rng; seed for reproducible CI
    mx.random.seed(0)
    np.random.seed(0)
    rs = np.random.RandomState(0)
    X, labels = synth(args.num_examples, args.seq_len, args.n_digits, rs)
    it = mx.io.NDArrayIter({"data": X}, {"label": labels},
                           batch_size=args.batch_size)
    mod = mx.mod.Module(get_symbol(args.seq_len, args.num_hidden, 10),
                        data_names=("data",), label_names=("label",),
                        context=mx.current_context())
    mod.fit(it, num_epoch=args.num_epochs, optimizer="adam",
            optimizer_params={"learning_rate": 0.01},
            initializer=mx.init.Xavier(),
            eval_metric=mx.metric.Loss())

    mod.forward(mx.io.DataBatch([mx.nd.array(X)],
                                [mx.nd.array(labels)]), is_train=False)
    probs = mod.get_outputs()[1].asnumpy()
    decoded = greedy_decode(probs)
    want = [[int(v) for v in row if v != 0] for row in labels]
    acc = float(np.mean([d == w for d, w in zip(decoded, want)]))
    print("exact-string accuracy %.4f (alignment-free)" % acc)
    return acc


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--seq-len", type=int, default=16)
    p.add_argument("--n-digits", type=int, default=3)
    p.add_argument("--num-hidden", type=int, default=48)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--num-examples", type=int, default=1024)
    p.add_argument("--num-epochs", type=int, default=15)
    main(p.parse_args())
