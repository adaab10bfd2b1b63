#!/usr/bin/env python
"""Noise-contrastive estimation over a shared output-embedding table
(reference ``example/nce-loss/toy_nce.py`` / ``nce.py``): instead of a
full-vocabulary softmax — O(vocab) output FLOPs and a dense (vocab, h)
gradient per step — each example scores 1 true + K noise candidates
against the output embedding and trains a logistic discriminator
(``LogisticRegressionOutput``), touching only K+1 embedding rows.

Toy task: predict (a + b) mod vocab from tokens (a, b).  After NCE
training the FULL-vocab argmax over the learned output table must
recover the target (the point of NCE: cheap training, intact ranking).

    python examples/nce-loss/toy_nce.py --num-epochs 12
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxnet_tpu as mx


def nce_loss(data, label, label_weight, vocab_size, num_hidden,
             num_label):
    """The reference's NCE head (``nce-loss/nce.py:26-33``): candidate
    embeddings dot the feature vector, logistic loss over true/noise."""
    embed_weight = mx.sym.Variable("output_embed_weight")
    label_embed = mx.sym.Embedding(label, input_dim=vocab_size,
                                   weight=embed_weight,
                                   output_dim=num_hidden,
                                   name="label_embed")
    data = mx.sym.Reshape(data, shape=(-1, 1, num_hidden))
    pred = mx.sym.broadcast_mul(data, label_embed)
    pred = mx.sym.sum(pred, axis=2)
    return mx.sym.LogisticRegressionOutput(pred, label_weight,
                                           name="nce")


def get_symbol(vocab_in, vocab_out, num_hidden, num_label):
    data = mx.sym.Variable("data")          # (N, 2) token pair
    label = mx.sym.Variable("label")        # (N, K+1) candidates
    label_weight = mx.sym.Variable("label_weight")  # 1 true, 0 noise
    emb = mx.sym.Embedding(data, input_dim=vocab_in, output_dim=num_hidden,
                           name="data_embed")
    feat = mx.sym.Reshape(emb, shape=(-1, 2 * num_hidden))
    feat = mx.sym.FullyConnected(feat, num_hidden=num_hidden,
                                 name="feat_fc")
    feat = mx.sym.Activation(feat, act_type="tanh")
    return nce_loss(feat, label, label_weight, vocab_out, num_hidden,
                    num_label)


def make_batches(n, vocab, num_label, rs):
    a = rs.randint(0, vocab, n)
    b = rs.randint(0, vocab, n)
    y = (a + b) % vocab
    data = np.stack([a, b], 1).astype("float32")
    # candidate 0 is the true class; the rest are noise draws
    cands = np.empty((n, num_label), "float32")
    weights = np.zeros((n, num_label), "float32")
    cands[:, 0] = y
    weights[:, 0] = 1.0
    cands[:, 1:] = rs.randint(0, vocab, (n, num_label - 1))
    return data, y, cands, weights


def main(args):
    # initializers draw from the process-global rng; seed for reproducible CI
    mx.random.seed(0)
    np.random.seed(0)
    rs = np.random.RandomState(0)
    vocab, h, num_label = args.vocab, args.num_hidden, args.num_label
    data, y, cands, weights = make_batches(args.num_examples, vocab,
                                           num_label, rs)
    it = mx.io.NDArrayIter({"data": data, "label": cands},
                           {"label_weight": weights},
                           batch_size=args.batch_size)
    net = get_symbol(vocab, vocab, h, num_label)
    mod = mx.mod.Module(net, data_names=("data", "label"),
                        label_names=("label_weight",),
                        context=mx.current_context())
    mod.fit(it, num_epoch=args.num_epochs, optimizer="adam",
            optimizer_params={"learning_rate": 0.02},
            initializer=mx.init.Xavier(),
            eval_metric=mx.metric.Loss())

    # full-vocab ranking with the learned tables: NCE must have shaped
    # the output embedding so the true class wins the argmax
    params, _ = mod.get_params()
    emb_w = params["data_embed_weight"].asnumpy()
    fc_w = params["feat_fc_weight"].asnumpy()
    fc_b = params["feat_fc_bias"].asnumpy()
    out_w = params["output_embed_weight"].asnumpy()
    feats = np.concatenate([emb_w[data[:, 0].astype(int)],
                            emb_w[data[:, 1].astype(int)]], 1)
    hid = np.tanh(feats @ fc_w.T + fc_b)
    scores = hid @ out_w.T            # (N, vocab) full ranking
    acc = float((scores.argmax(1) == y).mean())
    print("full-vocab argmax accuracy %.4f (vocab=%d, %d candidates "
          "scored per step during training)" % (acc, vocab, num_label))
    return acc


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--vocab", type=int, default=30)
    p.add_argument("--num-hidden", type=int, default=96)
    p.add_argument("--num-label", type=int, default=10)
    p.add_argument("--batch-size", type=int, default=128)
    p.add_argument("--num-epochs", type=int, default=25)
    p.add_argument("--num-examples", type=int, default=8192)
    main(p.parse_args())
