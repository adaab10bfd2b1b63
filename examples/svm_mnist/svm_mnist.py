#!/usr/bin/env python
"""SVM output layer (reference ``example/svm_mnist/``): the same MLP
trained once with ``SVMOutput`` (hinge loss, margin-based) and once
with ``SoftmaxOutput`` — both must learn the task; the SVM variant
demonstrates the margin head end-to-end (L2-regularized squared hinge
by default, ``use_linear=1`` for L1 hinge).

    python examples/svm_mnist/svm_mnist.py
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxnet_tpu as mx


def get_symbol(head, num_classes):
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=64, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=num_classes, name="fc2")
    if head == "svm":
        # L1 hinge (use_linear): bounded per-element gradients —
        # the squared hinge at this feature scale needs a much
        # cooler lr (its gradient grows with the violation)
        return mx.sym.SVMOutput(fc2, name="svm",
                                regularization_coefficient=1.0,
                                use_linear=1)
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def synth(n, rs, num_classes=4, dim=32):
    centers = rs.randn(num_classes, dim).astype("float32") * 1.5
    y = rs.randint(0, num_classes, n).astype("float32")
    X = centers[y.astype(int)] + 0.5 * rs.randn(n, dim).astype("float32")
    return X, y


def train(head, X, y, epochs):
    label_name = "svm_label" if head == "svm" else "softmax_label"
    it = mx.io.NDArrayIter(X, y, batch_size=64, label_name=label_name)
    mod = mx.mod.Module(get_symbol(head, 4), context=mx.current_context(),
                        label_names=(label_name,))
    lr = 0.1
    mod.fit(it, num_epoch=epochs, optimizer="sgd",
            optimizer_params={"learning_rate": lr, "momentum": 0.9},
            initializer=mx.init.Xavier())
    mod.forward(mx.io.DataBatch([mx.nd.array(X)], [mx.nd.array(y)]),
                is_train=False)
    scores = mod.get_outputs()[0].asnumpy()
    return float((scores.argmax(1) == y).mean())


def main(args):
    # initializers draw from the process-global rng; seed for reproducible CI
    mx.random.seed(0)
    np.random.seed(0)
    rs = np.random.RandomState(0)
    X, y = synth(args.num_examples, rs)
    svm_acc = train("svm", X, y, args.num_epochs)
    sm_acc = train("softmax", X, y, args.num_epochs)
    print("svm acc %.4f | softmax acc %.4f" % (svm_acc, sm_acc))
    return svm_acc, sm_acc


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--num-examples", type=int, default=512)
    p.add_argument("--num-epochs", type=int, default=20)
    main(p.parse_args())
