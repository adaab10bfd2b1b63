#!/usr/bin/env python
"""Adversarial examples by FGSM (reference ``example/adversary/``):
train a classifier, then perturb inputs along the SIGN of the loss
gradient w.r.t. the INPUT — accuracy must collapse under an epsilon
that leaves the images visually unchanged, and recover when the
perturbation is random instead of adversarial.

Exercises ``Module.bind(inputs_need_grad=True)`` + ``get_input_grads``
— the executor's data-gradient path.

    python examples/adversary/fgsm.py
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxnet_tpu as mx


def get_symbol():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=64, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def synth(n, rs):
    """4-class blobs in 16-d space with margin."""
    centers = rs.randn(4, 16).astype("float32") * 1.0
    y = rs.randint(0, 4, n).astype("float32")
    X = centers[y.astype(int)] + 0.4 * rs.randn(n, 16).astype("float32")
    return X, y


def accuracy(mod, X, y):
    mod.forward(mx.io.DataBatch([mx.nd.array(X)], [mx.nd.array(y)]),
                is_train=False)
    pred = mod.get_outputs()[0].asnumpy()
    return float((pred.argmax(1) == y).mean())


def main(args):
    # initializers draw from the process-global rng; seed for reproducible CI
    mx.random.seed(0)
    np.random.seed(0)
    rs = np.random.RandomState(0)
    X, y = synth(args.num_examples, rs)
    it = mx.io.NDArrayIter(X, y, batch_size=args.num_examples)
    mod = mx.mod.Module(get_symbol(), context=mx.current_context())
    mod.bind(data_shapes=it.provide_data,
             label_shapes=it.provide_label, inputs_need_grad=True)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="adam",
                       optimizer_params={"learning_rate": 0.01})
    for _ in range(args.num_epochs):
        it.reset()
        for batch in it:
            mod.forward(batch, is_train=True)
            mod.backward()
            mod.update()

    clean_acc = accuracy(mod, X, y)

    # FGSM: x_adv = x + eps * sign(dL/dx)
    mod.forward(mx.io.DataBatch([mx.nd.array(X)], [mx.nd.array(y)]),
                is_train=True)
    mod.backward()
    gx = mod.get_input_grads()[0].asnumpy()
    X_adv = X + args.eps * np.sign(gx)
    adv_acc = accuracy(mod, X_adv, y)

    # control: the same budget of RANDOM-sign noise barely hurts
    X_rand = X + args.eps * np.sign(rs.randn(*X.shape)).astype("float32")
    rand_acc = accuracy(mod, X_rand, y)

    print("clean acc %.3f | FGSM(eps=%.2f) acc %.3f | random-sign "
          "acc %.3f" % (clean_acc, args.eps, adv_acc, rand_acc))
    return clean_acc, adv_acc, rand_acc


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--num-examples", type=int, default=512)
    p.add_argument("--num-epochs", type=int, default=40)
    p.add_argument("--eps", type=float, default=0.8)
    main(p.parse_args())
