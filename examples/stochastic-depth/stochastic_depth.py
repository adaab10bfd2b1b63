#!/usr/bin/env python
"""Stochastic depth (reference ``example/stochastic-depth/
sd_cifar10.py`` — Huang et al. 2016): each residual BRANCH is dropped
whole with probability ``death_rate`` during training (a per-sample
Bernoulli gate built from symbolic ``random_uniform``), and scaled by
its survival probability at inference — an ensemble of shallower nets
in one model.

Exercises symbolic random ops beyond Dropout: the gate is a graph-level
``random_uniform -> _greater_scalar -> broadcast_mul`` pattern, train/
inference divergence expressed with two symbols sharing parameters.

    python examples/stochastic-depth/stochastic_depth.py
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxnet_tpu as mx


def residual_unit(x, idx, num_filter, death_rate, batch_size,
                  train):
    h = mx.sym.BatchNorm(x, fix_gamma=False, name="u%d_bn1" % idx)
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.Convolution(h, num_filter=num_filter, kernel=(3, 3),
                           pad=(1, 1), no_bias=True,
                           name="u%d_conv1" % idx)
    h = mx.sym.BatchNorm(h, fix_gamma=False, name="u%d_bn2" % idx)
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.Convolution(h, num_filter=num_filter, kernel=(3, 3),
                           pad=(1, 1), no_bias=True,
                           name="u%d_conv2" % idx)
    if train:
        # per-sample survival gate: u ~ U(0,1) >= death_rate, scaled by
        # 1/survival so the expectation matches inference
        gate = mx.sym.random_uniform(low=0.0, high=1.0,
                                     shape=(batch_size, 1, 1, 1))
        gate = mx.sym._greater_equal_scalar(gate, scalar=death_rate) \
            if hasattr(mx.sym, "_greater_equal_scalar") else \
            1.0 - mx.sym._lesser_scalar(gate, scalar=death_rate)
        h = mx.sym.broadcast_mul(h, gate) * (1.0 / (1.0 - death_rate))
    return x + h


def get_symbol(units, num_filter, death_rates, batch_size, train):
    x = mx.sym.Variable("data")
    x = mx.sym.Convolution(x, num_filter=num_filter, kernel=(3, 3),
                           pad=(1, 1), no_bias=True, name="conv0")
    for i in range(units):
        x = residual_unit(x, i, num_filter, death_rates[i], batch_size,
                          train)
    x = mx.sym.BatchNorm(x, fix_gamma=False, name="bn_out")
    x = mx.sym.Activation(x, act_type="relu")
    x = mx.sym.Pooling(x, global_pool=True, kernel=(2, 2),
                       pool_type="avg")
    x = mx.sym.FullyConnected(mx.sym.Flatten(x), num_hidden=4,
                              name="fc")
    return mx.sym.SoftmaxOutput(x, name="softmax")


def synth(n, rs):
    imgs = 0.3 * rs.randn(n, 3, 12, 12).astype("float32")
    labels = rs.randint(0, 4, n).astype("float32")
    yy, xx = np.mgrid[0:12, 0:12]
    for i in range(n):
        q = int(labels[i])
        cy, cx = 3 + 6 * (q // 2), 3 + 6 * (q % 2)
        imgs[i, :, max(0, cy - 2):cy + 2, max(0, cx - 2):cx + 2] += 1.3
    return imgs, labels


def main(args):
    # initializers draw from the process-global rng; seed for reproducible CI
    mx.random.seed(0)
    np.random.seed(0)
    rs = np.random.RandomState(0)
    X, y = synth(args.num_examples, rs)
    it = mx.io.NDArrayIter(X, y, batch_size=args.batch_size)
    # linearly increasing death rates over depth (the paper's schedule)
    rates = [args.death_rate * (i + 1) / args.units
             for i in range(args.units)]
    train_sym = get_symbol(args.units, 16, rates, args.batch_size, True)
    mod = mx.mod.Module(train_sym, context=mx.current_context())
    mod.fit(it, num_epoch=args.num_epochs, optimizer="adam",
            optimizer_params={"learning_rate": 5e-3},
            initializer=mx.init.Xavier())

    # inference graph: same parameters, gates replaced by expectation
    arg_params, aux_params = mod.get_params()
    infer_sym = get_symbol(args.units, 16, rates, args.batch_size, False)
    imod = mx.mod.Module(infer_sym, context=mx.current_context())
    it.reset()
    imod.bind(data_shapes=it.provide_data,
              label_shapes=it.provide_label, for_training=False)
    imod.set_params(arg_params, aux_params)
    score = dict(imod.score(it, mx.metric.Accuracy()))
    print("stochastic-depth val accuracy %.4f (death_rate %.2f over %d "
          "units)" % (score["accuracy"], args.death_rate, args.units))
    return score["accuracy"]


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--units", type=int, default=4)
    p.add_argument("--death-rate", type=float, default=0.3)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--num-examples", type=int, default=512)
    p.add_argument("--num-epochs", type=int, default=12)
    main(p.parse_args())
