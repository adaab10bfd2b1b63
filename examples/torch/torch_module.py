#!/usr/bin/env python
"""Use a PyTorch module inside a symbolic graph (the modern analogue of
the reference Torch plugin, ``plugin/torch`` TorchModule — which bridged
*Lua* Torch; see ``mxnet_tpu/torch.py``).

    python examples/torch/torch_module.py
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxnet_tpu as mx
import mxnet_tpu.torch as mxth
import torch


def main():
    # a torch feature extractor inside an mxnet_tpu classifier
    mxth.register_module(
        "torch_features",
        lambda: torch.nn.Sequential(torch.nn.Linear(16, 32),
                                    torch.nn.ReLU()))
    data = mx.sym.Variable("data")
    feats = mx.sym.Custom(data, op_type="torch_features", name="tfeat")
    out = mx.sym.FullyConnected(feats, num_hidden=3, name="head")
    net = mx.sym.SoftmaxOutput(out, name="softmax")

    rs = np.random.RandomState(0)
    x = rs.rand(256, 16).astype("float32")
    w = rs.rand(16, 3).astype("float32")
    y = (x @ w).argmax(1).astype("float32")

    it = mx.io.NDArrayIter(x, y, batch_size=64, shuffle=True)
    mod = mx.mod.Module(net, context=mx.cpu())  # host callbacks -> cpu
    mod.fit(it, num_epoch=15, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5},
            initializer=mx.init.Xavier())
    score = dict(mod.score(mx.io.NDArrayIter(x, y, batch_size=64),
                           mx.metric.create("acc")))
    print("accuracy with torch feature layer:", score)

    # imperative one-liner
    lin = torch.nn.Linear(4, 2)
    print("apply:", mxth.apply(lin, mx.nd.ones((1, 4))).asnumpy())


if __name__ == "__main__":
    main()
