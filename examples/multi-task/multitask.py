#!/usr/bin/env python
"""Multi-task training — one trunk, two supervised heads
(reference ``example/multi-task/example_multi_task.py``: shared conv
trunk, two SoftmaxOutput heads grouped, per-head metrics).

Synthetic task on 16x16 images of a bright blob: head A classifies the
QUADRANT (4-way), head B classifies the SIZE (small/large, 2-way) —
two labels per example, one shared representation.

    python examples/multi-task/multitask.py
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxnet_tpu as mx


def get_symbol():
    data = mx.sym.Variable("data")
    c = mx.sym.Convolution(data, num_filter=16, kernel=(3, 3),
                           pad=(1, 1), name="conv1")
    c = mx.sym.Activation(mx.sym.BatchNorm(c, name="bn1"),
                          act_type="relu")
    c = mx.sym.Pooling(c, kernel=(2, 2), stride=(2, 2), pool_type="max")
    c = mx.sym.Convolution(c, num_filter=32, kernel=(3, 3), pad=(1, 1),
                           name="conv2")
    c = mx.sym.Activation(mx.sym.BatchNorm(c, name="bn2"),
                          act_type="relu")
    feat = mx.sym.Flatten(mx.sym.Pooling(c, global_pool=True,
                                         kernel=(2, 2),
                                         pool_type="avg"))
    quad = mx.sym.FullyConnected(feat, num_hidden=4, name="quad_fc")
    quad = mx.sym.SoftmaxOutput(quad, name="quad")
    size = mx.sym.FullyConnected(feat, num_hidden=2, name="size_fc")
    size = mx.sym.SoftmaxOutput(size, name="size")
    return mx.sym.Group([quad, size])


def synth(n, rs):
    imgs = 0.2 * rs.randn(n, 1, 16, 16).astype("float32")
    quad = rs.randint(0, 4, n).astype("float32")
    size = rs.randint(0, 2, n).astype("float32")
    yy, xx = np.mgrid[0:16, 0:16]
    for i in range(n):
        cy = 4 + 8 * (int(quad[i]) // 2)
        cx = 4 + 8 * (int(quad[i]) % 2)
        r2 = (2 if size[i] == 0 else 4) ** 2
        imgs[i, 0][((yy - cy) ** 2 + (xx - cx) ** 2) < r2] += 1.5
    return imgs, quad, size


def main(args):
    # initializers draw from the process-global rng; seed for reproducible CI
    mx.random.seed(0)
    np.random.seed(0)
    rs = np.random.RandomState(0)
    imgs, quad, size = synth(args.num_examples, rs)
    it = mx.io.NDArrayIter(
        imgs, {"quad_label": quad, "size_label": size},
        batch_size=args.batch_size)
    mod = mx.mod.Module(get_symbol(),
                        label_names=("quad_label", "size_label"),
                        context=mx.current_context())
    mod.fit(it, num_epoch=args.num_epochs, optimizer="adam",
            optimizer_params={"learning_rate": 5e-3},
            initializer=mx.init.Xavier(),
            eval_metric=mx.metric.Accuracy())
    # per-head accuracies (update_metric pairs heads by exact name)
    accs = {}
    for name in ("quad", "size"):
        metric = mx.metric.Accuracy()
        it.reset()
        for batch in it:
            mod.forward(batch, is_train=False)
            outs = mod.get_outputs()
            idx = 0 if name == "quad" else 1
            lab = batch.label[idx]
            metric.update([lab], [outs[idx]])
        accs[name] = metric.get()[1]
        print("%s accuracy %.4f" % (name, accs[name]))
    return accs


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--num-examples", type=int, default=512)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--num-epochs", type=int, default=12)
    main(p.parse_args())
