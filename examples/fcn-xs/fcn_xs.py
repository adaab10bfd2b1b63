#!/usr/bin/env python
"""FCN-xs semantic segmentation (reference ``example/fcn-xs/``:
``symbol_fcnxs.py`` — conv encoder, 1x1 score heads, Deconvolution
upsampling with a skip fusion, per-pixel ``SoftmaxOutput``
``multi_output=True``).

The capability this proves: Deconvolution at segmentation scale — the
transposed-conv upsampling path and the fcn-16s-style skip sum — plus
the multi-output per-pixel softmax, trained end-to-end through
``Module.fit``.

Synthetic task: images containing a bright disk on textured background;
the label map marks disk pixels.  Pixel accuracy must exceed 0.9.

    python examples/fcn-xs/fcn_xs.py --num-epochs 6
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxnet_tpu as mx


def get_symbol(num_classes=2):
    """Encoder /4, score head, 2x deconv + skip (fcn-16s pattern,
    ``symbol_fcnxs.py:60-100``), then a final 2x deconv to full res."""
    data = mx.sym.Variable("data")
    c1 = mx.sym.Convolution(data, num_filter=16, kernel=(3, 3),
                            pad=(1, 1), name="conv1")
    c1 = mx.sym.Activation(mx.sym.BatchNorm(c1, name="bn1"),
                           act_type="relu")
    p1 = mx.sym.Pooling(c1, kernel=(2, 2), stride=(2, 2),
                        pool_type="max")                      # /2
    c2 = mx.sym.Convolution(p1, num_filter=32, kernel=(3, 3),
                            pad=(1, 1), name="conv2")
    c2 = mx.sym.Activation(mx.sym.BatchNorm(c2, name="bn2"),
                           act_type="relu")
    p2 = mx.sym.Pooling(c2, kernel=(2, 2), stride=(2, 2),
                        pool_type="max")                      # /4
    c3 = mx.sym.Convolution(p2, num_filter=32, kernel=(3, 3),
                            pad=(1, 1), name="conv3")
    c3 = mx.sym.Activation(mx.sym.BatchNorm(c3, name="bn3"),
                           act_type="relu")

    # score heads (1x1 convs) at /4 and /2, fused fcn-16s style
    score4 = mx.sym.Convolution(c3, num_filter=num_classes,
                                kernel=(1, 1), name="score4")
    up2 = mx.sym.Deconvolution(score4, kernel=(4, 4), stride=(2, 2),
                               pad=(1, 1), num_filter=num_classes,
                               no_bias=True, name="up2")      # /2
    score2 = mx.sym.Convolution(p1, num_filter=num_classes,
                                kernel=(1, 1), name="score2")
    fused = up2 + score2
    up1 = mx.sym.Deconvolution(fused, kernel=(4, 4), stride=(2, 2),
                               pad=(1, 1), num_filter=num_classes,
                               no_bias=True, name="up1")      # /1
    return mx.sym.SoftmaxOutput(up1, multi_output=True,
                                normalization="batch",
                                name="softmax")


def synth_batch(n, size, rs):
    """Disk of random center/radius on a textured background."""
    imgs = 0.3 * rs.randn(n, 3, size, size).astype("float32")
    labels = np.zeros((n, size, size), "float32")
    yy, xx = np.mgrid[0:size, 0:size]
    for i in range(n):
        cy, cx = rs.randint(size // 4, 3 * size // 4, 2)
        r2 = rs.randint(2, size // 3) ** 2
        mask = ((yy - cy) ** 2 + (xx - cx) ** 2) < r2
        labels[i][mask] = 1.0
        imgs[i, :, mask] += 1.5
    return imgs, labels


def main(args):
    # initializers draw from the process-global rng; seed for reproducible CI
    mx.random.seed(0)
    np.random.seed(0)
    rs = np.random.RandomState(0)
    imgs, labels = synth_batch(args.num_examples, args.size, rs)
    it = mx.io.NDArrayIter(imgs, labels, batch_size=args.batch_size)
    net = get_symbol()
    mod = mx.mod.Module(net, context=mx.current_context())
    mod.fit(it, num_epoch=args.num_epochs, optimizer="adam",
            optimizer_params={"learning_rate": 5e-3},
            initializer=mx.init.Xavier(),
            eval_metric=mx.metric.Loss())

    # per-pixel accuracy on a fresh batch
    test_imgs, test_labels = synth_batch(args.batch_size, args.size, rs)
    mod.forward(mx.io.DataBatch([mx.nd.array(test_imgs)], []),
                is_train=False)
    pred = mod.get_outputs()[0].asnumpy()       # (N, C, H, W)
    pix_acc = float((pred.argmax(1) == test_labels).mean())
    print("pixel accuracy %.4f" % pix_acc)
    return pix_acc


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--num-examples", type=int, default=256)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--size", type=int, default=32)
    p.add_argument("--num-epochs", type=int, default=6)
    main(p.parse_args())
