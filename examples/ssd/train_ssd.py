#!/usr/bin/env python
"""Train a miniature SSD detector end-to-end (reference ``example/ssd``):
``ImageDetIter`` feeds box labels to a multi-scale symbol built from
``MultiBoxPrior``/``MultiBoxTarget``, trained with the reference's
two-part loss (multi-output softmax over classes + smooth-L1 on masked
location offsets), and ``MultiBoxDetection`` decodes + NMSes at
inference.

Hermetic: synthetic images with one colored square per class.

    python examples/ssd/train_ssd.py --num-epochs 10
"""
import argparse
import logging
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxnet_tpu as mx

logging.basicConfig(level=logging.INFO)

NUM_CLASSES = 2          # square / circle-ish blob
SIZES = ((0.3, 0.4), (0.6, 0.8))
RATIOS = ((1.0,), (1.0,))


def conv_block(data, num_filter, name, stride=(1, 1)):
    c = mx.sym.Convolution(data, kernel=(3, 3), pad=(1, 1), stride=stride,
                           num_filter=num_filter, no_bias=True, name=name)
    bn = mx.sym.BatchNorm(c, fix_gamma=False, name=name + "_bn")
    return mx.sym.Activation(bn, act_type="relu")


def ssd_symbol(num_classes=NUM_CLASSES):
    data = mx.sym.Variable("data")
    label = mx.sym.Variable("label")
    body = conv_block(data, 16, "c1", stride=(2, 2))    # 32 -> 16
    body = conv_block(body, 32, "c2", stride=(2, 2))    # -> 8
    fm1 = body                                          # 8x8
    fm2 = conv_block(body, 64, "c3", stride=(2, 2))     # 4x4

    anchors, loc_preds, cls_preds = [], [], []
    for i, fm in enumerate((fm1, fm2)):
        a_per_cell = len(SIZES[i]) + len(RATIOS[i]) - 1
        anchors.append(mx.sym.MultiBoxPrior(
            fm, sizes=SIZES[i], ratios=RATIOS[i], name="anchors%d" % i))
        loc = mx.sym.Convolution(fm, kernel=(3, 3), pad=(1, 1),
                                 num_filter=a_per_cell * 4,
                                 name="loc%d" % i)
        loc = mx.sym.transpose(loc, axes=(0, 2, 3, 1))
        loc_preds.append(mx.sym.Flatten(loc))
        cls = mx.sym.Convolution(fm, kernel=(3, 3), pad=(1, 1),
                                 num_filter=a_per_cell * (num_classes + 1),
                                 name="cls%d" % i)
        cls = mx.sym.transpose(cls, axes=(0, 2, 3, 1))
        cls_preds.append(mx.sym.Reshape(
            cls, shape=(0, -1, num_classes + 1)))

    all_anchors = mx.sym.Concat(*anchors, dim=1, name="all_anchors")
    loc_pred = mx.sym.Concat(*loc_preds, dim=1, name="loc_pred")
    cls_pred = mx.sym.Concat(*cls_preds, dim=1, name="cls_pred_nac")
    # (B, N, C+1) -> (B, C+1, N): the layout MultiBox/softmax expect
    cls_pred = mx.sym.transpose(cls_pred, axes=(0, 2, 1),
                                name="cls_pred")

    loc_t, loc_m, cls_t = mx.sym.MultiBoxTarget(
        all_anchors, label, cls_pred, name="target")
    cls_prob = mx.sym.SoftmaxOutput(cls_pred, cls_t, multi_output=True,
                                    normalization="valid",
                                    name="cls_prob")
    loc_diff = loc_m * (loc_pred - loc_t)
    loc_loss = mx.sym.MakeLoss(mx.sym.smooth_l1(loc_diff, scalar=1.0),
                               grad_scale=1.0, normalization="valid",
                               name="loc_loss")
    # keep targets visible for metrics/decoding without extra binds
    return mx.sym.Group([cls_prob, loc_loss,
                         mx.sym.BlockGrad(cls_t),
                         mx.sym.BlockGrad(loc_pred),
                         mx.sym.BlockGrad(all_anchors)])


def synthetic_batch(rs, n, size=32):
    imgs = np.zeros((n, 3, size, size), "float32")
    labels = np.full((n, 2, 5), -1.0, "float32")
    for i in range(n):
        cls = int(rs.randint(NUM_CLASSES))
        w = rs.randint(size // 4, size // 2)
        x0 = rs.randint(0, size - w)
        y0 = rs.randint(0, size - w)
        imgs[i, cls, y0:y0 + w, x0:x0 + w] = 1.0
        labels[i, 0] = [cls, x0 / size, y0 / size, (x0 + w) / size,
                        (y0 + w) / size]
    return imgs, labels


def main(args):
    rs = np.random.RandomState(0)
    imgs, labels = synthetic_batch(rs, args.num_examples)
    it = mx.io.NDArrayIter(imgs, labels, args.batch_size, shuffle=True,
                           label_name="label")

    sym = ssd_symbol()
    mod = mx.mod.Module(sym, context=mx.current_context(),
                        label_names=("label",), data_names=("data",))
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="adam",
                       optimizer_params={"learning_rate": args.lr})

    first = last = None
    for epoch in range(args.num_epochs):
        it.reset()
        total = 0.0
        for batch in it:
            mod.forward(batch, is_train=True)
            outs = mod.get_outputs()
            cls_prob, _loc_loss, cls_t = outs[0], outs[1], outs[2]
            # cross-entropy of matched anchors (monitoring only)
            p = cls_prob.asnumpy()
            t = cls_t.asnumpy().astype(int)
            valid = t >= 0
            rows = np.take_along_axis(
                p, t[:, None, :].clip(0), axis=1)[:, 0, :]
            total += float(-np.log(rows[valid].clip(1e-9)).mean())
            mod.backward()
            mod.update()
        if first is None:
            first = total
        last = total
        logging.info("epoch %d cls-loss %.4f", epoch, total)

    # inference: decode + NMS
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(imgs[:4])],
                                label=[mx.nd.array(labels[:4])]),
                is_train=False)
    outs = mod.get_outputs()
    cls_prob, loc_pred, anchors = outs[0], outs[3], outs[4]
    det = mx.contrib.nd.MultiBoxDetection(cls_prob, loc_pred, anchors,
                                          nms_threshold=0.5)
    kept = det.asnumpy()[0]
    logging.info("detections (cls, score, box): %s",
                 kept[kept[:, 0] >= 0][:3])
    print("loss first->last: %.3f -> %.3f" % (first, last))
    return first, last


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--num-epochs", type=int, default=10)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--lr", type=float, default=0.005)
    p.add_argument("--num-examples", type=int, default=512)
    main(p.parse_args())
