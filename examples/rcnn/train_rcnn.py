#!/usr/bin/env python
"""Train a miniature Faster R-CNN / R-FCN detector end-to-end
(reference ``example/rcnn``): an RPN over a small conv backbone feeds
the ``Proposal`` op, proposals drive ``PSROIPooling`` (the R-FCN head),
and — like the reference, whose target assignment runs as custom Python
ops — anchor and proposal targets are ``CustomOp``s written with
``mx.nd`` operations, which this framework traces into the XLA program
so they run ON the accelerator (no host callback).

Hermetic: synthetic images with one colored square per class, gt boxes
in pixel coordinates (the Proposal/R-CNN convention).

    python examples/rcnn/train_rcnn.py --num-epochs 8
"""
import argparse
import logging
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxnet_tpu as mx
import mxnet_tpu.operator as mxop

logging.basicConfig(level=logging.INFO)

NUM_CLASSES = 2          # foreground classes; 0 is background
IMG = 32
STRIDE = 4
FM = IMG // STRIDE       # 8x8 feature map
SCALES = (2.0, 4.0)      # anchor sizes 8, 16 px at stride 4
RATIOS = (1.0,)
A = len(SCALES) * len(RATIOS)
POST_NMS = 8             # rois per image
POOLED = 3               # psroi grid


def _base_anchors():
    """Same anchor construction as the Proposal op (pixel coords)."""
    base = []
    for r in RATIOS:
        for s in SCALES:
            ww = STRIDE * s * np.sqrt(1.0 / r)
            hh = STRIDE * s * np.sqrt(r)
            base.append((-ww / 2, -hh / 2, ww / 2, hh / 2))
    base = np.asarray(base, "float32")                      # (A, 4)
    sy = np.arange(FM, dtype="float32") * STRIDE
    sx = np.arange(FM, dtype="float32") * STRIDE
    cy, cx = np.meshgrid(sy, sx, indexing="ij")
    shift = np.stack([cx, cy, cx, cy], axis=-1)             # (H, W, 4)
    return (shift[:, :, None, :] + base[None, None, :, :]    # (H,W,A,4)
            ).reshape(-1, 4)                                 # (HWA, 4)


def _iou_nd(boxes, gt):
    """IoU of (N, 4) boxes vs (N, 4) gt rows — mx.nd, traceable."""
    x1 = mx.nd.elemwise_maximum(boxes[:, 0], gt[:, 0])
    y1 = mx.nd.elemwise_maximum(boxes[:, 1], gt[:, 1])
    x2 = mx.nd.elemwise_minimum(boxes[:, 2], gt[:, 2])
    y2 = mx.nd.elemwise_minimum(boxes[:, 3], gt[:, 3])
    iw = mx.nd._maximum_scalar(x2 - x1 + 1.0, scalar=0.0)
    ih = mx.nd._maximum_scalar(y2 - y1 + 1.0, scalar=0.0)
    inter = iw * ih
    area_b = (boxes[:, 2] - boxes[:, 0] + 1.0) * \
             (boxes[:, 3] - boxes[:, 1] + 1.0)
    area_g = (gt[:, 2] - gt[:, 0] + 1.0) * (gt[:, 3] - gt[:, 1] + 1.0)
    return inter / (area_b + area_g - inter + 1e-6)


class AnchorTarget(mxop.CustomOp):
    """RPN targets (reference ``example/rcnn`` AnchorTarget layer, run
    as a custom op): fg/bg labels by IoU vs the (single) gt box, bbox
    regression deltas for fg anchors.  One gt per image keeps the demo
    hermetic."""

    def forward(self, is_train, req, in_data, out_data, aux):
        gt = in_data[0]                       # (B, 1, 5) [cls,x1,y1,x2,y2]
        b = gt.shape[0]
        anchors = mx.nd.array(_base_anchors())            # (HWA, 4)
        n = anchors.shape[0]
        labels, targets, masks = [], [], []
        for i in range(b):                    # B is tiny and static
            g = mx.nd.tile(mx.nd.Reshape(gt[i, 0, 1:], shape=(1, 4)),
                           reps=(n, 1))
            iou = _iou_nd(anchors, g)
            fg = iou > 0.5
            bg = iou < 0.2
            lab = mx.nd.where(fg, mx.nd.ones((n,)),
                              mx.nd.where(bg, mx.nd.zeros((n,)),
                                          mx.nd.full((n,), -1.0)))
            aw = anchors[:, 2] - anchors[:, 0] + 1.0
            ah = anchors[:, 3] - anchors[:, 1] + 1.0
            acx = anchors[:, 0] + aw * 0.5
            acy = anchors[:, 1] + ah * 0.5
            gw = g[:, 2] - g[:, 0] + 1.0
            gh = g[:, 3] - g[:, 1] + 1.0
            gcx = g[:, 0] + gw * 0.5
            gcy = g[:, 1] + gh * 0.5
            dx = (gcx - acx) / aw
            dy = (gcy - acy) / ah
            dw = mx.nd.log(gw / aw)
            dh = mx.nd.log(gh / ah)
            tgt = mx.nd.stack(dx, dy, dw, dh, axis=1)      # (HWA, 4)
            m = mx.nd.Reshape(fg.astype("float32"), shape=(n, 1))
            labels.append(lab)
            targets.append(tgt * m)
            masks.append(mx.nd.tile(m, reps=(1, 4)))
        self.assign(out_data[0], req[0], mx.nd.stack(*labels, axis=0))
        self.assign(out_data[1], req[1], mx.nd.stack(*targets, axis=0))
        self.assign(out_data[2], req[2], mx.nd.stack(*masks, axis=0))

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        self.assign(in_grad[0], req[0],
                    mx.nd.zeros_like(in_data[0]))


@mxop.register("rcnn_anchor_target")
class AnchorTargetProp(mxop.CustomOpProp):
    def __init__(self):
        super().__init__(need_top_grad=False)

    def list_arguments(self):
        return ["gt"]

    def list_outputs(self):
        return ["label", "bbox_target", "bbox_mask"]

    def infer_shape(self, in_shape):
        b = in_shape[0][0]
        n = FM * FM * A
        return [in_shape[0]], [(b, n), (b, n, 4), (b, n, 4)], []

    def create_operator(self, ctx, shapes, dtypes):
        return AnchorTarget()


class ProposalTarget(mxop.CustomOp):
    """Per-ROI class targets (reference proposal_target custom op):
    gt class + 1 when IoU > 0.5, else background 0."""

    def forward(self, is_train, req, in_data, out_data, aux):
        rois = in_data[0]                     # (B*P, 5) [bidx,x1,y1,x2,y2]
        gt = in_data[1]                       # (B, 1, 5)
        bidx = rois[:, 0].astype("int32")
        g = mx.nd.take(mx.nd.Reshape(gt, shape=(-3, 0)), bidx)  # (BP, 5)
        iou = _iou_nd(rois[:, 1:], g[:, 1:])
        lab = mx.nd.where(iou > 0.5, g[:, 0] + 1.0,
                          mx.nd.zeros_like(iou))
        self.assign(out_data[0], req[0], lab)

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        self.assign(in_grad[0], req[0], mx.nd.zeros_like(in_data[0]))
        self.assign(in_grad[1], req[1], mx.nd.zeros_like(in_data[1]))


@mxop.register("rcnn_proposal_target")
class ProposalTargetProp(mxop.CustomOpProp):
    def __init__(self):
        super().__init__(need_top_grad=False)

    def list_arguments(self):
        return ["rois", "gt"]

    def list_outputs(self):
        return ["label"]

    def infer_shape(self, in_shape):
        return [in_shape[0], in_shape[1]], [(in_shape[0][0],)], []

    def create_operator(self, ctx, shapes, dtypes):
        return ProposalTarget()


def conv_block(data, num_filter, name, stride=(1, 1)):
    c = mx.sym.Convolution(data, kernel=(3, 3), pad=(1, 1),
                           stride=stride, num_filter=num_filter,
                           no_bias=True, name=name)
    bn = mx.sym.BatchNorm(c, fix_gamma=False, name=name + "_bn")
    return mx.sym.Activation(bn, act_type="relu")


def rcnn_symbol(batch_size):
    data = mx.sym.Variable("data")
    gt = mx.sym.Variable("label")             # (B, 1, 5) pixel coords
    im_info = mx.sym.Variable("im_info")      # (B, 3) [h, w, scale]

    body = conv_block(data, 16, "c1", stride=(2, 2))     # 32 -> 16
    body = conv_block(body, 32, "c2", stride=(2, 2))     # -> 8 (stride 4)

    # ---- RPN ----
    rpn = conv_block(body, 32, "rpn_conv")
    rpn_cls = mx.sym.Convolution(rpn, kernel=(1, 1), num_filter=2 * A,
                                 name="rpn_cls")          # (B, 2A, H, W)
    rpn_bbox = mx.sym.Convolution(rpn, kernel=(1, 1), num_filter=4 * A,
                                  name="rpn_bbox")        # (B, 4A, H, W)

    tgt = mx.sym.Custom(gt, op_type="rcnn_anchor_target", name="atgt")
    rpn_label, bb_target, bb_mask = tgt[0], tgt[1], tgt[2]

    # fg/bg softmax over the 2-way axis; layout (B, 2, A*H*W) with the
    # anchor axis enumerated (H, W, A) row-major to match AnchorTarget
    cls_for_loss = mx.sym.Reshape(
        mx.sym.transpose(mx.sym.Reshape(rpn_cls,
                                        shape=(0, 2, A, FM, FM)),
                         axes=(0, 1, 3, 4, 2)),
        shape=(0, 2, -1), name="rpn_cls_hwa")
    rpn_cls_loss = mx.sym.SoftmaxOutput(
        cls_for_loss, rpn_label, multi_output=True, use_ignore=True,
        ignore_label=-1, normalization="valid", name="rpn_cls_prob")

    bb_pred = mx.sym.Reshape(
        mx.sym.transpose(mx.sym.Reshape(rpn_bbox,
                                        shape=(0, A, 4, FM, FM)),
                         axes=(0, 3, 4, 1, 2)),
        shape=(0, -1, 4), name="rpn_bb_hwa")              # (B, HWA, 4)
    rpn_bbox_loss = mx.sym.MakeLoss(
        mx.sym.smooth_l1(bb_mask * (bb_pred - bb_target), scalar=3.0),
        grad_scale=1.0 / (FM * FM * A), name="rpn_bbox_loss")

    # ---- proposals (gradient-free, like the reference) ----
    rpn_prob = mx.sym.Reshape(
        mx.sym.softmax(mx.sym.Reshape(rpn_cls, shape=(0, 2, -1)),
                       axis=1),
        shape=(0, 2 * A, FM, FM), name="rpn_prob")
    rois = mx.sym.Proposal(
        mx.sym.BlockGrad(rpn_prob), mx.sym.BlockGrad(rpn_bbox),
        im_info, feature_stride=STRIDE, scales=SCALES, ratios=RATIOS,
        rpn_pre_nms_top_n=32, rpn_post_nms_top_n=POST_NMS,
        threshold=0.7, rpn_min_size=4, name="proposal")
    rois_flat = mx.sym.Reshape(rois, shape=(-3, 0), name="rois_flat")

    # ---- R-FCN head: position-sensitive score maps + PSROIPooling ----
    psroi_feat = mx.sym.Convolution(
        body, kernel=(1, 1),
        num_filter=(NUM_CLASSES + 1) * POOLED * POOLED, name="psconv")
    pooled = mx.sym.PSROIPooling(
        psroi_feat, mx.sym.BlockGrad(rois_flat),
        spatial_scale=1.0 / STRIDE, output_dim=NUM_CLASSES + 1,
        pooled_size=POOLED, group_size=POOLED, name="psroi")
    scores = mx.sym.Reshape(
        mx.sym.Pooling(pooled, global_pool=True, pool_type="avg",
                       kernel=(1, 1)),
        shape=(0, NUM_CLASSES + 1), name="roi_scores")

    roi_label = mx.sym.Custom(mx.sym.BlockGrad(rois_flat), gt,
                              op_type="rcnn_proposal_target",
                              name="ptgt")
    roi_cls_loss = mx.sym.SoftmaxOutput(
        scores, roi_label, normalization="valid", name="roi_cls_prob")

    return mx.sym.Group([rpn_cls_loss, rpn_bbox_loss, roi_cls_loss,
                         mx.sym.BlockGrad(rois),
                         mx.sym.BlockGrad(roi_label)])


def synthetic_batch(rs, n):
    imgs = np.zeros((n, 3, IMG, IMG), "float32")
    labels = np.zeros((n, 1, 5), "float32")
    for i in range(n):
        cls = int(rs.randint(NUM_CLASSES))
        w = int(rs.randint(8, 17))
        x0 = int(rs.randint(0, IMG - w))
        y0 = int(rs.randint(0, IMG - w))
        imgs[i, cls, y0:y0 + w, x0:x0 + w] = 1.0
        labels[i, 0] = [cls, x0, y0, x0 + w - 1, y0 + w - 1]
    return imgs, labels


def main(args):
    rs = np.random.RandomState(0)
    imgs, labels = synthetic_batch(rs, args.num_examples)
    im_info = np.tile(np.asarray([[IMG, IMG, 1.0]], "float32"),
                      (args.num_examples, 1))
    it = mx.io.NDArrayIter({"data": imgs, "im_info": im_info},
                           {"label": labels}, args.batch_size,
                           shuffle=True)

    sym = rcnn_symbol(args.batch_size)
    mod = mx.mod.Module(sym, context=mx.current_context(),
                        data_names=("data", "im_info"),
                        label_names=("label",))
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="adam",
                       optimizer_params={"learning_rate": args.lr})

    first = last = None
    for epoch in range(args.num_epochs):
        it.reset()
        tot_roi = acc_n = acc_c = 0.0
        nb = 0
        for batch in it:
            mod.forward(batch, is_train=True)
            _, _, roi_prob, rois, roi_label = mod.get_outputs()
            mod.backward()
            mod.update()
            p = roi_prob.asnumpy()
            rl = roi_label.asnumpy().astype("int64")
            picked = p[np.arange(p.shape[0]), rl]
            tot_roi += float(-np.log(np.maximum(picked, 1e-8)).mean())
            acc_c += float((p.argmax(axis=1) == rl).sum())
            acc_n += rl.shape[0]
            nb += 1
        roi_loss = tot_roi / nb
        roi_acc = acc_c / acc_n
        if first is None:
            first = roi_loss
        last = roi_loss
        logging.info("Epoch[%d] roi-loss=%.4f roi-acc=%.3f", epoch,
                     roi_loss, roi_acc)
    print("loss first->last: %.4f -> %.4f" % (first, last))
    print("final roi accuracy: %.3f" % roi_acc)
    if last < first and roi_acc > 0.6:
        print("RCNN TRAINS OK")
    else:
        print("RCNN DID NOT LEARN")
        return 1
    return 0


if __name__ == "__main__":
    p = argparse.ArgumentParser(description="train mini Faster R-CNN")
    p.add_argument("--num-epochs", type=int, default=8)
    p.add_argument("--num-examples", type=int, default=128)
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--lr", type=float, default=2e-3)
    sys.exit(main(p.parse_args()))
