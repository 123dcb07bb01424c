"""Two-stage detector demo (reference: example/rcnn — Faster R-CNN).

A compact Faster-RCNN-style pipeline over synthetic data, end-to-end
through the framework's own detection ops:
  _contrib_Proposal (= MultiProposal)  -> RPN proposals with NMS
  ROIPooling                           -> fixed-size region features
  per-ROI classification + box head    -> trained with autograd
The RPN and head train jointly; proposals are treated as fixed ROIs for
the head's gradient (stop-gradient, like the reference's proposal op).

Run (CPU smoke):
  JAX_PLATFORMS=cpu python example/rcnn/train_rcnn.py --epochs 2
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.ndarray import invoke


class Backbone(gluon.HybridBlock):
    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.body = nn.HybridSequential(prefix="")
            for ch in (8, 16):
                self.body.add(nn.Conv2D(ch, 3, strides=2, padding=1,
                                        activation="relu"))

    def hybrid_forward(self, F, x):
        return self.body(x)


class RPN(gluon.HybridBlock):
    """1 anchor scale per position for the demo (A = num scales*ratios)."""

    def __init__(self, num_anchors, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.conv = nn.Conv2D(16, 3, padding=1, activation="relu")
            self.cls = nn.Conv2D(2 * num_anchors, 1)
            self.bbox = nn.Conv2D(4 * num_anchors, 1)

    def hybrid_forward(self, F, feat):
        t = self.conv(feat)
        return self.cls(t), self.bbox(t)


class RoiHead(gluon.HybridBlock):
    def __init__(self, num_classes, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.fc = nn.Dense(32, activation="relu")
            self.cls = nn.Dense(num_classes + 1)

    def hybrid_forward(self, F, pooled):
        return self.cls(self.fc(pooled.reshape((pooled.shape[0], -1))))


def synthetic_batch(rng, n, img):
    """Returns (images, image_class, boxes) — boxes normalized [0,1] for
    the shared VOCMApMetric."""
    x = rng.uniform(0, 0.1, (n, 3, img, img)).astype(np.float32)
    cls = np.zeros((n,), np.int64)
    boxes = np.zeros((n, 1, 5), np.float32)
    for i in range(n):
        c = rng.randint(0, 2)
        s = img // 2
        y0, x0 = rng.randint(0, img - s, 2)
        x[i, c, y0:y0 + s, x0:x0 + s] = 1.0
        cls[i] = c
        boxes[i, 0] = [c, x0 / img, y0 / img, (x0 + s) / img, (y0 + s) / img]
    return x, cls, boxes


def rpn_targets(boxes_np, img, fs, base_anchor):
    """Anchor-wise RPN targets (the reference example/rcnn AnchorLoader
    role): objectness = anchor center inside the gt box; bbox targets use
    the standard RCNN encoding matching the Proposal op's decode
    (contrib_ops.py _proposal: +1-pixel widths, exp size deltas).

    boxes_np: (N, 1, 5) [cls, box/img] normalized.  A = 1 anchor/position.
    Returns (obj (N,H,W), bbox_t (N,4,H,W), pos (N,H,W)) numpy arrays."""
    N = boxes_np.shape[0]
    H = W = img // fs
    aw = base_anchor[2] - base_anchor[0] + 1.0
    ah = base_anchor[3] - base_anchor[1] + 1.0
    gx, gy = np.meshgrid(np.arange(W), np.arange(H))
    acx = base_anchor[0] + 0.5 * (aw - 1.0) + gx * fs     # (H, W)
    acy = base_anchor[1] + 0.5 * (ah - 1.0) + gy * fs
    obj = np.zeros((N, H, W), np.float32)
    bbox_t = np.zeros((N, 4, H, W), np.float32)
    for i in range(N):
        x0, y0, x1, y1 = boxes_np[i, 0, 1:5] * img
        gw, gh = x1 - x0 + 1.0, y1 - y0 + 1.0
        gcx, gcy = x0 + 0.5 * (gw - 1.0), y0 + 0.5 * (gh - 1.0)
        inside = ((acx >= x0) & (acx <= x1) & (acy >= y0) & (acy <= y1))
        obj[i] = inside
        bbox_t[i, 0] = (gcx - acx) / aw
        bbox_t[i, 1] = (gcy - acy) / ah
        bbox_t[i, 2] = np.log(gw / aw)
        bbox_t[i, 3] = np.log(gh / ah)
    return obj, bbox_t, obj.copy()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--img-size", type=int, default=32)
    args = ap.parse_args()

    fs = 4                       # backbone stride (2 conv stride-2)
    scales = (2.0,)
    ratios = (1.0,)
    A = len(scales) * len(ratios)
    post_n = 4                   # proposals per image

    # deterministic init: Xavier draws from the numpy global RNG
    np.random.seed(0)
    backbone = Backbone()
    rpn = RPN(A)
    head = RoiHead(num_classes=2)
    for blk in (backbone, rpn, head):
        blk.initialize(mx.init.Xavier())
    all_params = {}
    for blk in (backbone, rpn, head):
        all_params.update(blk.collect_params())
    trainer = gluon.Trainer(all_params, "sgd",
                            {"learning_rate": 0.02, "momentum": 0.9})
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    rng = np.random.RandomState(0)

    im_info = nd.array(np.tile([args.img_size, args.img_size, 1.0],
                               (args.batch_size, 1)).astype(np.float32))
    from mxnet_tpu.ops.contrib_ops import _generate_anchors
    base_anchor = _generate_anchors(fs, ratios, scales)[0]

    for epoch in range(args.epochs):
        total = 0.0
        for it in range(8):
            x_np, cls_np, boxes_np = synthetic_batch(rng, args.batch_size,
                                                     args.img_size)
            obj_np, bbt_np, pos_np = rpn_targets(boxes_np, args.img_size,
                                                 fs, base_anchor)
            x = nd.array(x_np)
            obj_t = nd.array(obj_np)
            bbox_t = nd.array(bbt_np)
            pos = nd.array(pos_np[:, None])             # (N, 1, H, W)
            with autograd.record():
                feat = backbone(x)
                rpn_cls, rpn_bbox = rpn(feat)
                rois = invoke("_contrib_MultiProposal",
                              [nd.softmax(rpn_cls, axis=1), rpn_bbox,
                               im_info],
                              {"rpn_pre_nms_top_n": 12,
                               "rpn_post_nms_top_n": post_n,
                               "feature_stride": fs, "scales": scales,
                               "ratios": ratios, "rpn_min_size": 1,
                               "threshold": 0.7})
                pooled = invoke("ROIPooling", [feat, rois],
                                {"pooled_size": (3, 3),
                                 "spatial_scale": 1.0 / fs})
                logits = head(pooled)            # (N*post_n, C+1)
                # every proposal inherits its image's class label (one
                # object per synthetic image)
                roi_y = nd.array(np.repeat(cls_np, post_n)
                                 .astype(np.float32))
                l_head = ce(logits, roi_y).mean()
                # RPN supervision (reference AnchorLoader + rpn losses):
                # objectness CE over every anchor, smooth-L1 on positives
                logp = nd.log_softmax(nd.transpose(rpn_cls,
                                                   axes=(0, 2, 3, 1)),
                                      axis=-1)          # (N, H, W, 2)
                l_obj = -nd.pick(logp, obj_t, axis=-1).mean()
                n_pos = nd.maximum(pos.sum(), nd.array([1.0]))
                l_box = (invoke("smooth_l1", [(rpn_bbox - bbox_t) * pos],
                                {"scalar": 3.0})).sum() / n_pos
                loss = l_head + l_obj + l_box
            loss.backward()
            trainer.step(1)
            total += float(loss.asnumpy().sum())
        print("epoch %d loss %.4f" % (epoch, total / 8),
              flush=True)

    # the head should now classify proposals from held-out images
    x_np, cls_np, boxes_np = synthetic_batch(rng, 8, args.img_size)
    feat = backbone(nd.array(x_np))
    rpn_cls, rpn_bbox = rpn(feat)
    rois = invoke("_contrib_MultiProposal",
                  [nd.softmax(rpn_cls, axis=1), rpn_bbox,
                   nd.array(np.tile([args.img_size, args.img_size, 1.0],
                                    (8, 1)).astype(np.float32))],
                  {"rpn_pre_nms_top_n": 12, "rpn_post_nms_top_n": post_n,
                   "feature_stride": fs, "scales": scales, "ratios": ratios,
                   "rpn_min_size": 1, "threshold": 0.7})
    pooled = invoke("ROIPooling", [feat, rois],
                    {"pooled_size": (3, 3), "spatial_scale": 1.0 / fs})
    logits = head(pooled)
    pred = logits.asnumpy().argmax(1).reshape(8, post_n)
    votes = np.array([np.bincount(p, minlength=3).argmax() for p in pred])
    acc = float((votes == cls_np).mean())
    print("held-out proposal-vote accuracy: %.2f" % acc)

    # detection quality through the shared VOC mAP metric (reference
    # eval_metric.py, reused from example/ssd): each proposal becomes a
    # detection [cls, score, box/img]
    probs = nd.softmax(logits, axis=-1).asnumpy()       # (8*post_n, C+1)
    roi_np = rois.asnumpy().reshape(8, post_n, 5)       # [b, x0, y0, x1, y1]
    dets = np.zeros((8, post_n, 6), np.float32)
    dets[:, :, 0] = probs.argmax(-1).reshape(8, post_n)
    dets[:, :, 1] = probs.max(-1).reshape(8, post_n)
    dets[:, :, 2:6] = roi_np[:, :, 1:5] / args.img_size
    metric = mx.metric.VOCMApMetric(ovp_thresh=0.3)
    metric.update([nd.array(boxes_np)], [nd.array(dets)])
    print("proposal mAP@0.3: %.3f" % metric.get()[1])


if __name__ == "__main__":
    main()
