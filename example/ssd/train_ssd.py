"""Single-shot detector training (reference: example/ssd/train.py +
symbol/symbol_builder.py:60-130 multi_layer_feature/multibox_layer).

A multi-scale SSD over a model_zoo backbone, end-to-end through the
framework's own detection ops:

  _contrib_MultiBoxPrior     -> per-scale anchors (growing sizes), concat
  _contrib_MultiBoxTarget    -> anchor/ground-truth assignment + loc targets
  _contrib_MultiBoxDetection -> decode + NMS at inference

The backbone's feature pyramid is tapped wherever the spatial size drops
(the reference's ``from_layers``), and extra stride-2 blocks extend the
pyramid when the backbone is too shallow (the reference's '' layers).
Detection quality is measured with ``mx.metric.VOCMApMetric`` (reference
example/ssd/evaluate/eval_metric.py) on a held-out synthetic set — the
script prints mAP before and after training.

Run (CPU smoke):
  JAX_PLATFORMS=cpu python example/ssd/train_ssd.py --epochs 2
Multi-device:
  XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
      python example/ssd/train_ssd.py --num-devices 4
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


def _downsample_block(channels):
    blk = nn.HybridSequential(prefix="")
    blk.add(nn.Conv2D(channels, 3, strides=2, padding=1))
    blk.add(nn.BatchNorm())
    blk.add(nn.Activation("relu"))
    return blk


class MultiScaleSSD(gluon.Block):
    """SSD head over a feature pyramid (reference symbol_builder.py:60-130).

    ``backbone``: 'tiny' (3 stride-2 conv blocks) or any model_zoo name —
    the zoo net's ``features`` become the trunk and are tapped at every
    spatial downsampling, keeping the deepest ``num_scales`` taps.  Each
    scale gets its own 3x3 cls/loc heads; anchor sizes grow with depth.
    """

    def __init__(self, num_classes, backbone="tiny", num_scales=3, **kwargs):
        super().__init__(**kwargs)
        self.num_classes = num_classes
        self.num_scales = num_scales
        # reference multibox_layer pattern: growing sizes + fixed ratios;
        # each scale pairs s_i with sqrt(s_i * s_{i+1}), the terminal size
        # extending past `hi` so the deepest pair stays distinct
        lo, hi = 0.25, 0.7
        step = (hi - lo) / max(num_scales - 1, 1)
        s = [lo + i * step for i in range(num_scales)]
        s.append(min(hi + step, 1.0))
        self.scale_sizes = [(s[i], float(np.sqrt(s[i] * s[i + 1])))
                            for i in range(num_scales)]
        self.scale_ratios = [(1.0, 2.0, 0.5)] * num_scales
        num_anchors = [len(s) + len(r) - 1
                       for s, r in zip(self.scale_sizes, self.scale_ratios)]
        with self.name_scope():
            if backbone == "tiny":
                trunk = nn.HybridSequential(prefix="backbone_")
                with trunk.name_scope():
                    for ch in (16, 32, 64):
                        trunk.add(nn.Conv2D(ch, 3, strides=2, padding=1))
                        trunk.add(nn.BatchNorm())
                        trunk.add(nn.Activation("relu"))
                self.trunk = trunk
            else:
                from mxnet_tpu.gluon.model_zoo import vision
                zoo = vision.get_model(backbone, classes=2)
                self.trunk = zoo.features   # __setattr__ registers the child
            # extra pyramid levels if the trunk is too shallow (ref: '' layers)
            self.extras = nn.HybridSequential(prefix="extra_")
            with self.extras.name_scope():
                for _ in range(num_scales):
                    self.extras.add(_downsample_block(64))
            self.cls_heads = nn.HybridSequential(prefix="cls_")
            self.loc_heads = nn.HybridSequential(prefix="loc_")
            with self.cls_heads.name_scope():
                for a in num_anchors:
                    self.cls_heads.add(
                        nn.Conv2D(a * (num_classes + 1), 3, padding=1))
            with self.loc_heads.name_scope():
                for a in num_anchors:
                    self.loc_heads.add(nn.Conv2D(a * 4, 3, padding=1))

    def _pyramid(self, x):
        """Trunk taps at every spatial downsample + extra blocks; returns
        the deepest ``num_scales`` feature maps, shallowest first."""
        outs = []
        for child in self.trunk._children.values():
            y = child(x)
            if len(y.shape) < 4 or y.shape[2] < 2:
                break  # pooled/flattened classifier tail: stop tapping
            x = y
            outs.append(x)
        # the LAST output at each distinct spatial size is that scale's tap
        taps, seen = [], set()
        for o in reversed(outs):
            if o.shape[2] not in seen:
                taps.append(o)
                seen.add(o.shape[2])
        taps.reverse()
        for blk in self.extras._children.values():
            if len(taps) >= self.num_scales or taps[-1].shape[2] <= 2:
                break
            taps.append(blk(taps[-1]))
        return taps[-self.num_scales:]

    def forward(self, x):
        """Returns (anchors (1,A,4), cls (N,A,C+1), loc (N,A*4)) with the
        per-scale outputs flattened and concatenated (ref multibox_layer)."""
        feats = self._pyramid(x)
        N = x.shape[0]
        anchors, cls_preds, loc_preds = [], [], []
        for i, feat in enumerate(feats):
            anchors.append(invoke("_contrib_MultiBoxPrior", [feat],
                                  {"sizes": self.scale_sizes[i],
                                   "ratios": self.scale_ratios[i]}))
            cls = self.cls_heads._children[str(i)](feat)
            loc = self.loc_heads._children[str(i)](feat)
            cls_preds.append(nd.transpose(cls, axes=(0, 2, 3, 1)).reshape(
                (N, -1, self.num_classes + 1)))
            loc_preds.append(nd.transpose(loc, axes=(0, 2, 3, 1)).reshape(
                (N, -1)))
        return (nd.concat(*anchors, dim=1),
                nd.concat(*cls_preds, dim=1),
                nd.concat(*loc_preds, dim=1))


def synthetic_batch(rng, batch_size, img_size, num_classes):
    """Images containing one bright square each; label = [cls, box]."""
    x = rng.uniform(0, 0.1, (batch_size, 3, img_size, img_size))
    labels = np.zeros((batch_size, 1, 5), np.float32)
    for i in range(batch_size):
        cls = rng.randint(0, num_classes)
        s = rng.randint(img_size // 4, img_size // 2)
        y0 = rng.randint(0, img_size - s)
        x0 = rng.randint(0, img_size - s)
        x[i, cls % 3, y0:y0 + s, x0:x0 + s] = 1.0
        labels[i, 0] = [cls, x0 / img_size, y0 / img_size,
                        (x0 + s) / img_size, (y0 + s) / img_size]
    return x.astype(np.float32), labels


def evaluate_map(net, rng, args, num_batches=4):
    """Held-out synthetic mAP via MultiBoxDetection + VOCMApMetric."""
    metric = mx.metric.VOCMApMetric(ovp_thresh=0.5)
    for _ in range(num_batches):
        x_np, lab_np = synthetic_batch(rng, args.batch_size, args.img_size,
                                       args.num_classes)
        anchors, cls_f, loc_f = net(nd.array(x_np))
        probs = nd.softmax(nd.transpose(cls_f, axes=(0, 2, 1)), axis=1)
        det = invoke("_contrib_MultiBoxDetection", [probs, loc_f, anchors],
                     {"nms_threshold": 0.45, "threshold": 0.01,
                      "nms_topk": 100})
        metric.update([nd.array(lab_np)], [det])
    return metric.get()[1]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--iters", type=int, default=24)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--img-size", type=int, default=64)
    ap.add_argument("--num-classes", type=int, default=3)
    ap.add_argument("--num-scales", type=int, default=3)
    ap.add_argument("--backbone", default="tiny",
                    help="'tiny' or a model_zoo name (e.g. mobilenet0.25)")
    ap.add_argument("--num-devices", type=int, default=1)
    ap.add_argument("--optimizer", default="adam",
                    help="adam converges much faster than sgd on the "
                         "mined multi-task loss")
    ap.add_argument("--lr", type=float, default=0.002)
    args = ap.parse_args()

    # deterministic init: Xavier draws from the numpy global RNG
    np.random.seed(0)
    ctxs = [mx.cpu(i) for i in range(args.num_devices)]
    net = MultiScaleSSD(args.num_classes, backbone=args.backbone,
                        num_scales=args.num_scales)
    net.initialize(mx.init.Xavier(), ctx=ctxs)
    # probe forward materializes deferred shapes; extra pyramid blocks the
    # backbone didn't need stay deferred and are excluded from training
    net(nd.zeros((1, 3, args.img_size, args.img_size), ctx=ctxs[0]))
    params = {name: p for name, p in net.collect_params().items()
              if not p._deferred_init}
    opt_args = ({"learning_rate": args.lr, "momentum": 0.9}
                if args.optimizer == "sgd" else {"learning_rate": args.lr})
    trainer = gluon.Trainer(params, args.optimizer, opt_args,
                            kvstore="tpu_sync" if args.num_devices > 1
                            else "device")
    cls_loss = gluon.loss.SoftmaxCrossEntropyLoss()
    rng = np.random.RandomState(0)

    map_before = evaluate_map(net, np.random.RandomState(99), args)
    print("mAP before training: %.4f" % map_before, flush=True)

    per_dev = args.batch_size // args.num_devices
    for epoch in range(args.epochs):
        total = 0.0
        for it in range(args.iters):
            x_np, lab_np = synthetic_batch(rng, args.batch_size,
                                           args.img_size, args.num_classes)
            xs = [nd.array(x_np[i * per_dev:(i + 1) * per_dev], ctx=c)
                  for i, c in enumerate(ctxs)]
            labs = [nd.array(lab_np[i * per_dev:(i + 1) * per_dev], ctx=c)
                    for i, c in enumerate(ctxs)]
            losses = []
            with autograd.record():
                for xb, lb in zip(xs, labs):
                    anchors, cls_f, loc_f = net(xb)
                    # hard-negative mining 3:1 + ignore_label, the reference
                    # trainer's config (symbol_builder.py: MultiBoxTarget
                    # negative_mining_ratio=3, SoftmaxOutput use_ignore,
                    # normalization='valid')
                    loc_t, loc_m, cls_t = invoke(
                        "_contrib_MultiBoxTarget",
                        [anchors, lb, nd.transpose(cls_f, axes=(0, 2, 1))],
                        {"negative_mining_ratio": 3.0,
                         "negative_mining_thresh": 0.5})
                    valid = (cls_t >= 0).astype("float32")
                    n_valid = nd.maximum(valid.sum(), nd.array([1.0]))
                    logp = nd.log_softmax(cls_f, axis=-1)     # (N, A, C+1)
                    per_anchor = -nd.pick(
                        logp, nd.maximum(cls_t, nd.zeros_like(cls_t)),
                        axis=-1)                              # (N, A)
                    l_cls = (per_anchor * valid).sum() / n_valid
                    n_pos = nd.maximum(loc_m.sum() / 4.0, nd.array([1.0]))
                    l_loc = invoke("smooth_l1", [loc_f * loc_m - loc_t],
                                   {"scalar": 1.0}).sum() / n_pos
                    losses.append((l_cls + l_loc) * per_dev)
            autograd.backward(losses)
            trainer.step(args.batch_size)
            total += sum(float(l.asnumpy().sum()) for l in losses)
        print("epoch %d loss %.4f" % (epoch, total / (args.iters
                                                      * args.batch_size)),
              flush=True)

    map_after = evaluate_map(net, np.random.RandomState(99), args)
    print("mAP after training: %.4f (was %.4f)" % (map_after, map_before),
          flush=True)

    # inference path: decode + NMS through MultiBoxDetection
    x_np, _ = synthetic_batch(rng, 2, args.img_size, args.num_classes)
    anchors, cls_f, loc_f = net(nd.array(x_np, ctx=ctxs[0]))
    probs = nd.softmax(nd.transpose(cls_f, axes=(0, 2, 1)), axis=1)
    det = invoke("_contrib_MultiBoxDetection", [probs, loc_f, anchors],
                 {"nms_threshold": 0.5, "threshold": 0.01})
    kept = int((det.asnumpy()[:, :, 0] >= 0).sum())
    print("detections kept after NMS: %d" % kept)
    assert kept > 0, "NMS swallowed every box"


if __name__ == "__main__":
    main()
