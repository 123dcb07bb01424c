"""FCN-xs semantic segmentation (reference: example/fcn-xs/symbol_fcnxs.py
+ fcn_xs.py — Long et al. 2015: a conv backbone scored at coarse stride,
upsampled with transposed convolutions, fused with finer-stride skip
scores, cropped to input size, trained with per-pixel multi-output
softmax).

Zero-egress version: the same FCN-16s-style architecture (two pooling
stages -> /4 score head -> 2x deconv -> fuse with /2 skip score -> 2x
deconv -> Crop -> SoftmaxOutput(multi_output)) on synthetic images
containing a filled rectangle (class 1) and a filled disk (class 2) over
noise background (class 0).  Exercises the symbolic path end-to-end:
Deconvolution, Crop (sized from a reference input, the reference's
crop-to-data idiom), skip fusion, and the multi-output softmax gradient.
Evaluation is mean IoU over the three classes, the metric the reference's
segmentation evaluation uses.

Run (CPU smoke):  JAX_PLATFORMS=cpu python example/fcn-xs/fcn_xs.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import nd, sym

SIDE = 24
NUM_CLASSES = 3  # background / rectangle / disk


def synthetic_batch(rng, batch):
    """Images with one random rectangle and one random disk; per-pixel
    labels.  Shapes may overlap — the disk is drawn last and wins."""
    x = rng.normal(0, 0.25, (batch, 1, SIDE, SIDE)).astype(np.float32)
    y = np.zeros((batch, SIDE, SIDE), dtype=np.float32)
    yy, xx = np.mgrid[0:SIDE, 0:SIDE]
    for i in range(batch):
        # rectangle (class 1), intensity +1
        h, w = rng.randint(5, 10, 2)
        r0, c0 = rng.randint(0, SIDE - h), rng.randint(0, SIDE - w)
        x[i, 0, r0:r0 + h, c0:c0 + w] += 1.0
        y[i, r0:r0 + h, c0:c0 + w] = 1
        # disk (class 2), intensity -1
        rad = rng.randint(3, 6)
        cy, cx = rng.randint(rad, SIDE - rad, 2)
        mask = (yy - cy) ** 2 + (xx - cx) ** 2 <= rad ** 2
        x[i, 0][mask] -= 1.0
        y[i][mask] = 2
    return x, y


def get_fcn16s(num_classes=NUM_CLASSES):
    """FCN-16s-style symbol: /4 score, 2x upsample, fuse with /2 skip
    score, 2x upsample to full resolution, crop to data, per-pixel
    softmax (reference symbol_fcnxs.py's score/bigscore/crop chain)."""
    data = sym.Variable("data")
    conv1 = sym.Convolution(data, name="conv1", kernel=(3, 3), pad=(1, 1),
                            num_filter=16)
    act1 = sym.Activation(conv1, act_type="relu")
    pool1 = sym.Pooling(act1, pool_type="max", kernel=(2, 2), stride=(2, 2))
    conv2 = sym.Convolution(pool1, name="conv2", kernel=(3, 3), pad=(1, 1),
                            num_filter=32)
    act2 = sym.Activation(conv2, act_type="relu")
    pool2 = sym.Pooling(act2, pool_type="max", kernel=(2, 2), stride=(2, 2))

    # coarse head at /4
    score4 = sym.Convolution(pool2, name="score4", kernel=(1, 1),
                             num_filter=num_classes)
    up2 = sym.Deconvolution(score4, name="up2", kernel=(4, 4), stride=(2, 2),
                            pad=(1, 1), num_filter=num_classes)
    # skip score at /2, fused (the 16s trick)
    score2 = sym.Convolution(pool1, name="score2", kernel=(1, 1),
                             num_filter=num_classes)
    fuse = up2 + sym.Crop(score2, up2, name="crop2")
    up1 = sym.Deconvolution(fuse, name="up1", kernel=(4, 4), stride=(2, 2),
                            pad=(1, 1), num_filter=num_classes)
    bigscore = sym.Crop(up1, data, name="bigscore")
    return sym.SoftmaxOutput(bigscore, name="softmax", multi_output=True,
                             normalization="valid")


def mean_iou(pred_cls, label):
    """Mean intersection-over-union over classes present in the labels."""
    ious = []
    for c in range(NUM_CLASSES):
        p, l = pred_cls == c, label == c
        union = np.logical_or(p, l).sum()
        if union:
            ious.append(np.logical_and(p, l).sum() / union)
    return float(np.mean(ious))


def evaluate(mod, rng, batch, batches=4):
    scores = []
    for _ in range(batches):
        x, y = synthetic_batch(rng, batch)
        mod.forward(mx.io.DataBatch(data=[nd.array(x)]), is_train=False)
        prob = mod.get_outputs()[0].asnumpy()  # (B, C, H, W)
        scores.append(mean_iou(prob.argmax(axis=1), y))
    return float(np.mean(scores))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--batch-size", type=int, default=16)
    parser.add_argument("--steps", type=int, default=120)
    parser.add_argument("--lr", type=float, default=0.3)
    args = parser.parse_args()

    rng = np.random.RandomState(7)
    mx.random.seed(1)  # deterministic init from the framework stream (r5)
    net = get_fcn16s()
    mod = mx.mod.Module(net,
                        label_names=("softmax_label",))
    mod.bind(data_shapes=[("data", (args.batch_size, 1, SIDE, SIDE))],
             label_shapes=[("softmax_label", (args.batch_size, SIDE, SIDE))])
    mod.init_params(mx.init.Xavier(magnitude=2.0))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": args.lr,
                                         "momentum": 0.9})

    iou_before = evaluate(mod, np.random.RandomState(99), args.batch_size)
    for step in range(args.steps):
        x, y = synthetic_batch(rng, args.batch_size)
        batch = mx.io.DataBatch(data=[nd.array(x)], label=[nd.array(y)])
        mod.forward(batch, is_train=True)
        mod.backward()
        mod.update()
        if step % 30 == 0:
            prob = mod.get_outputs()[0].asnumpy()
            print("step %d train mIoU %.3f"
                  % (step, mean_iou(prob.argmax(axis=1), y)))
    iou_after = evaluate(mod, np.random.RandomState(99), args.batch_size)
    print("mean IoU before %.3f after %.3f" % (iou_before, iou_after))
    return iou_before, iou_after


if __name__ == "__main__":
    before, after = main()
    if not (after > 0.55 and after > before + 0.2):
        sys.exit("FAIL: segmentation did not learn (%.3f -> %.3f)"
                 % (before, after))
    print("FCN_XS OK")
