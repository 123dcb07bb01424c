"""Neural style transfer by input optimization (reference:
example/neural-style/nstyle.py — VGG feature matching with content +
Gram-matrix style losses, optimizing the IMAGE, not the network).

Zero-egress version: the feature extractor is a model_zoo VGG11 `features`
prefix with fixed seeded weights (feature matching against a fixed random
conv basis still defines a meaningful optimization target; stage a
pretrained .params via ``--pretrained`` to use trained features).  The
demo exercises the one capability no other example does: gradients with
respect to the INPUT through a deep conv stack (``x.attach_grad()`` +
``autograd.record`` + manual update), with multi-layer taps and Gram
matrices.

Success is quantitative: the combined content+style loss must drop by a
large factor from the noise init.

Run (CPU smoke):  JAX_PLATFORMS=cpu python example/neural-style/nstyle.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd
from mxnet_tpu.gluon.model_zoo import vision

IMG = 64


def content_image():
    """A bright disk — coarse structure the content loss should keep."""
    yy, xx = np.mgrid[0:IMG, 0:IMG]
    disk = (((yy - 32) ** 2 + (xx - 32) ** 2) <= 14 ** 2)
    img = np.tile((0.1 + 0.8 * disk)[None], (3, 1, 1))
    return img[None].astype(np.float32)


def style_image():
    """Diagonal stripes — texture statistics the Gram loss should copy."""
    yy, xx = np.mgrid[0:IMG, 0:IMG]
    stripes = ((yy + xx) // 6) % 2
    img = np.stack([stripes, 1 - stripes, stripes], 0).astype(np.float32)
    return (0.15 + 0.7 * img)[None]


class FeatureTaps:
    """Run a VGG features prefix, returning activations at chosen taps
    (reference style_layers/content_layer selection)."""

    def __init__(self, depth=9, taps=(2, 5, 8), pretrained=None):
        np.random.seed(7)   # fixed feature basis (Xavier uses global RNG)
        if pretrained:
            net = vision.get_model("vgg11", pretrained=pretrained)
        else:
            net = vision.get_model("vgg11")
            net.initialize(mx.init.Xavier())
        self.blocks = list(net.features._children.values())[:depth]
        self.taps = set(taps)

    def __call__(self, x):
        feats = []
        for i, blk in enumerate(self.blocks):
            x = blk(x)
            if i in self.taps:
                feats.append(x)
        return feats


def gram(feat):
    N, C = feat.shape[0], feat.shape[1]
    f = feat.reshape((N, C, -1))
    return nd.batch_dot(f, nd.transpose(f, axes=(0, 2, 1))) / f.shape[2]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--style-weight", type=float, default=2.0)
    ap.add_argument("--pretrained", default=None,
                    help="optional staged vgg11 .params for trained features")
    args = ap.parse_args()

    taps = FeatureTaps(pretrained=args.pretrained)
    content = nd.array(content_image())
    style = nd.array(style_image())
    with autograd.pause():
        content_feats = [f.detach() for f in taps(content)]
        style_grams = [gram(f).detach() for f in taps(style)]

    rng = np.random.RandomState(0)
    x = nd.array(rng.uniform(0.2, 0.8, content.shape).astype(np.float32))
    x.attach_grad()
    velocity = nd.zeros(x.shape)

    def losses():
        feats = taps(x)
        c_loss = sum(((f - cf) ** 2).mean() for f, cf
                     in zip(feats, content_feats))
        s_loss = sum(((gram(f) - g) ** 2).mean() for f, g
                     in zip(feats, style_grams))
        return c_loss, s_loss

    first = None
    for step in range(args.steps):
        with autograd.record():
            c_loss, s_loss = losses()
            loss = c_loss + args.style_weight * s_loss
        loss.backward()
        val = float(loss.asnumpy().ravel()[0])
        if first is None:
            first = val
        # momentum update on the IMAGE, gradient-normalized like the
        # reference's lr scheduling keeps steps stable
        g = x.grad / (nd.abs(x.grad).mean() + 1e-8)
        velocity = 0.9 * velocity - args.lr * g
        with autograd.pause():
            x._set_data((x + velocity).clip(0.0, 1.0)._data)
        if step % 30 == 0:
            print("step %d loss %.5f (content %.5f style %.5f)"
                  % (step, val, float(c_loss.asnumpy().ravel()[0]),
                     float(s_loss.asnumpy().ravel()[0])), flush=True)

    c_loss, s_loss = losses()
    final = float((c_loss + args.style_weight * s_loss).asnumpy().ravel()[0])
    print("loss: %.5f -> %.5f (%.1fx reduction)"
          % (first, final, first / max(final, 1e-12)))


if __name__ == "__main__":
    main()
