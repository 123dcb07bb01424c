"""Post-training int8 quantization (reference: example/quantization/
imagenet_gen_qsym.py + imagenet_inference.py — quantize a trained FP32
model with calibration and compare inference accuracy).

Zero-egress version: train a small symbolic convnet on synthetic
channel-coded classes through the Module API, then

  1. quantize_model(...)            — graph rewrite to _contrib_quantized_*
  2. calibration (minmax / entropy) — activation ranges from sample batches
  3. int8 inference                 — accuracy + fp32-agreement report

Run (CPU smoke):  JAX_PLATFORMS=cpu python example/quantization/quantize_infer.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.contrib import quantization as q

NUM_CLASSES = 4
IMG = 16


def synthetic_batch(rng, n):
    """Class = which quadrant of channel-0 carries the bright square."""
    x = rng.uniform(0, 0.2, (n, 3, IMG, IMG)).astype(np.float32)
    y = rng.randint(0, NUM_CLASSES, n)
    half = IMG // 2
    for i, c in enumerate(y):
        r, col = divmod(int(c), 2)
        x[i, 0, r * half:(r + 1) * half, col * half:(col + 1) * half] += 0.8
    return x, y.astype(np.float32)


def build_net():
    data = mx.sym.var("data")
    h = mx.sym.Convolution(data, num_filter=8, kernel=(3, 3), stride=(2, 2),
                           pad=(1, 1), name="conv1")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.Convolution(h, num_filter=16, kernel=(3, 3), stride=(2, 2),
                           pad=(1, 1), name="conv2")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=NUM_CLASSES, name="fc1")
    return mx.sym.SoftmaxOutput(h, name="softmax")


def evaluate(run_fp, run_q, batches):
    """One forward per engine per batch: accuracy for both plus top-1
    agreement from the cached predictions."""
    fp_ok = q_ok = same = total = 0
    for x, y in batches:
        fp_pred = run_fp(x).argmax(1)
        q_pred = run_q(x).argmax(1)
        fp_ok += (fp_pred == y).sum()
        q_ok += (q_pred == y).sum()
        same += (fp_pred == q_pred).sum()
        total += len(y)
    return fp_ok / total, q_ok / total, same / total


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--calib-mode", default="entropy",
                    choices=["minmax", "entropy", "none"])
    ap.add_argument("--calib-batches", type=int, default=4)
    args = ap.parse_args()

    np.random.seed(0)
    mx.random.seed(0)  # deterministic init (framework stream, r5)
    rng = np.random.RandomState(0)
    net = build_net()
    xs, ys = zip(*(synthetic_batch(rng, args.batch_size) for _ in range(24)))
    train_iter = mx.io.NDArrayIter(np.concatenate(xs), np.concatenate(ys),
                                   args.batch_size, shuffle=True)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(train_iter, num_epoch=args.epochs,
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            eval_metric="acc")
    arg_params, aux_params = mod.get_params()

    held = [synthetic_batch(np.random.RandomState(100 + i), 64)
            for i in range(4)]

    fp_exe = net.simple_bind(mx.cpu(), data=(64, 3, IMG, IMG),
                             grad_req="null")
    fp_exe.copy_params_from(arg_params, aux_params)

    def run_fp(x):
        return fp_exe.forward(is_train=False,
                              data=nd.array(x))[0].asnumpy()

    if args.calib_mode == "none":
        calib = None
    else:
        cx, cy = zip(*(synthetic_batch(rng, args.batch_size)
                       for _ in range(args.calib_batches)))
        calib = mx.io.NDArrayIter(np.concatenate(cx), np.concatenate(cy),
                                  args.batch_size)
    qsym, qargs, qaux = q.quantize_model(
        net, arg_params, aux_params, calib_data=calib,
        calib_mode=args.calib_mode)
    q_exe = qsym.simple_bind(mx.cpu(), data=(64, 3, IMG, IMG),
                             grad_req="null")
    q_exe.copy_params_from(qargs, qaux)

    def run_q(x):
        return q_exe.forward(is_train=False,
                             data=nd.array(x))[0].asnumpy()

    fp_acc, q_acc, agree = evaluate(run_fp, run_q, held)
    print("fp32 accuracy: %.3f  int8 accuracy: %.3f  top-1 agreement: %.3f"
          % (fp_acc, q_acc, agree))


if __name__ == "__main__":
    main()
