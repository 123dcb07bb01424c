"""The whole step's share of the chip's peak: FLOPs the model requires per
image (flops.py) x the images per second of this run, over chips x peak bf16
FLOP/s (peaks.json).  From the host's clock and shapes; no trace needed."""
from benchmark import flops


def read(run):
    if run["peaks"] is None:
        return None
    rate = run["end_to_end"]["train_images_per_s"]
    need = flops.train_flops_per_image(run["cell"].config) * rate
    return 100.0 * need / (run["device"]["count"] * run["peaks"]["flops_per_s"])
