"""The whole step's share of the chip's peak: FLOPs the model requires per
sample (flops.py: an image, or one sequence of the cell's fixed length) x the
samples per second of this run (``train_images_per_s``), over chips x peak
bf16 FLOP/s (peaks.json).  From the host's clock and shapes; no trace needed,
and no family left out: whatever the reference computes through ``Ops``."""
from benchmark import flops


def read(run):
    if run["peaks"] is None:
        return None
    rate = run["end_to_end"]["train_images_per_s"]
    need = flops.train_flops_per_sample(run["cell"]) * rate
    return 100.0 * need / (run["device"]["count"] * run["peaks"]["flops_per_s"])
