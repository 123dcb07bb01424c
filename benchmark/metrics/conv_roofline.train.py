"""The convolutions' share of their roofline, by its compute bound: the
convolution and dense FLOPs of the steps in the window (flops.py, from shapes)
over peak FLOP/s, divided by the device time of the operations that
trace.conv_class puts among the convolutions.  Silent where the trace shows
none: never 0.  For convolutional networks only (BENCHMARK.json lists its
cells): the TPU compiler roots a fusion of kind ``kOutput`` in a plain matrix
product too, and all the model's FLOPs are taken for the convolutions'."""
from benchmark import flops
from benchmark.trace import conv_class, union_ns


def read(run):
    trace = run["trace"]
    if trace is None or run["peaks"] is None:
        return None
    device = trace.devices[0]
    steps = device.steps()
    if not steps:
        return None
    lo, hi = steps[0][0], steps[-1][1]     # whole runs of the step program
    conv_ns = union_ns((max(s, lo), min(s + d, hi)) for name, s, d
                       in device.ops if conv_class(name) and s + d > lo
                       and s < hi)
    if not conv_ns:
        return None
    images = len(steps) * run["cell"].traffic["batch"]
    least_s = flops.train_flops_per_sample(run["cell"]) * images \
        / run["peaks"]["flops_per_s"]
    return 100.0 * least_s / (conv_ns / 1e9)
