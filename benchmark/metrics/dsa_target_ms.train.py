"""What the indexer's target costs a step: the device time, in ms, of the
operations named ``index_target`` (``ops/pallas_ops.py``
``head_mean_probabilities``: the attention's own distribution over each
query's picked keys, averaged over the query heads, which the indexer's loss
is taken against) inside whole runs of the step program, over those runs.

The kernel computes every query head's scores again over every causal tile:
attention work that ``dsa_kernel_roofline.train`` leaves out (it reads the
operations named ``attention_*`` alone) and that ``flops.py`` does not count
as required (the attention has had those scores once).  It runs once in a
layer's forward pass and once more when the layer is recomputed.  Silent,
never 0, where the trace shows no such operation (the XLA path has no kernel
of this name)."""
import importlib.util
import os

KERNEL = "index_target"

_spec = importlib.util.spec_from_file_location(
    "benchmark_metric_dsa_kernel_roofline_train",
    os.path.join(os.path.dirname(__file__), "dsa_kernel_roofline.train.py"))
_kernels = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_kernels)


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    seconds, steps = _kernels.kernel_seconds(trace.devices[0], KERNEL)
    return 1e3 * seconds / steps if seconds else None
