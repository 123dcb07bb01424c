"""The sparse attention kernels' share of their roofline, by its compute
bound: the FLOPs that attention over the picked pairs requires in the traced
steps over peak FLOP/s, divided by the device time of the attention kernels'
operations.

Required: the picked pairs alone, ``k (k + 1) / 2 + (L - k) k`` per sequence
of ``L`` tokens with ``k = min(topk, L)`` keys a query (a query before ``k``
sees every key up to its own), x query heads x head_dim x 2 products (scores,
values) x 2 FLOPs x 3 passes (forward, and the gradients of the scores' and
the values' operands) x layers.  A kernel that visits whole tiles computes the
unpicked pairs of every visited tile too, and the backward kernel recomputes
the scores: the program's choice and not required work, so the share cannot
reach 100% while they are there.  **Outside it**: the kernel ``index_target``,
which computes every head's scores over the causal tiles once more for the
indexer's loss (forward and in the recomputed layer); that is attention work
too, and with its time in the denominator the share reads about half.
``dsa_target_ms.train`` reads that kernel.

Found in ``device.ops`` by the name the profiler gives the kernels, which is
the name the program gives its ``pallas_call``s: ``attention_fwd`` and
``attention_bwd``, the static masks' own kernels with the picked pairs as one
more operand.  Silent, never 0, where the trace shows none, and in a
configuration that has no ``sa_config``."""
from benchmark.trace import union_ns

KERNELS = "attention_"


def required_flops(config, traffic):
    """Of one trained sequence."""
    length = traffic["seq_len"]
    kept = min(config["sa_config"]["topk"], length)
    pairs = kept * (kept + 1) // 2 + (length - kept) * kept
    macs = pairs * config["num_attention_heads"] * config["head_dim"] * 2
    return 3 * 2 * macs * config["num_hidden_layers"]


def kernel_seconds(device, prefix):
    """(seconds in the operations whose name starts with ``prefix``, steps)
    inside whole runs of the step program."""
    steps = device.steps()
    if not steps:
        return 0.0, 0
    lo, hi = steps[0][0], steps[-1][1]
    busy = union_ns((max(s, lo), min(s + d, hi)) for name, s, d in device.ops
                    if name.startswith(prefix) and s + d > lo and s < hi)
    return busy / 1e9, len(steps)


def read(run):
    trace, cell = run["trace"], run["cell"]
    if trace is None or run["peaks"] is None \
            or "sa_config" not in cell.config:
        return None
    seconds, steps = kernel_seconds(trace.devices[0], KERNELS)
    if not seconds:
        return None
    need = required_flops(cell.config, cell.traffic) * steps \
        * cell.traffic["batch"]
    return 100.0 * need / run["peaks"]["flops_per_s"] / seconds
