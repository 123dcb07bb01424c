"""The attention kernels' share of their roofline, by its compute bound: the
FLOPs that attention requires in the traced steps over peak FLOP/s, divided
by the device time of the attention kernels' operations.

Required: the visible pairs alone, ``L * (L + block_length)`` per sequence of
``L`` clean tokens under the block-diffusion mask (a noised row sees its block
and the clean blocks before it, a clean row its block and those before), x
query heads x head_dim x 2 products (scores, values) x 2 FLOPs x 3 passes
(forward, and the gradients of the scores' and the values' operands) x
layers.  The recomputation of the forward pass in the backward pass and of the
scores in the backward kernels is the program's choice and not required work,
so the share cannot reach 100% while they are there.

Found in ``device.ops`` by the name the profiler gives the kernels, which is
the name the program gives its ``pallas_call``s: ``attention_fwd``,
``attention_bwd_dq``, ``attention_bwd_dkv``.  Silent, never 0, where the trace
shows none."""
from benchmark.trace import union_ns

KERNELS = "attention_"


def required_flops(config, traffic):
    """Of one trained sequence."""
    length = traffic["seq_len"]
    pairs = length * (length + config["block_length"])
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
    trace = run["trace"]
    if trace is None or run["peaks"] is None:
        return None
    seconds, steps = kernel_seconds(trace.devices[0], KERNELS)
    if not seconds:
        return None
    cell = run["cell"]
    need = required_flops(cell.config, cell.traffic) * steps \
        * cell.traffic["batch"]
    return 100.0 * need / run["peaks"]["flops_per_s"] / seconds
