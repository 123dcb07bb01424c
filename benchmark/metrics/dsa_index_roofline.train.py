"""The selection kernel's share of its roofline, by its memory bound: the
bytes that an exact selection has to read in the traced steps over peak
bytes/s, divided by the device time of the kernel's operations.

Required: every index score once, ``L x L`` float32 per sequence and layer
(the kernel's operand is the whole square; the half above the diagonal is
read and masked), once a step: a recomputed layer keeps its rows' thresholds
and searches nothing in the backward pass.  The kernel reads ``64 x L`` scores
into VMEM and makes its 32 + log2(L) passes of compare and count there, so
its time is the vector unit's and the share says how far that is from
reading the scores once; the memory bound is the nearer of the two (the
kernel has no product for the MXU).

Found in ``device.ops`` by the name the program gives the ``pallas_call``,
``index_select`` (``ops/pallas_ops.py`` ``select_thresholds``).  Silent,
never 0, where the trace shows none (the XLA search is no kernel of its
own), and in a configuration that has no ``sa_config``."""
from benchmark.trace import union_ns

KERNEL = "index_select"


def required_bytes(config, traffic):
    """Of one trained sequence."""
    return traffic["seq_len"] ** 2 * 4 * config["num_hidden_layers"]


def read(run):
    trace, cell = run["trace"], run["cell"]
    if trace is None or run["peaks"] is None \
            or "sa_config" not in cell.config:
        return None
    device = trace.devices[0]
    steps = device.steps()
    if not steps:
        return None
    lo, hi = steps[0][0], steps[-1][1]
    busy = union_ns((max(s, lo), min(s + d, hi)) for name, s, d in device.ops
                    if name.startswith(KERNEL) and s + d > lo and s < hi)
    if not busy:
        return None
    need = required_bytes(cell.config, cell.traffic) * len(steps) \
        * cell.traffic["batch"]
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / (busy / 1e9)
