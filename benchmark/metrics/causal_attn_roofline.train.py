"""The causal attention kernels' share of their roofline, by its compute
bound: the FLOPs that causal attention requires in the traced steps over peak
FLOP/s, divided by the device time of the attention kernels' operations.

Required: the visible pairs alone, ``L (L + 1) / 2`` per sequence of ``L``
tokens, x query heads x head size x 2 products (scores, values) x 2 FLOPs x 3
passes (forward, and the gradients of the scores' and the values' operands) x
the layers whose operator is attention.  The diagonal tiles' masked pairs and
the backward kernel's recomputed scores are the program's choice and not
required work, so the share cannot reach 100% while they are there; at head
size 64 a product contracts over half of the matrix unit's depth.

Bytes (for the record; the kernels are bound by compute and by the vector
unit's work on a tile, not by memory): forward, q and the output once and
k, v once a query tile of ``block_q`` rows.

Found in ``device.ops`` by the name the profiler gives the kernels, which is
the name the program gives its ``pallas_call``s: ``attention_fwd`` and
``attention_bwd`` (the reduction is ``attn_kernel_roofline.train``'s
``kernel_seconds``).  Silent, never 0, where the trace shows none, and in a
configuration without ``layer_types``."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "benchmark_metric_attn_kernel_roofline_train",
    os.path.join(os.path.dirname(__file__), "attn_kernel_roofline.train.py"))
_kernels = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_kernels)
kernel_seconds = _kernels.kernel_seconds

KERNELS = "attention_"


def layers_of(config, kind):
    """How many of the layers held here have ``kind`` for their operator."""
    held = config.get("deployment", {}).get("layers") \
        or range(config["num_hidden_layers"])
    return sum(config["layer_types"][i] == kind for i in held)


def head_dim(config):
    return config.get("head_dim") \
        or config["hidden_size"] // config["num_attention_heads"]


def required_flops(config, traffic):
    """Of one trained sequence."""
    length = traffic["seq_len"]
    pairs = length * (length + 1) // 2
    macs = pairs * config["num_attention_heads"] * head_dim(config) * 2
    return 3 * 2 * macs * layers_of(config, "full_attention")


def forward_bytes(config, traffic, block_q=512):
    """Of one sequence's forward kernels, float32 operands."""
    length, d = traffic["seq_len"], head_dim(config)
    rows = 2 * config["num_attention_heads"] * length
    key_rows = 2 * config["num_attention_heads"] * (
        length * (length + block_q) // 2 // block_q)
    return 4 * d * (rows + key_rows) * layers_of(config, "full_attention")


def read(run):
    trace, cell = run["trace"], run["cell"]
    if trace is None or run["peaks"] is None \
            or "layer_types" not in cell.config:
        return None
    seconds, steps = kernel_seconds(trace.devices[0], KERNELS)
    if not seconds:
        return None
    need = required_flops(cell.config, cell.traffic) * steps \
        * cell.traffic["batch"]
    return 100.0 * need / run["peaks"]["flops_per_s"] / seconds
