"""The held experts' grouped-product kernels' share of their roofline, by its
compute bound: the FLOPs that the routed experts require in the traced steps
over peak FLOP/s, divided by the device time of the kernels.

Required: the even load that ``flops.py`` counts.  A routed layer sees
``rows`` rows a sample (twice the sequence under block diffusion: noised and
clean), each picks ``num_experts_per_tok`` of ``deployment.num_experts_total``
experts, and this chip holds ``num_experts`` of them: ``rows x k x held / E``
pairs a layer, x 3 products (gate, up, down) x ``moe_intermediate_size x
hidden_size`` x 2 FLOPs x 3 passes (forward, and the gradients of both
operands) x the routed layers held here.  A layer that computes every slot of
a table sized for any routing (``rows x min(k, held)`` slots) cannot pass
``held / E x k / min(k, held)`` of it, 12.5% in all three decoder cells, and
its recomputed forward pass is not required work: the share says how much a
layer that skipped the empty tiles would have left to gain.

Found in ``device.ops`` by the prefix the program gives its ``pallas_call``s,
``moe_`` (``moe_experts_hidden``, ``moe_experts_down``, ``moe_experts_bwd``,
``moe_experts_wgrad``); the reduction is ``attn_kernel_roofline.train``'s
``kernel_seconds``.  Silent, never 0, where the trace shows no such kernel
(a program whose expert products are plain XLA fusions: the parent) and in a
configuration without routed experts."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "benchmark_metric_attn_kernel_roofline_train",
    os.path.join(os.path.dirname(__file__), "attn_kernel_roofline.train.py"))
_kernels = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_kernels)
kernel_seconds = _kernels.kernel_seconds

KERNELS = "moe_"


def routed_layers(config):
    """How many of the layers held here have routed experts."""
    held = config.get("deployment", {}).get("layers") \
        or range(config["num_hidden_layers"])
    return sum(i >= config.get("num_dense_layers", 0) for i in held)


def required_flops(config, traffic):
    """Of one trained sample's routed experts, at an even load."""
    rows = traffic["seq_len"] * (
        2 if traffic.get("generator") == "block_diffusion" else 1)
    pairs = rows * config["num_experts_per_tok"] * config["num_experts"] \
        / config["deployment"]["num_experts_total"]
    macs = pairs * 3 * config["moe_intermediate_size"] * config["hidden_size"]
    return 3 * 2 * macs * routed_layers(config)


def read(run):
    trace, cell = run["trace"], run["cell"]
    if trace is None or run["peaks"] is None \
            or "moe_intermediate_size" not in cell.config:
        return None
    seconds, steps = kernel_seconds(trace.devices[0], KERNELS)
    if not seconds:
        return None
    need = required_flops(cell.config, cell.traffic) * steps \
        * cell.traffic["batch"]
    return 100.0 * need / run["peaks"]["flops_per_s"] / seconds
