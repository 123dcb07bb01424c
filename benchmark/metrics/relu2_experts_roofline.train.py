"""The relu² experts' grouped-product kernels' share of their roofline, by
its compute bound: the FLOPs that the routed experts require in the traced
steps over peak FLOP/s, divided by the device time of the kernels
``moe_experts_*`` (found as ``moe_experts_roofline.train`` finds them).

Required: the even load.  A routed layer sees ``seq_len`` rows a sample,
each picks ``num_experts_per_tok`` of ``deployment.num_experts_total``
experts, and this chip holds ``n_routed_experts`` of them: ``rows x k x held
/ E`` pairs a layer, x 2 products (up, down: a relu² expert has no gate) x
``moe_intermediate_size x hidden_size`` x 2 FLOPs x 3 passes x the routed
layers held here, the held ones whose letter of ``hybrid_override_pattern``
is ``E`` (not ``num_dense_layers``, which such a model lacks).  The table's
empty slots and the recomputed forward pass are not required work.  Silent
in a configuration without that pattern or without relu² experts, and where
the trace shows no such kernel."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "benchmark_metric_attn_kernel_roofline_train",
    os.path.join(os.path.dirname(__file__), "attn_kernel_roofline.train.py"))
_kernels = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_kernels)

KERNELS = "moe_experts_"


def routed_layers(config):
    """How many of the layers held here are routed layers."""
    held = config.get("deployment", {}).get("layers") \
        or range(config["num_hidden_layers"])
    pattern = config.get("hybrid_override_pattern", "")
    return sum(pattern[i:i + 1] == "E" for i in held)


def required_flops(config, traffic):
    """Of one trained sample's routed experts, at an even load."""
    pairs = traffic["seq_len"] * config["num_experts_per_tok"] \
        * config["n_routed_experts"] \
        / config["deployment"]["num_experts_total"]
    macs = pairs * 2 * config["moe_intermediate_size"] * config["hidden_size"]
    return 3 * 2 * macs * routed_layers(config)


def read(run):
    trace, cell = run["trace"], run["cell"]
    if trace is None or run["peaks"] is None \
            or cell.config.get("mlp_hidden_act") != "relu2" \
            or not routed_layers(cell.config):
        return None
    seconds, steps = _kernels.kernel_seconds(trace.devices[0], KERNELS)
    if not seconds:
        return None
    need = required_flops(cell.config, cell.traffic) * steps \
        * cell.traffic["batch"]
    return 100.0 * need / run["peaks"]["flops_per_s"] / seconds
