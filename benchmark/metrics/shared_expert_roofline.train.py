"""The shared expert's share of its roofline, by its compute bound: the
FLOPs that the shared experts of the routed layers held here require in a
step over peak FLOP/s, divided by the device ms a step of the scope
``mlp.shared`` (both passes, from the program's own table through
benchmark/scopes.py).

Required: rows a step x 2 products (up, down) x ``hidden_size x
n_shared_experts x moe_shared_expert_intermediate_size`` x 2 FLOPs x 3
passes x the routed layers held here (``relu2_experts_roofline.train``'s
count from ``hybrid_override_pattern``).  The recomputed forward pass is not
required work.  Silent where the configuration has no shared expert, and
where the scopes are (no table or no trace)."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "benchmark_metric_relu2_experts_roofline_train",
    os.path.join(os.path.dirname(__file__),
                 "relu2_experts_roofline.train.py"))
_relu2 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_relu2)

from benchmark import scopes  # noqa: E402

SCOPES = ("mlp.shared",)


def required_flops(config, traffic):
    """Of one trained sequence's shared experts."""
    width = config.get("n_shared_experts", 0) \
        * config.get("moe_shared_expert_intermediate_size", 0)
    return 3 * 2 * traffic["seq_len"] * 2 * config["hidden_size"] * width \
        * _relu2.routed_layers(config)


def read(run):
    cell = run["cell"]
    if run["peaks"] is None or not required_flops(cell.config, cell.traffic):
        return None
    ms = scopes.scopes_ms(run, SCOPES)
    if not ms:
        return None
    need = required_flops(cell.config, cell.traffic) * cell.traffic["batch"]
    return 100.0 * need / run["peaks"]["flops_per_s"] / (ms / 1e3)
