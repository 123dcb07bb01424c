"""The dense gated feed-forward's share of its roofline, by its compute
bound: the FLOPs that the dense layers held here require in a step over peak
FLOP/s, divided by the device ms a step of the scope ``mlp.dense`` (both
passes, from the program's own table through benchmark/scopes.py).

Required: the layers held (``deployment.layers``, else all) below
``num_dense_layers``, x rows a step (twice the sequence under block
diffusion) x 3 products (gate, up, down) x ``hidden_size x
intermediate_size`` x 2 FLOPs x 3 passes (forward, and the gradients of both
operands).  Adam's update, where the compiler fuses it into a weight
gradient, runs in the scope and is counted in its time; the recomputed
forward pass is not required work.  Silent where the configuration holds no
dense layer, and where the scopes are (no table or no trace)."""
from benchmark import scopes

SCOPES = ("mlp.dense",)


def dense_layers(config):
    """How many of the layers held here have a dense feed-forward."""
    held = config.get("deployment", {}).get("layers") \
        or range(config["num_hidden_layers"])
    return sum(i < config.get("num_dense_layers", 0) for i in held)


def required_flops(config, traffic):
    """Of one trained sample's dense feed-forwards."""
    rows = traffic["seq_len"] * (
        2 if traffic.get("generator") == "block_diffusion" else 1)
    return 3 * 2 * rows * 3 * config["hidden_size"] \
        * config["intermediate_size"] * dense_layers(config)


def read(run):
    cell = run["cell"]
    if run["peaks"] is None or "intermediate_size" not in cell.config \
            or not dense_layers(cell.config):
        return None
    ms = scopes.scopes_ms(run, SCOPES)
    if not ms:
        return None
    need = required_flops(cell.config, cell.traffic) * cell.traffic["batch"]
    return 100.0 * need / run["peaks"]["flops_per_s"] / (ms / 1e3)
