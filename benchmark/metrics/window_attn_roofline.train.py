"""The sliding-window attention's share of its roofline, by its compute
bound: the FLOPs that the sliding layers held here require in a step over
peak FLOP/s, divided by the device ms a step of the scope ``attn.window``
(both kernels of a sliding layer and what feeds them, both passes, from the
program's own table through benchmark/scopes.py).

Required: the visible pairs alone, ``sum_{i < L} min(i + 1, W)`` per
sequence of ``L`` tokens under a window of ``W`` = ``sliding_window`` keys, x
query heads x head size x 2 products (scores, values) x 2 FLOPs x 3 passes
(forward, and the gradients of the scores' and the values' operands) x the
layers held whose kind is ``sliding_attention``.  The masked pairs of the
band's edge tiles and the backward kernel's recomputed scores are the
program's choice and not required work, so the share cannot reach 100%
while they are there.

Bytes (for the record; the kernels are bound by compute): forward, q and the
output once, and k and v once for each visited tile's query rows.

Silent where the configuration holds no sliding layer, and where the scopes
are (no table or no trace)."""
from benchmark import scopes

SCOPES = ("attn.window",)
KIND = "sliding_attention"


def layers_of(config, kind):
    """How many of the layers held here are of ``kind``."""
    held = config.get("deployment", {}).get("layers") \
        or range(config["num_hidden_layers"])
    return sum(config["layer_types"][i] == kind for i in held)


def head_dim(config):
    return config.get("head_dim") \
        or config["hidden_size"] // config["num_attention_heads"]


def visible_pairs(length, window):
    """``sum_{i < length} min(i + 1, window)``."""
    inside = min(length, window)
    return inside * (inside + 1) // 2 + (length - inside) * window


def required_flops(config, traffic):
    """Of one trained sequence's sliding layers."""
    pairs = visible_pairs(traffic["seq_len"], config["sliding_window"])
    macs = pairs * config["num_attention_heads"] * head_dim(config) * 2
    return 3 * 2 * macs * layers_of(config, KIND)


def forward_bytes(config, traffic, block=512):
    """Of one sequence's forward kernels, float32 operands: q and the output
    once, k and v once for each of a query tile's visited key tiles (at
    most ``W / block + 1`` of them)."""
    length, d = traffic["seq_len"], head_dim(config)
    tiles = min(config["sliding_window"] // block + 1, length // block)
    heads = config["num_attention_heads"]
    return 4 * d * heads * length * (2 + 2 * tiles) * layers_of(config, KIND)


def read(run):
    cell = run["cell"]
    if run["peaks"] is None or "sliding_window" not in cell.config \
            or "layer_types" not in cell.config \
            or not layers_of(cell.config, KIND):
        return None
    ms = scopes.scopes_ms(run, SCOPES)
    if not ms:
        return None
    need = required_flops(cell.config, cell.traffic) * cell.traffic["batch"]
    return 100.0 * need / run["peaks"]["flops_per_s"] / (ms / 1e3)
