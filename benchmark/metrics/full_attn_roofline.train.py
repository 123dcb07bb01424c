"""The full (causal) attention's share of its roofline in a model whose
attention layers differ, by its compute bound: the FLOPs that the layers
held here whose kind is ``full_attention`` require in a step over peak
FLOP/s, divided by the device ms a step of the scope ``attn.causal`` (both
kernels of such a layer and what feeds them, both passes, from the program's
own table through benchmark/scopes.py).

Required: the visible pairs alone, ``L (L + 1) / 2`` per sequence of ``L``
tokens, x query heads x head size x 2 products x 2 FLOPs x 3 passes x those
layers; the diagonal tiles' masked pairs and the backward kernel's
recomputed scores are not required work.  ``causal_attn_roofline.train``
reads the same kernels by their operations' names, which a model whose
other layers run the same two kernels under another mask cannot split; this
reads the scope.

Silent where the configuration has no ``sliding_window`` (a model whose
attention is of one kind has its share read by name), holds no full layer,
or where the scopes are (no table or no trace)."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "benchmark_metric_window_attn_roofline_train",
    os.path.join(os.path.dirname(__file__), "window_attn_roofline.train.py"))
_window = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_window)

from benchmark import scopes  # noqa: E402

SCOPES = ("attn.causal",)
KIND = "full_attention"


def required_flops(config, traffic):
    """Of one trained sequence's full layers."""
    length = traffic["seq_len"]
    macs = length * (length + 1) // 2 * config["num_attention_heads"] \
        * _window.head_dim(config) * 2
    return 3 * 2 * macs * _window.layers_of(config, KIND)


def forward_bytes(config, traffic, block=512):
    """Of one sequence's forward kernels, float32 operands: q and the output
    once, k and v once a query tile of ``block`` rows."""
    length, d = traffic["seq_len"], _window.head_dim(config)
    heads = config["num_attention_heads"]
    key_rows = length * (length + block) // 2 // block
    return 4 * d * heads * 2 * (length + key_rows) \
        * _window.layers_of(config, KIND)


def read(run):
    cell = run["cell"]
    if run["peaks"] is None or "sliding_window" not in cell.config \
            or not _window.layers_of(cell.config, KIND):
        return None
    ms = scopes.scopes_ms(run, SCOPES)
    if not ms:
        return None
    need = required_flops(cell.config, cell.traffic) * cell.traffic["batch"]
    return 100.0 * need / run["peaks"]["flops_per_s"] / (ms / 1e3)
