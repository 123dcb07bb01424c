"""The gated short convolution kernels' share of their roofline, by its
memory bound: the bytes a gated short convolution has to move in the traced
steps over peak bytes/s, divided by the device time of the kernels'
operations.

Required: forward 4 passes of ``B L x hidden`` float32 values a convolution
layer (three streams read, one written), backward 7 (the output's gradient
and the three streams read, three gradients written): 11 in all.  The taps'
multiply-accumulates (``K`` a value) are nothing beside them: 6,144 a row
against 44 KB.  A recomputed layer runs the forward kernel a second time,
which is the program's choice and not required, so the share cannot reach
100% while it does.

Found in ``device.ops`` by the name the program gives its ``pallas_call``s:
``short_conv_fwd`` and ``short_conv_bwd`` (the layers' count and the
reduction are ``causal_attn_roofline.train``'s).  Silent, never 0, where the
trace shows none (a program whose convolution is XLA's) and in a
configuration without ``layer_types``."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "benchmark_metric_causal_attn_roofline_train",
    os.path.join(os.path.dirname(__file__), "causal_attn_roofline.train.py"))
_causal = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_causal)

KERNELS = "short_conv_"
PASSES = 4 + 7


def required_bytes(config, traffic):
    """Of one trained sequence."""
    return PASSES * traffic["seq_len"] * config["hidden_size"] * 4 \
        * _causal.layers_of(config, "conv")


def read(run):
    trace, cell = run["trace"], run["cell"]
    if trace is None or run["peaks"] is None \
            or "layer_types" not in cell.config:
        return None
    seconds, steps = _causal.kernel_seconds(trace.devices[0], KERNELS)
    if not seconds:
        return None
    need = required_bytes(cell.config, cell.traffic) * steps \
        * cell.traffic["batch"]
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / seconds
