"""The Mamba-2 mixer's convolution's share of its roofline, by its memory
bound: the bytes the convolutions of a step have to move over peak bytes/s,
divided by the device ms a step of the scope ``ssm.conv`` (both passes, the
kernels or XLA's form and what XLA does around them, from the program's own
table through benchmark/scopes.py).

Required, a Mamba-2 layer over ``rows`` rows of ``H P + 2 G N`` channels
(``x``, ``B`` and ``C``), float32: forward ``x`` read and the output
written, backward the output's gradient and ``x`` read and ``x``'s gradient
written, 5 passes.  The 4 taps' multiply-accumulates are nothing beside them.
At 8,192 rows, 64 heads of 64 and 8 groups of 128: 1,006,632,960 B a layer.
A recomputed layer runs the forward pass a second time, which is the
program's choice and not required, so the share cannot pass 5/7 (71%) while
it does.

``rows`` is the traffic's ``batch`` x ``seq_len``; the layers are the held
ones whose letter of ``hybrid_override_pattern`` is ``M``.  Silent where the
configuration holds no Mamba-2 layer or where the scope is not found (no
table or no trace)."""
import importlib.util
import os

from benchmark import scopes

_spec = importlib.util.spec_from_file_location(
    "benchmark_metric_ssd_scan_roofline_train",
    os.path.join(os.path.dirname(__file__), "ssd_scan_roofline.train.py"))
_scan = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_scan)

SCOPES = ("ssm.conv",)
BYTES = 4
PASSES = 5


def required_bytes(config, traffic):
    """Of one layer's convolution over a step's rows, both passes."""
    channels = config["mamba_num_heads"] * config["mamba_head_dim"] \
        + 2 * config["n_groups"] * config["ssm_state_size"]
    return PASSES * traffic["batch"] * traffic["seq_len"] * channels * BYTES


def read(run):
    cell = run["cell"]
    if run["peaks"] is None or not _scan.ssm_layers(cell.config):
        return None
    ms = scopes.scopes_ms(run, SCOPES)
    if not ms:
        return None
    moved = required_bytes(cell.config, cell.traffic) \
        * _scan.ssm_layers(cell.config)
    return 100.0 * moved / run["peaks"]["hbm_bytes_per_s"] / (ms / 1e3)
