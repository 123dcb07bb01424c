"""The state-space scan's share of its roofline, by the nearer of its two
bounds: the least time the scans of a step could take (the larger of their
required FLOPs over peak FLOP/s and their required bytes over peak bytes/s)
divided by the device ms a step of the scope ``ssm.scan`` (both kernels and
what XLA does around them, both passes, from the program's own table through
benchmark/scopes.py).

Required, a Mamba-2 layer over ``chunks`` chunks of ``Q`` = ``chunk_size``
rows, ``H`` heads of ``P``, ``G`` groups of ``N``: forward ``G Q^2 N``
(``C B^T``) + ``H (Q^2 P + 2 Q P N)`` (a chunk's rows from its own, from the
state at its start, and the state at its end) multiply-accumulates a chunk,
x 2 FLOPs x 3 passes; bytes, float32, forward ``x``, ``B``, ``C`` and the
steps read, ``y`` and each chunk's starting state written, backward those
read again with ``y``'s gradient, and the gradients of ``x``, ``B``, ``C``
and the steps written.  At 8,192 rows, 64 heads of 64, 8 groups of 128: 84
GFLOP and 1.15 GB a layer, so the bound is memory's.  A recomputed layer's
forward kernel is not required work.

``chunks`` is the program's counter ``ssm.chunks`` (a layer's scan, the
batch's chunks, written when the layer is traced); the layers are the held
ones whose letter of ``hybrid_override_pattern`` is ``M``.  Silent where the
configuration holds no Mamba-2 layer, where the program has no such counter,
or where the scopes are (no table or no trace)."""
from benchmark import scopes

SCOPES = ("ssm.scan",)
BYTES = 4


def ssm_layers(config):
    """How many of the layers held here are Mamba-2 layers."""
    held = config.get("deployment", {}).get("layers") \
        or range(config["num_hidden_layers"])
    pattern = config.get("hybrid_override_pattern", "")
    return sum(pattern[i:i + 1] == "M" for i in held)


def required(config, chunks):
    """(FLOPs, bytes) of one layer's scan over ``chunks`` chunks, both
    passes."""
    H, P = config["mamba_num_heads"], config["mamba_head_dim"]
    G, N, Q = config["n_groups"], config["ssm_state_size"], \
        config["chunk_size"]
    macs = chunks * (G * Q * Q * N + H * (Q * Q * P + 2 * Q * P * N))
    rows = chunks * Q
    x, bc, steps = rows * H * P, 2 * rows * G * N, rows * H
    states = chunks * H * P * N
    forward = (x + bc + steps) + (x + states)
    backward = (x + bc + steps + x + states) + (x + bc + steps)
    return 3 * 2 * macs, BYTES * (forward + backward)


def read(run):
    cell = run["cell"]
    if run["peaks"] is None or not ssm_layers(cell.config):
        return None
    try:
        from mxnet_tpu import profiler
        chunks = profiler.totals()["ssm.chunks"]["max"]
    except Exception:
        return None
    ms = scopes.scopes_ms(run, SCOPES)
    if not ms or not chunks:
        return None
    flops, moved = required(cell.config, chunks)
    peaks = run["peaks"]
    least = max(flops / peaks["flops_per_s"], moved / peaks["hbm_bytes_per_s"])
    return 100.0 * least * ssm_layers(cell.config) / (ms / 1e3)
