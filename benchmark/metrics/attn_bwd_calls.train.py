"""How often a backward attention kernel runs in a step: the operations whose
name starts with ``attention_bwd`` (the names the program gives those
``pallas_call``s) inside each run of the step program, the median over the
runs: ``attn_fwd_calls.train``'s count, of the other kernels.

One call a decoder layer is required: a kernel that visits every tile once
and takes the query, key and value gradients from that visit.  Twice the
layers says the backward walks the tiles twice (``attention_bwd_dq`` and
``attention_bwd_dkv``), recomputing each tile's scores and exponentials for
the second walk.  Silent, never 0, where the trace shows none."""
import importlib.util
import os

KERNELS = "attention_bwd"

_spec = importlib.util.spec_from_file_location(
    "benchmark_metric_attn_fwd_calls_train",
    os.path.join(os.path.dirname(__file__), "attn_fwd_calls.train.py"))
_fwd = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_fwd)


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    return _fwd.calls_per_step(trace.devices[0], KERNELS)
