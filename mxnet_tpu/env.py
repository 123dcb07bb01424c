"""Central registry of environment-variable knobs
(reference: docs/faq/env_var.md — the documented MXNET_* configuration
surface).

Every knob the framework reads is declared here with its type, default and
one-line description; ``mxnet_tpu.env.describe()`` prints the table and
``get(name)`` is the typed accessor used by the subsystems.  Reference
variables that configure components XLA now owns (engine thread pools,
memory pools, cuDNN autotune) are listed as "absorbed" so users migrating
from the reference can see where each knob went.
"""
from __future__ import annotations

import os

__all__ = ["VARIABLES", "ABSORBED", "get", "describe"]


class EnvVar:
    def __init__(self, name, type_, default, doc):
        self.name = name
        self.type = type_
        self.default = default
        self.doc = doc

    def read(self):
        raw = os.environ.get(self.name)
        if raw is None:
            return self.default
        if self.type is bool:
            return raw.strip().lower() not in ("0", "false", "no", "off", "")
        return self.type(raw)


_V = [
    # --- paths / data -----------------------------------------------------
    EnvVar("MXNET_HOME", str, os.path.join(os.path.expanduser("~"), ".mxnet"),
           "Root directory for datasets, model zoo downloads and embeddings."),
    EnvVar("MXNET_GLUON_REPO", str,
           "https://apache-mxnet.s3-accelerate.dualstack.amazonaws.com/",
           "Base URL for gluon model/dataset downloads (no egress here: "
           "stage files locally under MXNET_HOME instead)."),
    # --- distributed (reference DMLC_* launcher contract) -----------------
    EnvVar("DMLC_WORKER_ID", int, 0,
           "This worker's rank in dist kvstore (tools/launch.py sets it)."),
    EnvVar("DMLC_NUM_WORKER", int, 1,
           "Total number of dist kvstore workers."),
    EnvVar("DMLC_PS_ROOT_URI", str, None,
           "Coordinator address for the jax.distributed rendezvous."),
    EnvVar("DMLC_PS_ROOT_PORT", int, 9876,
           "Coordinator port for the jax.distributed rendezvous."),
    EnvVar("MX_KV_RANK", int, None,
           "Override for DMLC_WORKER_ID (takes precedence when set)."),
    EnvVar("MX_KV_NUM_WORKERS", int, None,
           "Override for DMLC_NUM_WORKER."),
    EnvVar("MX_KV_ROOT_URI", str, None,
           "Override for DMLC_PS_ROOT_URI."),
    EnvVar("MX_KV_ROOT_PORT", int, None,
           "Override for DMLC_PS_ROOT_PORT."),
    EnvVar("MX_KV_INIT_TIMEOUT", float, 120.0,
           "Seconds each worker waits in the dist-kvstore rendezvous before "
           "failing with a diagnosis (barrier health at init)."),
    # --- memory / recompute -----------------------------------------------
    EnvVar("MXNET_BACKWARD_DO_MIRROR", bool, False,
           "Recompute activations in backward instead of saving them "
           "(reference env_var.md:140-145 mirroring; lowers to jax.checkpoint "
           "on every hybridized CachedOp; per-block override: "
           "hybridize(remat=True))."),
    EnvVar("MXNET_REMAT_POLICY", str, "full",
           "jax.checkpoint_policies name selecting what remat still saves "
           "('full' = save nothing, recompute everything; e.g. "
           "'dots_saveable' keeps matmul outputs on-chip)."),
    # --- profiling / testing ----------------------------------------------
    EnvVar("MXNET_PROFILER_AUTOSTART", bool, False,
           "Start the jax.profiler trace at import (profiler.py)."),
    EnvVar("MXNET_TEST_SEED", int, None,
           "Fixed RNG seed for test reproduction (conftest logs it)."),
]

VARIABLES = {v.name: v for v in _V}

# Reference knobs whose jobs the XLA runtime absorbed — kept as a migration
# map (docs/faq/env_var.md rows with no TPU meaning).
ABSORBED = {
    "MXNET_ENGINE_TYPE": "XLA async dispatch replaces the dependency engine.",
    "MXNET_CPU_WORKER_NTHREADS": "XLA thread pools; tune XLA_FLAGS instead.",
    "MXNET_GPU_WORKER_NTHREADS": "No CUDA streams; XLA schedules the TPU.",
    "MXNET_EXEC_BULK_EXEC_INFERENCE": "Whole-graph jit always bulks.",
    "MXNET_EXEC_BULK_EXEC_TRAIN": "Whole-graph jit always bulks.",
    "MXNET_GPU_MEM_POOL_RESERVE": "XLA BFC allocator owns device memory.",
    "MXNET_CUDNN_AUTOTUNE_DEFAULT": "XLA autotuning; no cuDNN.",
    "MXNET_KVSTORE_BIGARRAY_BOUND": "One fused allreduce per step.",
    "OMP_NUM_THREADS": "Honored by XLA's CPU backend directly.",
}


def get(name):
    """Typed value of a registered knob (env override or default)."""
    return VARIABLES[name].read()


def get_first(*names):
    """First non-None value along an override chain (each name's own default
    already folds in via read()); None when the whole chain is unset.

    Expresses precedence rules like MX_KV_RANK > DMLC_WORKER_ID once, here,
    where they are documented."""
    for name in names:
        val = get(name)
        if val is not None:
            return val
    return None


def describe(file=None):
    """Print the knob table (the docs/faq/env_var.md analog)."""
    import sys
    out = file or sys.stdout
    out.write("%-28s %-8s %-22s %s\n" % ("variable", "type", "default", "doc"))
    for v in _V:
        out.write("%-28s %-8s %-22s %s\n"
                  % (v.name, v.type.__name__, str(v.default)[:22], v.doc))
    out.write("\nabsorbed by the XLA runtime:\n")
    for k, why in ABSORBED.items():
        out.write("  %-34s %s\n" % (k, why))
