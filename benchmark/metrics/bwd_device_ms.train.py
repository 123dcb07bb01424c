"""Device ms a step of the step program's backward pass: phase ``bwd`` of the
program's own table, the recomputed forward pass of a layer that keeps no
activations included (``remat_device_ms.train`` says how much that is).
From the program's recorder (``profiler.program_ops``) through
benchmark/scopes.py; silent without it (the parent of PR 37)."""
from benchmark import scopes


def read(run):
    return scopes.phase_ms(run, "bwd")
