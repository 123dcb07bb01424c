"""Device ms a step of the step program's forward pass: the operations whose
instruction the program's own table puts in phase ``fwd``, the median over the
runs of the step in the traced window.
From the program's recorder (``profiler.program_ops``) through
benchmark/scopes.py; silent without it (the parent of PR 37)."""
from benchmark import scopes


def read(run):
    return scopes.phase_ms(run, "fwd")
