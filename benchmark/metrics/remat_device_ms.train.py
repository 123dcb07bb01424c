"""Device ms a step of the operations that recompute, in the backward pass, a
forward value that a layer under ``hybridize(remat=True)`` did not keep
(``rematted_computation`` on the instruction's path): what per-layer
recomputation costs in time for the memory it saves.  Silent where nothing is
recomputed.
From the program's recorder (``profiler.program_ops``) through
benchmark/scopes.py; silent without it (the parent of PR 37)."""
from benchmark import scopes


def read(run):
    return scopes.remat_ms(run)
