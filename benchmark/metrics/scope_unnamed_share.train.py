"""The share (%) of the step's ``fwd`` + ``bwd`` device time whose instructions
carry no scope of the program: norms and residuals between blocks, and
whatever a later change adds without naming it.  What the per-scope numbers
cannot account for.
From the program's recorder (``profiler.program_ops``) through
benchmark/scopes.py; silent without it (the parent of PR 37)."""
from benchmark import scopes


def read(run):
    return scopes.unnamed_share(run)
