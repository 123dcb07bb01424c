"""Device ms a step of the part of the expert layers that is no product:
``moe.sort`` + ``moe.combine``, rows gathered to slots and slots gathered back
to rows with the sum over a row's picks, both passes (PERF.md §7's
"gathers").
From the program's recorder (``profiler.program_ops``) through
benchmark/scopes.py; silent without it (the parent of PR 37)."""
from benchmark import scopes


def read(run):
    return scopes.scopes_ms(run, scopes.MOE_MOVE)
