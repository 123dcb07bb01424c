"""Device ms a step of the expert layers, both passes: the scopes
``moe.route`` + ``moe.sort`` + ``moe.experts`` + ``moe.combine`` of the
program's own table (router, slot table and rows to slots, the grouped
products with the matrices' casts, slots back to rows).  Adam's update of the
experts is in phase ``opt`` and in no scope.
From the program's recorder (``profiler.program_ops``) through
benchmark/scopes.py; silent without it (the parent of PR 37)."""
from benchmark import scopes


def read(run):
    return scopes.scopes_ms(run, scopes.MOE_LAYER)
