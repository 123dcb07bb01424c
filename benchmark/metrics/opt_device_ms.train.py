"""Device ms a step of the optimizer's update: phase ``opt`` of the program's
own table.  An update that XLA fuses into a weight gradient's fusion is that
fusion's root's, so it shows here only where it runs on its own (PR 36: the
held experts' Adam fell out of such a fusion, 7.0 -> 19.0 ms).
From the program's recorder (``profiler.program_ops``) through
benchmark/scopes.py; silent without it (the parent of PR 37)."""
from benchmark import scopes


def read(run):
    return scopes.phase_ms(run, "opt")
