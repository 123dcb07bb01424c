"""Between-program device idle that lies inside step.dispatch (run_window: hyper
vectors, stacking, the CachedOp call), per whole step: the launch path exposed.
From the program's recorder through benchmark/spans.py; silent without it."""
from benchmark import spans


def read(run):
    return spans.dispatch_exposed_ms(run)
