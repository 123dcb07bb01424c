"""CPU time of the thread that runs fit() inside fit.step, per step of the traced
window: the step time under which the loop itself would set the pace.
From the program's recorder through benchmark/spans.py; silent without it."""
from benchmark import spans


def read(run):
    return spans.host_cpu_ms(run)
