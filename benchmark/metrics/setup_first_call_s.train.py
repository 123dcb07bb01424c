"""Wall time of the step program's cachedop.first_call in set-up: trace, lower,
compile or cache load.
From the program's recorder through benchmark/spans.py; silent without it."""
from benchmark import spans


def read(run):
    return spans.setup_first_call_s(run)
