"""Share of the between-program device idle that no child span of fit.step covers:
whether the program's spans explain the gaps.
From the program's recorder through benchmark/spans.py; silent without it."""
from benchmark import spans


def read(run):
    return spans.idle_unattributed_share(run)
