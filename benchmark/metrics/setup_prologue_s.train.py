"""fit.bind + fit.init_params + fit.init_optimizer + fit.build_step, from the
recorder's totals: what fit() does before its first batch.
From the program's recorder through benchmark/spans.py; silent without it."""
from benchmark import spans


def read(run):
    return spans.setup_prologue_s(run)
