"""Wall seconds, in set-up, of the step program's ``cachedop.lower`` spans:
tracing the step in Python and lowering it to StableHLO, the part of a first
call that no compile cache shortens.
From the program's recorder through benchmark/scopes.py (the window's
``t_open`` and the recorder's clock are one: no trace is needed); silent
without the span (the parent of PR 37)."""
from benchmark import scopes


def read(run):
    return scopes.setup_spans(run).get("cachedop.lower")
