"""Wall seconds, in set-up, of the step program's ``cachedop.compile`` spans:
the backend's compile where the persistent cache missed (cold), the cache's
load and the executable's deserialization where it hit (warm).  The recorder
charges ``compile.cache_hits`` and ``compile.cache_misses`` to the span, and
the run's log says which it was.
From the program's recorder through benchmark/scopes.py; silent without the
span (the parent of PR 37)."""
from benchmark import scopes


def read(run):
    return scopes.setup_spans(run).get("cachedop.compile")
