"""The share of the causal pairs that the indexer picks: the program's
counters ``dsa.pairs_selected`` over ``dsa.pairs_causal``, written when a
layer is traced (``min(t + 1, topk)`` keys for query ``t``, summed, over ``L
(L + 1) / 2``).  It is the most that a kernel which skipped every unpicked
pair could save of a dense causal attention's work; the tile-wise kernels
save none of it (they visit every causal tile and compute all its pairs).
Silent where the program has no such counters."""


def read(run):
    try:
        from mxnet_tpu import profiler
        totals = profiler.totals()
        selected = totals["dsa.pairs_selected"]["count"]
        causal = totals["dsa.pairs_causal"]["count"]
    except Exception:
        return None
    return 100.0 * selected / causal if causal else None
