"""The share of the computed expert rows that the router asked for, in the
newest step: the pairs routed to the experts held here (every
mixture-of-experts block keeps ``[pairs routed here, largest load]`` of its
newest step, which ``profiler.totals()`` fetches under ``moe.load.<block>``),
summed over the layers, over ``moe.rows x moe.experts_held`` a layer (the rows
a routed layer sees and the experts it holds, written when the layer is
traced): the dropless layer computes every held expert for every row, and a
load-following layer would keep this share of those products.  6.25% is an
even load of 4 of 64 experts a token.  A block with no pair on record (one
that never ran a training step: the comparison builds such a one) is left
out.  Silent where the program has no such record."""


def routed_share(loads, rows, held):
    """``loads``: [(pairs routed here, largest load)] a layer."""
    return 100.0 * sum(pairs for pairs, _ in loads) / (len(loads) * rows
                                                       * held)


def read(run):
    try:
        from mxnet_tpu import profiler
        totals = profiler.totals()
        rows = totals["moe.rows"]["max"]
        held = totals["moe.experts_held"]["max"]
        loads = [(v["count"], v["max"]) for k, v in sorted(totals.items())
                 if k.startswith("moe.load.") and v["count"] > 0]
    except Exception:
        return None
    if not loads or not rows or not held:
        return None
    return routed_share(loads, rows, held)
