"""The share of the expert layer's computed slots that hold a pair the router
asked for, in the newest step: the pairs routed to the experts held here
(every mixture-of-experts block keeps ``[pairs routed here, largest load]`` of
its newest step, which ``profiler.totals()`` fetches under
``moe.load.<block>``), summed over the layers, over the slots of the table
that a layer's grouped products run over (``moe.slots``, written beside
``moe.rows`` when the layer is traced: ``rows x min(k, held)`` and a tile of
alignment an expert, whatever the routing).  Every slot is computed every
step, an empty one with weight 0, so this is the share of the mechanism's
work that was asked for: 12.5% at an even load of 8 of 128 experts a token
with 16 held (or 4 of 64 with 8), less the alignment; a layer that skipped
the empty tiles would read near 100%.  A block with no pair on record (one
that never ran a training step: the comparison builds such a one) is left
out.  Silent where the program has no such counter (a layer with no slot
table: the parent)."""


def fill_share(loads, slots):
    """``loads``: [(pairs routed here, largest load)] a layer."""
    return 100.0 * sum(pairs for pairs, _ in loads) / (len(loads) * slots)


def read(run):
    try:
        from mxnet_tpu import profiler
        totals = profiler.totals()
        slots = totals["moe.slots"]["max"]
        loads = [(v["count"], v["max"]) for k, v in sorted(totals.items())
                 if k.startswith("moe.load.") and v["count"] > 0]
    except Exception:
        return None
    if not loads or not slots:
        return None
    return fill_share(loads, slots)
