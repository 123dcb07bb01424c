"""How unevenly the router loads the experts held here, in the newest step:
the largest held expert's load over the mean load of the held experts, worst
layer over the layers' mean.  From the program's record: every
mixture-of-experts block keeps ``[pairs routed here, largest load]`` of its
newest step as state on the device, which ``profiler.totals()`` fetches when
asked (``moe.load.<block>``: ``count`` and ``max``); ``moe.experts_held``'s
``max`` is the experts a layer holds.  1 is even.  A block with no pair on
record (one that never ran a training step: the comparison builds such a one)
is left out.  Silent where the program has no such record."""


def expert_loads(totals):
    """[(pairs routed here, largest held expert's load)] of every expert
    layer on record in ``profiler.totals()``: the newest step's."""
    return [(v["count"], v["max"]) for k, v in sorted(totals.items())
            if k.startswith("moe.load.") and v["count"] > 0]


def read(run):
    try:
        from mxnet_tpu import profiler
        totals = profiler.totals()
        held = totals["moe.experts_held"]["max"]
        found = expert_loads(totals)
    except Exception:
        return None
    pairs = sum(p for p, _ in found)
    if not found or not held or not pairs:
        return None
    mean_load = pairs / len(found) / held
    return max(largest for _, largest in found) / mean_load
