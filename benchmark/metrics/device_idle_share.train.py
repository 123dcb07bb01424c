"""1 - union of device op intervals over the traced window, on the chip
that idles most."""


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    span = trace.hi_ns - trace.lo_ns
    return max(100.0 * (1 - trace.busy_ns(d) / span) for d in trace.devices)
