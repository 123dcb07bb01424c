"""Device-busy time per step: the union of op intervals from one start of
the step program to the next (the step and the small programs the loop runs
between two steps), averaged over the whole steps in the window."""


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    device = trace.devices[0]
    periods = device.periods()
    if not periods:
        return None
    return sum(device.busy_in(periods)) / len(periods) / 1e6
