"""Mean idle time between the end of one step program and the start of the
next, on the first chip: the gap less whatever small programs ran in it."""


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    device = trace.devices[0]
    steps = device.steps()
    if len(steps) < 2:
        return None
    between = [(a[1], b[0]) for a, b in zip(steps, steps[1:]) if b[0] > a[1]]
    idle = sum(hi - lo for lo, hi in between) - sum(device.busy_in(between))
    return idle / (len(steps) - 1) / 1e6
