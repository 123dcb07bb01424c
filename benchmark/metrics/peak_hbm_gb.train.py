"""Peak bytes on the fullest chip (the result line's memory_peak_bytes), GB."""


def read(run):
    return run["device"]["memory_peak_bytes"] / 1e9
