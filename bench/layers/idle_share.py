"""device: share of the window in which no operation ran on the chip."""
from bench import trace


def read(r):
    return 100.0 * (1.0 - trace.busy_seconds(r.trace, r.window) / r.window_s)
