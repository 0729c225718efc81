"""Percent of the traced window in which no operation ran on the device,
averaged over the cell's chips.  Moves ``frames_per_s``."""
from bench import trace


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - trace.busy_s(ctx.trace) / ctx.trace.window_s)
