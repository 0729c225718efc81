"""Percent of the traced window in which the device ran operations other
than the Pallas sweep kernels (ghost refresh, reduce fold, loop control:
``core/executor.py``, ``core/frames.py``).  Moves ``solve_s``."""
from bench import trace

KERNELS = ("stencil2d_fused_framed", "stencil2d_multistep_framed")


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * trace.busy_outside_s(ctx.trace, KERNELS) \
        / ctx.trace.window_s
