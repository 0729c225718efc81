"""Percent of the traced window in which the device ran the farm's stage
executable (``FarmEngine._stage_impl`` in ``core/streaming.py``: the
prep, here the adaptive median detection, and the write of its result
into the staging ring).  None where the window ran no stage.  Moves
``frames_per_s``."""
from bench import trace

STAGE = "jit__stage_impl"


def read(ctx):
    if ctx.trace is None:
        return None
    if not any(n == STAGE for runs in ctx.trace.modules.values()
               for n, _, _ in runs):
        return None
    return 100.0 * trace.module_ops_s(ctx.trace, STAGE) / ctx.trace.window_s
