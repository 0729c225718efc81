"""Percent of the window's lane steps that were wasted: sweeps of a lane
slot with no live frame in it (``FarmEngine.stats``,
``wasted_lane_steps / lane_steps``, counted in ``core/streaming.py``).
Reads no trace.  Moves ``frames_per_s``."""


def read(ctx):
    steps = ctx.counters.get("lane_steps")
    if not steps:
        return None
    return 100.0 * ctx.counters["wasted_lane_steps"] / steps
