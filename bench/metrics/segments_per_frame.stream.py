"""Segments the farm dispatched in the window per frame it emitted
(``FarmEngine.stats["segments"]``): each segment is one chained
dispatch, one host read and one drain.  Reads no trace.  Moves
``frames_per_s``."""


def read(ctx):
    frames = ctx.counters.get("frames")
    if not frames:
        return None
    return ctx.counters["segments"] / frames
