"""Percent of the traced window in which a collective (the halo
exchange's collective-permutes, the reduce's all-reduces) ran on a chip
with no other operation beside it, averaged over the chips
(``bench/trace.py`` ``exposed_collective_s``).  Moves ``solve_s``."""
from bench import trace


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * trace.exposed_collective_s(ctx.trace) \
        / ctx.trace.window_s
