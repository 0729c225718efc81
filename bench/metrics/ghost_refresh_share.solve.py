"""Percent of the traced window in which the device re-asserted the
frame's ghost ring (the program's scope ``repro.ghost_refresh``:
``refresh_frame`` and its lane and sharded twins in ``core/frames.py``).
Moves ``solve_s``."""
from bench.scopes import scope_share


def read(ctx):
    return scope_share(ctx, "repro.ghost_refresh")
