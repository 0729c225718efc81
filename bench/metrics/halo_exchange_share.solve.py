"""Percent of the traced window in which the device ran the 1:n halo
exchange (the program's scope ``repro.halo_exchange``:
``_refresh_axis_sharded`` in ``core/frames.py``, the edge strips, their
ppermutes and the writes into the ghost ring), averaged over the chips.
None where the solve's executable has no such scope.  Moves
``solve_s``."""
from bench import scopes

SCOPE = "repro.halo_exchange"


def read(ctx):
    if ctx.trace is None:
        return None
    names = scopes.solve_op_scopes()
    if not names or SCOPE not in names.values():
        return None
    return 100.0 * scopes.scope_s(ctx.trace, names, SCOPE) \
        / ctx.trace.window_s
