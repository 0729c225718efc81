"""Percent of the traced window in which the device ran the mesh-wide
combine of the fused reduce (the program's scope
``repro.global_reduce``: ``collective_combine`` in ``core/reduce.py``,
the all-reduces and the NaN re-propagation), averaged over the chips.
None where the solve's executable has no such scope.  Moves
``solve_s``."""
from bench import scopes

SCOPE = "repro.global_reduce"


def read(ctx):
    if ctx.trace is None:
        return None
    names = scopes.solve_op_scopes()
    if not names or SCOPE not in names.values():
        return None
    return 100.0 * scopes.scope_s(ctx.trace, names, SCOPE) \
        / ctx.trace.window_s
