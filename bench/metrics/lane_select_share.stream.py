"""Percent of the traced window in which the device ran the lane farm's
done-mask (the program's scope ``repro.done_mask``: ``lane_where`` of
``_lane_body`` in ``core/pattern.py``, which keeps a finished lane's
frame) inside the farm's chained dispatch executable
(``FarmEngine._chain_entry``).  Moves ``frames_per_s``."""
from bench import scopes, trace

CHAIN = "jit__chain_entry"


def read(ctx):
    if ctx.trace is None:
        return None
    names = scopes.entry_op_scopes(ctx.counters.get("chain_entry"))
    if names is None:
        return None
    ops = {n for n, sc in names.items() if sc == "repro.done_mask"}
    return 100.0 * trace.module_ops_s(ctx.trace, CHAIN, ops) \
        / ctx.trace.window_s
