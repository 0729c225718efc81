"""Percent of the traced window in which the device ran the engine's
done-mask (the program's scope ``repro.done_mask``: the ``jnp.where``
that freezes a finished loop's carry in ``core/pattern.py``), whatever
XLA fused it into.  Moves ``solve_s``."""
from bench.scopes import scope_share


def read(ctx):
    return scope_share(ctx, "repro.done_mask")
