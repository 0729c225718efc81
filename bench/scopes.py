"""The program's own names in a run: its device scopes and its recompile
counts, as the readers ``done_mask_share.solve``,
``ghost_refresh_share.solve``, ``recompiles.solve`` and
``lane_select_share.stream`` take them.

The program (``src/repro/core/spans.py``) puts device scopes
``repro.<name>`` in the ``op_name`` metadata of the HLO instructions a
region lowers to.  A device trace names an op only by its HLO
instruction name, so an op's scope comes from a map ``{instruction
name: scope}`` of the executable that ran (``spans.op_scopes``).  Its
solve entry (``ops.jacobi_solve``, a ``spans.Entry``) counts the
traces, compiles and cache loads of its calls.

Every reader here finds nothing, and returns None, where the program
has no such names (a program without ``repro.core.spans``).
"""
from __future__ import annotations

from bench import trace


def scope_s(tr: trace.Trace, op_scopes: dict, scope: str) -> float:
    """Seconds in which an op mapped to ``scope`` ran: the union of those
    leaf ops' intervals inside the window, averaged over the devices.
    Ops the map does not name belong to no scope."""
    total = 0.0
    for evs in tr.devices.values():
        total += trace.length(trace.union(
            (s, e) for n, s, e in evs if op_scopes.get(n) == scope))
    return total * 1e-9 / len(tr.devices)


def _solve_entry():
    """The program's solve entry, where it is a ``spans.Entry``."""
    try:
        from repro.kernels import ops
    except ImportError:
        return None
    entry = ops.jacobi_solve
    return entry if hasattr(entry, "after_first") else None


def solve_op_scopes():
    """``{instruction name: scope}`` of the one executable every call of
    the program's solve entry ran (the warm call's, the window's and the
    probe's), or None."""
    entry = _solve_entry()
    return None if entry is None else entry.op_scopes()


def solve_recompiles():
    """Traces, compiles and cache loads of the program's solve entry in
    every call after its first (the cell's warm call), or None."""
    entry = _solve_entry()
    if entry is None or entry.calls < 2:
        return None
    return sum(entry.after_first.values())


def entry_op_scopes(entry):
    """``{instruction name: scope}`` of a jitted entry's executable,
    rebuilt from ``(jitted function, abstract arguments)`` (a
    compile-cache hit), or None where there is no entry or the program
    has no scopes."""
    try:
        from repro.core import spans
    except ImportError:
        return None
    if entry is None:
        return None
    fn, args = entry
    return spans.op_scopes(fn.lower(*args).compile())


def scope_share(ctx, scope: str):
    """Percent of the traced window in which ops of ``scope`` ran on the
    device, or None where the run was not traced or the program has no
    scopes."""
    if ctx.trace is None:
        return None
    scopes = solve_op_scopes()
    if scopes is None:
        return None
    return 100.0 * scope_s(ctx.trace, scopes, scope) / ctx.trace.window_s
