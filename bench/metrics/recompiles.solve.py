"""Traces, backend compiles and compile-cache loads of the program's solve
entry after its warm call, as the program counts them
(``spans.Entry.after_first``): the window's solves and the probe, which
all share the warm call's signature, so each one is a recompile.  Reads
no trace, so it reports on untraced runs too.  Moves ``solve_s``."""
from bench.scopes import solve_recompiles


def read(ctx):
    return solve_recompiles()
