"""Mean iterations to convergence of the window's solves (pattern entry:
``kernels/ops.py`` ``jacobi_solve``, ``core/pattern.py``).  Moves
``solve_s``: time to solution is iterations times time per sweep."""


def read(ctx):
    iters = ctx.counters.get("iters")
    return sum(iters) / len(iters) if iters else None
