"""Share of the bytes roofline reached by the temporal-blocking kernel
``stencil2d_multistep_framed`` in a solve, per T-sweep call (bytes-bound;
see ``bench/roofline.py``).  Moves ``solve_s``."""
from bench.roofline import kernel_share


def read(ctx):
    return kernel_share(ctx, "stencil2d_multistep_framed")
