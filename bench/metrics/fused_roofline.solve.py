"""Share of the bytes roofline reached by the single-step kernel
``stencil2d_fused_framed`` in a solve (bytes-bound; see
``bench/roofline.py``).  Moves ``solve_s``."""
from bench.roofline import kernel_share


def read(ctx):
    return kernel_share(ctx, "stencil2d_fused_framed")
