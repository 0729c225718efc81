"""Share of the bytes roofline reached by the single-step kernel
``stencil2d_fused_framed`` on each chip of a mesh-sharded solve: a call
sweeps one shard (the configuration's ``shard``), and the calls and
their device time are summed over the chips (bytes-bound; see
``bench/roofline.py``).  Moves ``solve_s``."""
from bench.roofline import kernel_share


def read(ctx):
    return kernel_share(ctx, "stencil2d_fused_framed", shape="shard")
