"""Share of the bytes roofline reached by the farm's lane sweep kernel
``stencil2d_fused_framed``, one call sweeping every lane's frame (bytes
bound; see ``bench/roofline.py``).  Moves ``frames_per_s``."""
from bench.roofline import kernel_share


def read(ctx):
    lanes = ctx.counters.get("lanes")
    if not lanes:
        return None
    return kernel_share(ctx, "stencil2d_fused_framed", shape="frame",
                        lanes=lanes)
