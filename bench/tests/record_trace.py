"""Record the small chip trace the reduction tests read.

    python3 bench/tests/record_trace.py <out.xplane.pb>

Run on one TPU chip.  Inside a ``bench.window`` span: two fixed
8-sweep ``pallas`` solves at 1024², each in a ``bench.solve`` span, with
a 20 ms host sleep in a ``bench.host`` span between them (an idle gap
the reduction has to name).  Python tracing and the HLO protos are left
out to keep the file small.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(out: str) -> None:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.common import span
    from repro.kernels import ops

    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: no TPU")
    u0 = jnp.zeros((1024, 1024), jnp.float32)
    f = jax.random.normal(jax.random.key(0), (1024, 1024), jnp.float32)

    def solve():
        return ops.jacobi_solve(u0, f, alpha=0.1, dx=1.0,
                                tol=np.float32(0.0), max_iters=8,
                                backend="pallas")

    jax.block_until_ready(solve())
    tdir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(tdir, profiler_options=opts)
    with span("window"):
        with span("solve"):
            jax.block_until_ready(solve())
        with span("host"):
            time.sleep(0.02)
        with span("solve"):
            jax.block_until_ready(solve())
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    shutil.copy(path, out)
    shutil.rmtree(tdir)
    print(out, os.path.getsize(out))


if __name__ == "__main__":
    main(sys.argv[1])
