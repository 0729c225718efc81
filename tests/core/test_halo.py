"""Distributed 1:n mode ≡ single-device execution (bit-level).

Multi-device tests run in a SUBPROCESS with 8 placeholder host devices so
the main test process keeps the single-device view (the dry-run rule:
never set the device-count flag globally).
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "..", "src")


def run_multidevice(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


PRELUDE = """
import numpy as np, jax, jax.numpy as jnp
from repro.core import *
rng = np.random.default_rng(0)
b0 = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
def jac(get, *_):
    return 0.25*(get(-1,0)+get(1,0)+get(0,-1)+get(0,1))
solo = LoopOfStencilReduce(f=jac, k=1, combine="max", identity=-jnp.inf,
                           cond=lambda r: r < 1e-4,
                           delta=lambda n,o: jnp.abs(n-o),
                           max_iters=1500).run(b0)
"""


@pytest.mark.slow
class TestDistributedPattern:
    def test_1d_rows_decomposition(self):
        out = run_multidevice(PRELUDE + textwrap.dedent("""
            from repro.sharding.specs import make_mesh
            mesh = make_mesh((8,), ("data",))
            part = GridPartition(mesh=mesh, axis_names=("data",),
                                 array_axes=(0,))
            dist = distributed_loop_of_stencil_reduce(
                jac, "max", lambda r: r < 1e-4, b0, k=1, part=part,
                identity=-jnp.inf, delta=lambda n,o: jnp.abs(n-o),
                max_iters=1500)
            assert int(dist.iters) == int(solo.iters), (dist.iters, solo.iters)
            assert np.allclose(dist.a, solo.a, atol=1e-6)
            print("OK1D")
        """))
        assert "OK1D" in out

    def test_2d_decomposition_with_corners(self):
        out = run_multidevice(PRELUDE + textwrap.dedent("""
            from repro.sharding.specs import make_mesh
            mesh = make_mesh((4, 2), ("data", "model"))
            part = GridPartition(mesh=mesh, axis_names=("data", "model"),
                                 array_axes=(0, 1))
            # k=2 stencil with diagonal (corner) taps
            def blur(get, *_):
                s = sum(get(i, j) for i in (-2,-1,0,1,2)
                        for j in (-2,-1,0,1,2))
                return s / 25.0
            one = stencil_taps(blur, b0, 2, "reflect")
            dist = distributed_loop_of_stencil_reduce(
                blur, "max", lambda r: True, b0, k=2, part=part,
                identity=-jnp.inf, boundary="reflect", max_iters=5)
            assert np.allclose(dist.a, one, atol=1e-5)
            print("OK2D")
        """))
        assert "OK2D" in out

    def test_wrap_boundary_ring_exchange(self):
        out = run_multidevice(PRELUDE + textwrap.dedent("""
            from repro.sharding.specs import make_mesh
            mesh = make_mesh((8,), ("data",))
            part = GridPartition(mesh=mesh, axis_names=("data",),
                                 array_axes=(0,))
            one = stencil_taps(lambda g: jac(g), b0, 1, "wrap")
            dist = distributed_loop_of_stencil_reduce(
                jac, "max", lambda r: True, b0, k=1, part=part,
                identity=-jnp.inf, boundary="wrap", max_iters=3)
            assert np.allclose(dist.a, one, atol=1e-6)
            print("OKWRAP")
        """))
        assert "OKWRAP" in out
