"""Paper Table 1 — Helmholtz equation solver, across the backend axis.

Deployments compared (the paper's CPU / 1×GPU / 2×GPU 1:2 columns mapped
to this host):
    naive            host-driven loop, device_get of the full grid +
                     re-upload each iteration (the §3.3 strawman)
    pallas_per_iter  on-device while_loop, but the seed's pad-per-
                     iteration kernel staging: jnp.pad + full-grid slice
                     inside the loop body (what this engine retires)
    persistent       the Loop-of-stencil-reduce through the engine's
                     backend axis — jnp (shift algebra), pallas
                     (persistent halo frame, zero-copy body), and
                     pallas-multistep at several unroll depths T
                     (÷T HBM traffic per sweep)
    1:n              the persistent loop under an n-way halo-exchange
                     decomposition over this process's devices (on CPU,
                     XLA_FLAGS=--xla_force_host_platform_device_count=8
                     gives it eight)

Fixed 10 iterations ("convergence is reached after 10 iterations",
Table 1 caption) so rows are comparable across sizes; the multistep rows
use unroll values that divide 10 exactly.  Derived GB/s is *algorithmic*
bandwidth (3 full-grid streams × iterations / wall-time), so the
pad-hoist and the ÷T traffic win surface as higher effective GB/s.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import GridPartition, distributed_loop_of_stencil_reduce
from repro.core.pattern import LoopOfStencilReduce
from repro.kernels import ref as R
from repro.sharding.specs import make_mesh
from repro.kernels.ops import fused_sweep
from .common import record, stencil_gbps, time_fn

ITERS = 10
ALPHA, DX = 0.5, 1.0 / 512
BACKENDS = (("jnp", 1), ("pallas", 1), ("pallas-multistep", 2),
            ("pallas-multistep", 5))


def naive_loop(u0, fxy):
    """Full D2H + H2D round trip per iteration (paper's naïve schema)."""
    f = R.helmholtz_jacobi_taps(ALPHA, DX)
    step = jax.jit(lambda u, e: fused_sweep(
        u, f, env=(e,), k=1, combine="max", identity=-jnp.inf,
        measure=R.abs_delta, use_pallas=False))
    u = u0
    for _ in range(ITERS):
        u, delta = step(u, fxy)
        u_host = np.asarray(jax.device_get(u))        # D2H (full grid)
        float(delta)                                  # host-side condition
        u = jax.device_put(jnp.asarray(u_host))       # H2D (full grid)
    return u


@jax.jit
def pallas_per_iter_loop(u0, fxy):
    """ONE while_loop, but framing/unframing the grid EVERY iteration —
    the seed's kernel staging, kept as the pad-hoist baseline."""
    f = R.helmholtz_jacobi_taps(ALPHA, DX)

    def body(carry):
        u, it = carry
        u, _ = fused_sweep(u, f, env=(fxy,), k=1, combine="max",
                           identity=-jnp.inf, measure=R.abs_delta,
                           backend="pallas")
        return u, it + 1

    u, _ = jax.lax.while_loop(lambda c: c[1] < ITERS, body,
                              (u0, jnp.asarray(0)))
    return u


@functools.partial(jax.jit, static_argnames=("backend", "unroll"))
def persistent_loop(u0, fxy, *, backend="jnp", unroll=1):
    """ONE while_loop: grids never leave the device (the pattern).  On
    the pallas backends the halo frame is the carry — no pad/slice in
    the body."""
    loop = LoopOfStencilReduce(
        f=R.helmholtz_jacobi_taps(ALPHA, DX), k=1, combine="max",
        cond=lambda r: False, delta=R.abs_delta, boundary="zero",
        max_iters=ITERS, unroll=unroll, backend=backend)
    return loop.run(u0, env=(fxy,)).a


def one_to_n(u0, fxy) -> tuple[float, int]:
    """1:n halo-exchange deployment over every device of this process
    (rows split over a ``data`` axis).  Runs in-process: the chip belongs
    to one process.  Returns (median seconds, n)."""
    n = len(jax.devices())
    part = GridPartition(mesh=make_mesh((n,), ("data",)),
                         axis_names=("data",), array_axes=(0,))
    taps = R.helmholtz_jacobi_taps(ALPHA, DX)
    run = jax.jit(lambda u: distributed_loop_of_stencil_reduce(
        lambda get: taps(get, 0.0),      # forcing folded out for timing
        "max", lambda r: False, u, k=1, part=part, identity=-jnp.inf,
        max_iters=ITERS).a)
    return time_fn(run, u0), n


def run(sizes=(512, 1024, 2048)) -> list[dict]:
    rows = []
    rng = np.random.default_rng(0)
    for size in sizes:
        u0 = jnp.zeros((size, size), jnp.float32)
        fxy = jnp.asarray(rng.normal(size=(size, size)), jnp.float32)
        gbps = lambda t: stencil_gbps(size, ITERS, t)

        t_naive = time_fn(naive_loop, u0, fxy)
        rows.append(record(f"helmholtz_{size}_naive", t_naive,
                           backend="jnp", gbps=gbps(t_naive),
                           derived=f"{ITERS}it"))
        t_ppi = time_fn(pallas_per_iter_loop, u0, fxy)
        rows.append(record(
            f"helmholtz_{size}_pallas_per_iter", t_ppi, backend="pallas",
            gbps=gbps(t_ppi), derived="pad-per-iteration baseline"))
        for backend, unroll in BACKENDS:
            t = time_fn(persistent_loop, u0, fxy, backend=backend,
                        unroll=unroll)
            extra = (f"speedup_vs_pad_per_iter={t_ppi / t:.2f}x"
                     if backend.startswith("pallas") else
                     f"speedup_vs_naive={t_naive / t:.2f}x")
            rows.append(record(f"helmholtz_{size}_persistent", t,
                               backend=backend, unroll=unroll,
                               gbps=gbps(t), derived=extra))
        t_1n, n = one_to_n(u0, fxy)
        rows.append(record(
            f"helmholtz_{size}_1to{n}", t_1n, backend="jnp",
            mesh=f"{n}x1", gbps=gbps(t_1n),
            derived=f"speedup_vs_naive={t_naive / t_1n:.2f}x"))
    return rows


if __name__ == "__main__":
    from .common import csv_row
    print("\n".join(csv_row(r) for r in run()))
