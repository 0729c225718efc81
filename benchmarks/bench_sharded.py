"""Sharded persistent-halo deployment — the communication-avoiding rows.

Runs the ``pallas-sharded`` backend over every device of this process
(rows split over a ``data`` axis; on CPU,
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` gives it eight)
and reports:

* per-iteration wall time of the distributed loop at unroll 1 and 4
  (the deep-halo temporal-blocking schedule checks the condition — and
  exchanges ghosts — once per 4 fused sweeps);
* the ppermute rounds per while-body counted from the jaxpr, so the
  ≈T× ICI-message reduction of ``unroll=T`` is pinned by structure, not
  just wall time;
* the jnp 1:n deployment as the non-persistent reference.

Everything runs in this process: the chip belongs to one process.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (GridPartition, LoopOfStencilReduce,
                        distributed_loop_of_stencil_reduce)
from repro.core.introspect import count_primitive, while_body_eqns
from repro.kernels import ref as R
from repro.sharding.specs import make_mesh
from .common import record, time_fn

ITERS = 8


def run(sizes=(256,)) -> list[dict]:
    n = len(jax.devices())
    part = GridPartition(mesh=make_mesh((n,), ("data",)),
                         axis_names=("data",), array_axes=(0,))
    heat = R.heat_taps(0.1)
    rows = []
    rng = np.random.default_rng(0)
    for size in sizes:
        a = jnp.asarray(rng.normal(size=(size, size)), jnp.float32)
        for unroll in (1, 4):
            loop = LoopOfStencilReduce(
                f=heat, k=1, combine="max", cond=lambda r: False,
                delta=R.abs_delta, boundary="zero", max_iters=ITERS,
                unroll=unroll, backend="pallas-sharded", partition=part,
                block=(32, 128))
            t = time_fn(jax.jit(lambda x: loop.run(x).a), a)
            ppb = count_primitive(
                while_body_eqns(lambda x: loop.run(x).a, a), "ppermute")
            # exchange rounds per SWEEP: body rounds / sweeps-per-body
            rows.append(record(
                f"sharded_{size}_persistent", t, backend="pallas-sharded",
                unroll=unroll, mesh=f"{n}x1",
                derived=(f"per_iter={t / ITERS * 1e6:.1f}us;"
                         f"ppermute_per_body={ppb};"
                         f"ppermute_per_sweep={ppb / unroll:g}")))
        dist = jax.jit(lambda x: distributed_loop_of_stencil_reduce(
            heat, "max", lambda r: False, x, k=1, part=part,
            delta=R.abs_delta, max_iters=ITERS).a)
        t = time_fn(dist, a)
        rows.append(record(
            f"sharded_{size}_jnp_dist", t, backend="jnp", mesh=f"{n}x1",
            derived=f"per_iter={t / ITERS * 1e6:.1f}us"))
    return rows


if __name__ == "__main__":
    from .common import csv_row
    print("\n".join(csv_row(r) for r in run()))
