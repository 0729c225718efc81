"""The ``solve_sharded`` driver: whole converged solves of
``ops.jacobi_solve`` with the grid split over a mesh (the paper's 1:n
deployment, arXiv:1609.04567 sec. 3.4).

The cell's devices form a ``rows x cols`` mesh, as the configuration's
``mesh`` says; the grid is split over it by rows and columns
(``GridPartition`` over array axes (0, 1)), and every input is made on
the mesh already sharded, never whole on one chip.  Everything else (the
window, the probe, the check against the plain reference, which XLA
partitions over the same mesh) is the ``solve`` driver's.
"""
from __future__ import annotations

import functools

import numpy as np

from bench import generators
from bench.drivers import solve


class Cell(solve.Cell):
    def __init__(self, config, traffic, seed, devices):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding

        from repro.core import GridPartition
        from repro.kernels import ops

        self.config, self.traffic, self.seed = config, traffic, seed
        self.shape = tuple(config["grid"])
        self.tol = np.float32(traffic["tol"])
        self.unroll = int(traffic.get("unroll", 1))
        rows, cols = config["mesh"]["rows"], config["mesh"]["cols"]
        mesh = jax.make_mesh((rows, cols), ("rows", "cols"), devices=devices)
        self.part = GridPartition(mesh=mesh, axis_names=("rows", "cols"),
                                  array_axes=(0, 1))
        self.sharding = NamedSharding(self.part.mesh, self.part.pspec)
        self.make = getattr(generators, traffic["generator"])
        self.forcings = self.make(seed, self.shape, int(traffic["forcings"]),
                                  int(traffic["field_seed"]), self.sharding)
        self.step = self.unroll    # sweeps between two checks
        self.u0 = jax.jit(lambda: jnp.zeros(self.shape, jnp.float32),
                          out_shardings=self.sharding)()
        self.solve = functools.partial(
            ops.jacobi_solve, alpha=config["alpha"], dx=config["dx"],
            max_iters=config["max_iters"], backend=traffic["engine"],
            unroll=self.unroll, part=self.part)
        self._gap = jax.jit(lambda a, b: jnp.max(jnp.abs(a - b)))
        # tol is traced: an infinite one runs the same executable and
        # stops at the first check
        u, _, _ = self.solve(self.u0, self.forcings[0],
                             tol=np.float32(np.inf))
        float(self._gap(u, self.u0))
        self.first = {}     # forcing index -> its first solution
        self.records = []   # (forcing index, delta, iters, |u - first|)
