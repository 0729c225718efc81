"""The ``solve`` driver: whole converged solves of ``ops.jacobi_solve``.

Set-up makes the forcing fields on the chip from the seed and warms the
one executable with an infinite tolerance, which exits after the first
check.  The window runs whole solves back to back, each ending with a
host read of its reduced change and iteration count, until ``seconds``
have passed; ``solve_s`` is the time of those solves over their number.
The probe then solves one forcing drawn from the seed itself through the
same executable, and the check holds it to the reference with the rest.
"""
from __future__ import annotations

import functools
import time

import numpy as np

from bench import generators, reference
from bench.common import Check, Window, span


class Cell:
    def __init__(self, config, traffic, seed, devices):
        import jax
        import jax.numpy as jnp
        from jax.sharding import SingleDeviceSharding

        from repro.kernels import ops

        self.config, self.traffic, self.seed = config, traffic, seed
        self.shape = tuple(config["grid"])
        self.tol = np.float32(traffic["tol"])
        self.unroll = int(traffic.get("unroll", 1))
        self.sharding = SingleDeviceSharding(devices[0])
        self.make = getattr(generators, traffic["generator"])
        self.forcings = self.make(seed, self.shape, int(traffic["forcings"]),
                                  int(traffic["field_seed"]), self.sharding)
        self.step = self.unroll if traffic["engine"] == "pallas-multistep" \
            else 1     # sweeps between two checks of the condition
        self.u0 = jax.jit(lambda: jnp.zeros(self.shape, jnp.float32),
                          out_shardings=self.sharding)()
        self.solve = functools.partial(
            ops.jacobi_solve, alpha=config["alpha"], dx=config["dx"],
            max_iters=config["max_iters"], backend=traffic["engine"],
            unroll=self.unroll)
        self._gap = jax.jit(lambda a, b: jnp.max(jnp.abs(a - b)))
        # tol is traced: an infinite one runs the same executable and
        # stops at the first check
        u, _, _ = self.solve(self.u0, self.forcings[0],
                             tol=np.float32(np.inf))
        float(self._gap(u, self.u0))
        self.first = {}     # forcing index -> its first solution
        self.records = []   # (forcing index, delta, iters, |u - first|)

    def window(self, seconds: float) -> Window:
        import jax

        count = len(self.forcings)
        busy = 0.0
        stop = time.perf_counter() + seconds
        while True:
            p = len(self.records) % count
            t0 = time.perf_counter()
            with span("solve"):
                u, delta, iters = self.solve(self.u0, self.forcings[p],
                                             tol=self.tol)
                delta, iters = jax.device_get((delta, iters))
                u.block_until_ready()
            busy += time.perf_counter() - t0
            # outside the timed solve: every later solve of a forcing is
            # held to its first, and the first to the reference
            with span("hold"):
                if p in self.first:
                    gap = float(self._gap(u, self.first[p]))
                else:
                    self.first[p], gap = u, 0.0
            del u
            self.records.append((p, float(delta), int(iters), gap))
            if time.perf_counter() >= stop:
                break
        n = len(self.records)
        cap = self.config["max_iters"]
        failed = sum(1 for _, d, it, _ in self.records
                     if it >= cap or not d < self.tol)
        return Window(e2e={"solve_s": busy / n}, attempted=n,
                      failed=failed,
                      counters={"iters": [r[2] for r in self.records]})

    def probe(self):
        """After the window: one solve of a forcing drawn from the seed
        itself, through the window's executable, kept for the check as
        one more forcing."""
        import jax

        f = self.make(self.seed, self.shape, 1,
                      generators.seed_draw(self.seed), self.sharding)[0]
        u, delta, iters = self.solve(self.u0, f, tol=self.tol)
        delta, iters = jax.device_get((delta, iters))
        p = len(self.forcings)
        self.forcings.append(f)
        self.first[p] = u
        self.records.append((p, float(delta), int(iters), 0.0))

    def release(self):
        """Free what only the program needed before the reference runs."""
        self.u0 = None
        self.solve = None

    def _reference(self, p, dtype):
        import jax.numpy as jnp

        c = self.config
        return reference.helmholtz_solve(
            self.forcings[p], self.tol, alpha=c["alpha"], dx=c["dx"],
            max_iters=c["max_iters"], check_every=self.step,
            dtype=getattr(jnp, dtype))

    def check(self, control: bool = False) -> list[Check]:
        """Every solve of the window and the probe against the plain
        reference: the solution (its forcing's first one, plus its
        distance from that first one), the last reduced change and the
        iteration count.  ``control`` puts the
        reference in the next precision down in the program's place."""
        import jax.numpy as jnp

        records = self.records
        if control:
            records = []
            for p in sorted(self.first):
                u, d, it = self._reference(p, "bfloat16")
                self.first[p] = u.astype(jnp.float32)
                records.append((p, float(d), int(it), 0.0))
        limits = self.config["limits"]
        u_err = iters_gap = delta_gap = 0.0
        for p in sorted(self.first):
            ur, dr, ir = self._reference(p, self.config["dtype"])
            umax = float(jnp.max(jnp.abs(ur)))
            err = float(jnp.max(jnp.abs(self.first[p] - ur))) / umax
            dr, ir = float(dr), int(ir)
            del ur
            for q, d, it, gap in records:
                if q != p:
                    continue
                u_err = max(u_err, err + gap / umax)
                iters_gap = max(iters_gap, abs(it - ir) / self.step)
                delta_gap = max(delta_gap, abs(d - dr) / float(self.tol))
        return [Check("u_err", u_err, limits["u_err"]),
                Check("iters_gap", iters_gap, limits["iters_gap"]),
                Check("delta_gap", delta_gap, limits["delta_gap"])]
