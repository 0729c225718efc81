"""Plain references that decide ``correct``: the same semantics as the
program, written out in ``jax.numpy`` from the paper's equations.

Nothing here imports the program.  Each reference takes a ``dtype``: the
configuration's own (float32) for the check, and the next precision down
(bfloat16) for the control that has to come out as not correct.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _neighbours(u, mode):
    """The four neighbours of every cell, with the boundary ``mode`` of
    ``jnp.pad`` ("constant" = zero outside the domain, "reflect" = the
    mirror that leaves the edge cell out)."""
    up = jnp.pad(u, 1, mode=mode)
    return up[:-2, 1:-1], up[2:, 1:-1], up[1:-1, :-2], up[1:-1, 2:]


@functools.partial(jax.jit, static_argnames=("alpha", "dx", "max_iters",
                                             "check_every", "dtype"))
def helmholtz_solve(f, tol, *, alpha, dx, max_iters, check_every=1,
                    dtype=jnp.float32):
    """Jacobi iteration for (∇² − α)u = −f with u = 0 outside the domain,
    from u = 0:

        u' = (dx²·f + u[i-1,j] + u[i+1,j] + u[i,j-1] + u[i,j+1]) / (4 + α·dx²)

    The loop stops after the first check at which max|u' − u| < tol, or at
    ``max_iters``; it checks every ``check_every`` sweeps, the change being
    that of the last sweep.  Returns (u, last max|Δ|, sweeps)."""
    f = f.astype(dtype)
    scale = jnp.asarray(dx * dx, dtype)
    denom = jnp.asarray(4.0 + alpha * dx * dx, dtype)

    def sweep(u):
        n, s, w, e = _neighbours(u, "constant")
        return (scale * f + (n + s + w + e)) / denom

    def body(carry):
        u, _, it = carry
        for _ in range(check_every - 1):
            u = sweep(u)
        un = sweep(u)
        delta = jnp.max(jnp.abs(un - u)).astype(jnp.float32)
        return un, delta, it + check_every

    def cond(carry):
        _, delta, it = carry
        return jnp.logical_and(delta >= tol, it < max_iters)

    return jax.lax.while_loop(
        cond, body, (jnp.zeros_like(f), jnp.float32(jnp.inf),
                     jnp.int32(0)))
