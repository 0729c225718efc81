"""Public jit'd wrappers around the execution engine (paper §4 apps).

Every app here instantiates the Loop-of-stencil-reduce through the
persistent-halo engine's **backend axis** (:mod:`repro.core.executor`):

* ``backend="jnp"``       — the shift-algebra reference path;
* ``backend="pallas"``    — the fused single-step kernel iterated on a
  persistent halo frame (pad/round-up hoisted out of the loop);
* ``backend="pallas-multistep"`` — temporal blocking, ``unroll`` sweeps
  fused per HBM round-trip.

``use_pallas`` is kept as a boolean shorthand (False → "jnp",
True → "pallas"); an explicit ``backend=`` wins.  All paths implement the
same Loop-of-stencil-reduce contract, so the whole framework runs
end-to-end on any of them.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import spans
from repro.core.executor import sweep_once
from repro.core.pattern import LoopOfStencilReduce
from repro.core.stencil import stencil_taps

from . import ref as R


def _resolve_backend(use_pallas: bool, backend: Optional[str]) -> str:
    return backend if backend else ("pallas" if use_pallas else "jnp")


def _resolve_sharded_backend(use_pallas: bool, backend: Optional[str],
                             part) -> str:
    """Backend resolution for the iterative apps: a mesh partition means
    the 1:n deployment — refuse a conflicting single-device backend
    rather than silently ignoring ``part``."""
    if part is None:
        return _resolve_backend(use_pallas, backend)
    if backend not in (None, "pallas-sharded"):
        raise ValueError(
            f"part= selects the sharded 1:n deployment; backend="
            f"{backend!r} conflicts (pass backend='pallas-sharded' or "
            "drop it)")
    return "pallas-sharded"


def fused_sweep(a, f, *, env=(), k=1, combine="sum", identity=None,
                measure=None, boundary="zero", block=(256, 256),
                use_pallas=True, backend=None, unroll=1, interpret=None,
                double_buffer=True):
    """One fused stencil+reduce sweep: returns (new, reduced)."""
    return sweep_once(
        a, f, env=env, k=k, combine=combine, identity=identity,
        measure=measure, boundary=boundary, block=block,
        backend=_resolve_backend(use_pallas, backend), unroll=unroll,
        interpret=interpret, double_buffer=double_buffer)


def jacobi_solve(u0, fxy, *, alpha=0.5, dx=1.0 / 512, tol=1e-4,
                 max_iters=1000, use_pallas=False, backend=None, unroll=1,
                 part=None):
    """Full Helmholtz Jacobi solve as ONE on-device while_loop (persistent
    device memory, fused sweep+delta-reduce — the paper's optimised path).

    On the Pallas backends the grid is carried as a persistent halo frame:
    no per-iteration pad/slice; ``unroll`` with "pallas-multistep" fuses
    that many sweeps per HBM round-trip (convergence checked every
    ``unroll`` iterations, as the pattern's unroll semantics).  Passing a
    ``part`` (:class:`repro.sharding.specs.GridPartition`, hashable →
    jit-static) selects the 1:n deployment: per-shard frames inside
    shard_map, ppermute ghost exchange, unroll=T deep halos
    (``backend`` then defaults to "pallas-sharded").
    """
    be = _resolve_sharded_backend(use_pallas, backend, part)
    loop = LoopOfStencilReduce(
        f=R.helmholtz_jacobi_taps(alpha, dx), k=1, combine="max",
        cond=lambda r: r < tol, delta=R.abs_delta, boundary="zero",
        max_iters=max_iters, unroll=unroll, backend=be, partition=part)
    res = loop.run(u0, env=(fxy,))
    return res.a, res.reduced, res.iters


# the host call opens the span ``repro.solve`` (dispatch, and any trace,
# compile or cache load it triggers); ``jacobi_solve.lower`` is the jit's own
jacobi_solve = spans.Entry(
    "solve", jacobi_solve,
    static_argnames=("alpha", "dx", "max_iters", "use_pallas", "backend",
                     "unroll", "part"))


@functools.partial(jax.jit, static_argnames=("use_pallas", "backend"))
def sobel(img, *, use_pallas=False, backend=None):
    """Single-iteration stencil (the paper's worst case for accelerators):
    Sobel magnitude + fused max-response reduce (stream statistics)."""
    new, r = sweep_once(img, R.sobel_taps(), k=1, combine="max",
                        identity=-jnp.inf, boundary="reflect",
                        backend=_resolve_backend(use_pallas, backend))
    return new, r


@functools.partial(jax.jit, static_argnames=("max_iters", "use_pallas",
                                             "backend", "unroll", "part"))
def restore(frame, noisy_mask, *, beta=2.0, tol=1e-3, max_iters=64,
            use_pallas=False, backend=None, unroll=1, part=None):
    """Restoration phase (§4.3): iterate the regularisation sweep until the
    mean absolute update over noisy pixels converges.  ``part`` selects
    the sharded 1:n deployment, as in :func:`jacobi_solve`."""
    be = _resolve_sharded_backend(use_pallas, backend, part)
    npx = jnp.maximum(noisy_mask.sum(), 1.0)
    loop = LoopOfStencilReduce(
        f=R.restore_taps(beta), k=1, combine="sum",
        cond=lambda r: r / npx < tol, delta=R.abs_delta,
        boundary="reflect", max_iters=max_iters, unroll=unroll,
        backend=be, partition=part)
    res = loop.run(frame, env=(frame, noisy_mask))
    return res.a, res.reduced / npx, res.iters


@functools.partial(jax.jit, static_argnames=("use_pallas", "kmax",
                                             "backend"))
def adaptive_median_detect(frame, *, kmax=3, use_pallas=False, backend=None):
    """Detection phase (§4.3): classic adaptive median filter with window
    escalation 3×3→5×5→7×7.  Returns (noise_mask, repaired_frame) where the
    repaired frame replaces flagged pixels by the AMF median — the
    restoration phase's initial guess.

    The jnp path evaluates the filter once per pixel for both planes; the
    Pallas kernels emit one plane a sweep, so that path sweeps twice."""
    be = _resolve_backend(use_pallas, backend)
    f = R.amf_detect_taps(kmax)
    if be == "jnp":
        mask, repl = stencil_taps(f, frame, kmax, "reflect")
    else:
        mask, _ = sweep_once(frame, lambda get: f(get)[0], k=kmax,
                             combine="sum", identity=0.0,
                             boundary="reflect", backend=be)
        repl, _ = sweep_once(frame, lambda get: f(get)[1], k=kmax,
                             combine="sum", identity=0.0,
                             boundary="reflect", backend=be)
    repaired = jnp.where(mask > 0, repl, frame)
    return mask, repaired
