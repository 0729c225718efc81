"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

These are built on :mod:`repro.core.stencil` — which is itself
property-tested against the executable formal semantics — so the kernel
tests close the loop: Pallas kernel ≡ core stencil ≡ paper semantics.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.reduce import resolve_monoid, tree_reduce
from repro.core.stencil import TapAccessor, stencil_taps
from repro.core.semantics import Boundary


def stencil2d_fused_ref(a, f, *, env=(), k=1, combine="sum", identity=None,
                        measure: Optional[Callable] = None,
                        boundary="zero", acc_dtype=jnp.float32):
    """Oracle for :func:`repro.kernels.stencil2d.stencil2d_fused`."""
    op, ident = resolve_monoid(combine, identity)
    new = stencil_taps(lambda get: f(get, *env), a, k, boundary)
    meas = measure(new, a) if measure is not None else new
    red = tree_reduce(op, meas.astype(acc_dtype), ident)
    return new, red


# ---------------------------------------------------------------------------
# Application elemental functions (shared by kernels, refs, and the apps).
# Taps-style (paper's data-oriented elemental-function protocol).
# ---------------------------------------------------------------------------

def jacobi_taps(rhs_scale: float = 0.25):
    """Jacobi sweep for the Helmholtz/Laplace problem: 4-point average."""
    def f(get):
        return rhs_scale * (get(-1, 0) + get(1, 0) + get(0, -1) + get(0, 1))
    return f


def helmholtz_jacobi_taps(alpha: float, dx: float):
    """Jacobi iteration for (∇² - α)u = -f on a uniform grid.

    u' = (dx²·f + Σ_4-neighbours u) / (4 + α·dx²)
    The forcing field enters through the kernel's ``env`` — the paper's
    read-only input matrix combined with the partial-solution's 3×3
    neighbourhood (§4.1, and Fig. 2's ``(input, env)`` schema).
    """
    denom = 4.0 + alpha * dx * dx

    def f(get, fxy):
        s = get(-1, 0) + get(1, 0) + get(0, -1) + get(0, 1)
        return (dx * dx * fxy + s) / denom
    return f


def sobel_taps():
    """Sobel edge detector: gradient magnitude of the 3×3 neighbourhood."""
    def f(get, *_):
        gx = (get(-1, 1) + 2 * get(0, 1) + get(1, 1)
              - get(-1, -1) - 2 * get(0, -1) - get(1, -1))
        gy = (get(1, -1) + 2 * get(1, 0) + get(1, 1)
              - get(-1, -1) - 2 * get(-1, 0) - get(-1, 1))
        return jnp.sqrt(gx * gx + gy * gy)
    return f


def gol_taps():
    """Conway's Game of Life (the paper's running example, Fig. 1)."""
    def f(get, *_):
        n = sum(get(di, dj) for di in (-1, 0, 1) for dj in (-1, 0, 1)
                if (di, dj) != (0, 0))
        return jnp.where((n == 3) | ((get(0, 0) > 0) & (n == 2)), 1.0, 0.0)
    return f


@functools.lru_cache(maxsize=None)
def _selection_network(n: int, ranks: tuple) -> tuple:
    """Comparators ``(i, j, keep_min, keep_max)`` that leave the ``ranks``
    of ``n`` keys in place, ``i < j``: Batcher's odd–even merge sort on the
    next power of two, with every comparator that touches a padding index
    dropped (padding would hold +inf and sit above every key, so those
    comparators move nothing), pruned backwards to the comparators some
    output depends on.  ``keep_min`` / ``keep_max`` say which of a kept
    comparator's two results is read later."""
    p = 1 << (n - 1).bit_length()
    full = []
    size = 1
    while size < p:
        k = size
        while k >= 1:
            for j in range(k % size, p - k, 2 * k):
                for i in range(min(k, p - j - k)):
                    a, b = i + j, i + j + k
                    if a // (2 * size) == b // (2 * size) and b < n:
                        full.append((a, b))
            k //= 2
        size *= 2
    need, kept = set(ranks), []
    for a, b in reversed(full):
        if a in need or b in need:
            kept.append((a, b, a in need, b in need))
            need |= {a, b}
    return tuple(reversed(kept))


def select_ranks(values, ranks):
    """The elements at ``ranks`` of the elementwise-sorted ``values`` (a
    sequence of equal-shape arrays): a comparator network of
    ``jnp.minimum`` / ``jnp.maximum``, built at trace time from
    ``len(values)`` and ``ranks`` alone — no stack, no sort.

    A sorting network's output at a rank is that rank's element, so for
    inputs without NaN each result is bitwise the element ``jnp.sort``
    puts there, except that the sign of a zero may differ where +0.0 and
    -0.0 tie.  NaN propagates through min/max instead of sorting last.
    """
    v = list(values)
    for a, b, keep_min, keep_max in _selection_network(len(v), tuple(ranks)):
        x, y = v[a], v[b]
        if keep_min:
            v[a] = jnp.minimum(x, y)
        if keep_max:
            v[b] = jnp.maximum(x, y)
    return [v[r] for r in ranks]


def median3_taps():
    """3×3 median (detection phase of the video-restoration app, §4.3)."""
    def f(get, *_):
        w = [get(di, dj) for di in (-1, 0, 1) for dj in (-1, 0, 1)]
        return select_ranks(w, (4,))[0]
    return f


def amf_detect_taps(kmax: int = 3):
    """Adaptive median filter detection (§4.3 phase 1, after [5]).

    The classic AMF escalates the window 3×3 → 5×5 → 7×7 ("dynamic stencil
    with reasonable static bounds", paper §3.2): at each level, if the
    window median is strictly between the window min/max the decision is
    made there — the pixel is noise iff it equals a window extreme;
    otherwise the window grows.  Pixels undecided at kmax are flagged.

    Each level's min, median (rank ``n // 2`` of the ``n`` taps) and max
    come from one selection network (:func:`select_ranks`), sort-free.
    For inputs without NaN the decisions and the median are bitwise those
    of a full sort of the window (up to a zero's sign where ±0 tie).

    Returns a taps function emitting both planes from one evaluation:
    ``(noise, repl)``, 1.0 where noise and the median replacement.
    """
    def f(get, *_):
        x = get(0, 0)
        decided = jnp.zeros_like(x, dtype=bool)
        noise = jnp.zeros_like(x, dtype=bool)
        repl = x
        for k in range(1, kmax + 1):
            w = [get(di, dj)
                 for di in range(-k, k + 1)
                 for dj in range(-k, k + 1)]
            mn, med, mx = select_ranks(w, (0, len(w) // 2, len(w) - 1))
            level_a = (med > mn) & (med < mx)
            is_noise_here = ~((x > mn) & (x < mx))
            newly = level_a & ~decided
            noise = jnp.where(newly, is_noise_here, noise)
            repl = jnp.where(newly & is_noise_here, med, repl)
            decided = decided | level_a
        noise = jnp.where(decided, noise, True)
        repl = jnp.where(~decided, med, repl)  # last-level median fallback
        return noise.astype(x.dtype), repl
    return f


def restore_taps(beta: float = 2.0):
    """Regularisation sweep of the two-phase restoration (§4.3).

    Pixels flagged noisy (mask=1) move toward a weighted combination of the
    4-neighbourhood median and mean (edge-preserving smoothing functional
    minimisation, as in [5]); clean pixels are pinned to the observation.
    ``env = (noisy_observation, noise_mask)``.  The median of four is the
    mean of the two middle values, ``(sum - min - max) / 2`` — sort-free,
    so the sweep lowers inside a TPU kernel.
    """
    def f(get, noisy, mask):
        a, b, c, d = get(-1, 0), get(1, 0), get(0, -1), get(0, 1)
        s = a + b + c + d
        lo = jnp.minimum(jnp.minimum(a, b), jnp.minimum(c, d))
        hi = jnp.maximum(jnp.maximum(a, b), jnp.maximum(c, d))
        med4 = 0.5 * (s - lo - hi)
        mean4 = 0.25 * s
        prop = (beta * med4 + mean4) / (beta + 1.0)
        return jnp.where(mask > 0, prop, noisy)
    return f


def heat_taps(nu: float = 0.1):
    """Explicit heat equation step (generic iterative stencil for tests)."""
    def f(get, *_):
        lap = (get(-1, 0) + get(1, 0) + get(0, -1) + get(0, 1)
               - 4.0 * get(0, 0))
        return get(0, 0) + nu * lap
    return f


def abs_delta(new, old):
    """The -d variant's δ for convergence-on-change monitoring."""
    return jnp.abs(new - old)
