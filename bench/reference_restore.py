"""Plain reference of the two-phase restoration (arXiv:1609.04567 sec.
4.3), written out in ``jax.numpy`` from its equations.  Nothing here
imports the program.

Detection, the adaptive median filter: for k = 1, 2, 3 (windows 3x3,
5x5, 7x7, mirrored at the edge without repeating it) take the window's
minimum, median and maximum.  At the first k whose median lies strictly
between its minimum and maximum the pixel is decided: it is noise unless
it lies strictly between them too, and noise takes that median.  A pixel
decided at no k is noise and takes the 7x7 median.

Restoration: from the detected frame, sweep every noisy pixel to

    (beta * median4 + mean4) / (beta + 1)

of its four neighbours (median4 = the mean of the two middle values =
(sum - min - max) / 2, mean4 = sum / 4, sum = north + south + west +
east), the other pixels held at the detected frame, until
max|a' - a| < tol after a sweep, or ``max_iters`` sweeps.

``dtype`` is float32 for the check and bfloat16 for the control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _window_stats(x, k):
    """Minimum, median and maximum of every (2k+1)^2 mirrored window."""
    h, w = x.shape
    xp = jnp.pad(x, k, mode="reflect")
    win = jnp.stack([xp[k + di:k + di + h, k + dj:k + dj + w]
                     for di in range(-k, k + 1) for dj in range(-k, k + 1)])
    srt = jnp.sort(win, axis=0)
    return srt[0], srt[win.shape[0] // 2], srt[-1]


def detect(x, kmax):
    """``(noise mask, detected frame)``: the adaptive median filter."""
    decided = jnp.zeros(x.shape, bool)
    noise = jnp.ones(x.shape, bool)
    value = x
    for k in range(1, kmax + 1):
        lo, med, hi = _window_stats(x, k)
        here = jnp.logical_and(med > lo, med < hi) & ~decided
        is_noise = ~((x > lo) & (x < hi))
        noise = jnp.where(here, is_noise, noise)
        value = jnp.where(here & is_noise, med, value)
        value = jnp.where(~decided & ~here & (k == kmax), med, value)
        decided = decided | here
    return noise, value


def _sweep(a, fixed, noise, beta):
    ap = jnp.pad(a, 1, mode="reflect")
    n, s = ap[:-2, 1:-1], ap[2:, 1:-1]
    w, e = ap[1:-1, :-2], ap[1:-1, 2:]
    total = n + s + w + e
    lo = jnp.minimum(jnp.minimum(n, s), jnp.minimum(w, e))
    hi = jnp.maximum(jnp.maximum(n, s), jnp.maximum(w, e))
    median4 = (total - lo - hi) * jnp.asarray(0.5, a.dtype)
    mean4 = total * jnp.asarray(0.25, a.dtype)
    b = jnp.asarray(beta, a.dtype)
    new = (b * median4 + mean4) / (b + jnp.asarray(1.0, a.dtype))
    return jnp.where(noise, new, fixed)


@functools.partial(jax.jit, static_argnames=("kmax", "beta", "tol",
                                             "max_iters", "dtype"))
def restore_frame(x, *, kmax, beta, tol, max_iters, dtype=jnp.float32):
    """Detection then restoration of one frame: ``(a, sweeps)``."""
    with jax.default_matmul_precision("highest"):
        x = x.astype(dtype)
        noise, a0 = detect(x, kmax)

        def body(carry):
            a, _, it = carry
            new = _sweep(a, a0, noise, beta)
            delta = jnp.max(jnp.abs(new - a)).astype(jnp.float32)
            return new, delta, it + 1

        def cond(carry):
            _, delta, it = carry
            return jnp.logical_and(delta >= tol, it < max_iters)

        a, _, it = jax.lax.while_loop(
            cond, body, (a0, jnp.float32(jnp.inf), jnp.int32(0)))
    return a.astype(jnp.float32), it
