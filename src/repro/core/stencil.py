"""Production stencil application: the α(f)∘σ_k of the paper, compiled to shifts.

Two execution strategies, both extensionally equal to
:func:`repro.core.semantics.stencil` (property-tested):

* :func:`stencil_windows` — materialise the window tensor (general; memory
  cost ×(2k+1)^n).  Used for elemental functions that need the whole window
  (e.g. a sort over the window).
* :func:`stencil_taps` — the elemental function receives a *tap accessor*
  ``get(*offsets)`` returning the array shifted by the given offsets.  XLA
  fuses the shifts; nothing is materialised.  This is the fast path used by
  Jacobi, Sobel, Game-of-Life and by the sequence-stencil layers of the LM
  stack, and it is the semantics the Pallas kernels implement in VMEM.

Both paths share the boundary model (⊥ realisation) of the semantics module.
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax.numpy as jnp

from .semantics import Boundary, neighborhoods


class TapAccessor:
    """Shifted-array accessor handed to tap-style elemental functions.

    ``get(d1, ..., dn)`` returns the array whose item at position i is
    ``a'[i + (d1..dn)]`` — i.e. the neighbour at relative offset d, with ⊥
    filled according to the boundary model.  Offsets must lie in [-k, k].
    """

    def __init__(self, a: jnp.ndarray, k: int, boundary: Boundary,
                 axes: Sequence[int] | None = None):
        self._k = k
        self._axes = tuple(axes) if axes is not None else tuple(range(a.ndim))
        self._p = Boundary(boundary).pad(a, k, axes=self._axes)
        self._shape = a.shape

    def __call__(self, *offsets: int) -> jnp.ndarray:
        if len(offsets) != len(self._axes):
            raise ValueError(
                f"expected {len(self._axes)} offsets, got {len(offsets)}")
        if any(abs(o) > self._k for o in offsets):
            raise ValueError(f"offset out of stencil radius k={self._k}")
        idx = [slice(None)] * self._p.ndim
        for ax, off in zip(self._axes, offsets):
            start = self._k + off
            idx[ax] = slice(start, start + self._shape[ax])
        return self._p[tuple(idx)]

    @property
    def center(self) -> jnp.ndarray:
        return self(*([0] * len(self._axes)))


def stencil_taps(f: Callable[[TapAccessor], jnp.ndarray], a: jnp.ndarray,
                 k: int, boundary: Boundary | str = Boundary.ZERO,
                 axes: Sequence[int] | None = None) -> jnp.ndarray:
    """Apply a tap-style elemental function.  ``f(get) -> new array``."""
    return f(TapAccessor(a, k, Boundary(boundary), axes))


def stencil_windows(f: Callable[[jnp.ndarray], jnp.ndarray], a: jnp.ndarray,
                    k: int, boundary: Boundary | str = Boundary.ZERO
                    ) -> jnp.ndarray:
    """Apply a window-style elemental function (materialised σ_k)."""
    return f(neighborhoods(a, k, Boundary(boundary)))


def stencil_indexed(f: Callable, a: jnp.ndarray, k: int,
                    boundary: Boundary | str = Boundary.ZERO) -> jnp.ndarray:
    """-i variant: f receives (windows, absolute-index tensor) — σ̄_k."""
    from .semantics import indexed_neighborhoods
    w, idx = indexed_neighborhoods(a, k, Boundary(boundary))
    return f(w, idx)


def conv_taps(weights: jnp.ndarray,
              boundary: Boundary | str = Boundary.ZERO) -> Callable:
    """Build a tap-style linear-stencil elemental function from a weight
    window of shape (2k+1,)*n — the convolution special case."""
    win = weights.shape[0]
    k = (win - 1) // 2
    n = weights.ndim

    def f(get: TapAccessor):
        import itertools
        acc = None
        for offs in itertools.product(range(win), repeat=n):
            wv = weights[offs]
            term = get(*[o - k for o in offs]) * wv
            acc = term if acc is None else acc + term
        return acc

    f.k = k  # type: ignore[attr-defined]
    return f
