"""/(⊕) — parallel reduce, and the paper's two-phase device reduce.

The paper realises reduce as "a sequence of partial GPU-side reduces,
followed by a global host-side reduce" (§1) and fuses the first partial
reduce into the stencil kernel (§3.3, ``stencil<SUM_kernel,MF_kernel>``).
On TPU the same structure appears as: per-tile partials inside the Pallas
kernel (or per-shard partials inside shard_map), then a tiny final combine —
here :func:`tree_reduce` / :func:`two_phase_reduce` — that XLA keeps on
device (stronger than the paper's host-side final reduce).
"""
from __future__ import annotations

import dataclasses
import operator
from typing import Callable

import jax
import jax.numpy as jnp

from .spans import scope

# Named monoids usable across the codebase (op, identity).
MONOIDS = {
    "sum": (operator.add, 0.0),
    "prod": (operator.mul, 1.0),
    "max": (jnp.maximum, -jnp.inf),
    "min": (jnp.minimum, jnp.inf),
    "any": (jnp.logical_or, False),
    "all": (jnp.logical_and, True),
}


def resolve_monoid(op, identity):
    """Accept either a named monoid ('sum') or an (op, identity) pair."""
    if isinstance(op, str):
        return MONOIDS[op]
    if identity is None:
        raise ValueError("identity required for custom combinator")
    return op, identity


@scope("global_reduce")
def collective_combine(op: Callable, r: jnp.ndarray,
                       axis_names) -> jnp.ndarray:
    """Monoid-aware global combine of per-shard partials over mesh axes.

    The cross-device phase of the paper's two-phase reduce: every shard
    contributes its local fold and every shard receives the identical
    global value, so a convergence condition evaluated per-shard agrees
    everywhere (no host in the loop).  Named monoids map onto the native
    collective (``psum``/``pmax``/``pmin``); ``any``/``all`` go through a
    psum of indicator counts; other associative ops must be psum-compatible
    (i.e. ``op`` must *be* addition-like) — there is no generic
    all-reduce for arbitrary combinators on the mesh.  The collectives
    and the NaN re-propagation are the device scope
    ``repro.global_reduce``.
    """
    from jax import lax
    for name in axis_names:
        if op is jnp.maximum or op is jnp.minimum:
            # XLA's all-reduce max/min DROP NaN (unlike jnp.maximum),
            # which would silently un-poison a ⊥=NaN convergence measure
            # on exactly one deployment — re-propagate it explicitly so
            # every shard sees the same (possibly NaN) value.
            coll = lax.pmax(r, name) if op is jnp.maximum \
                else lax.pmin(r, name)
            if jnp.issubdtype(r.dtype, jnp.floating):
                nanq = lax.psum(jnp.isnan(r).astype(jnp.float32), name)
                coll = jnp.where(nanq > 0,
                                 jnp.asarray(jnp.nan, coll.dtype), coll)
            r = coll
        elif op in (jnp.logical_or, jnp.logical_and):
            rf = lax.psum(r.astype(jnp.float32), name)
            r = (rf > 0) if op is jnp.logical_or else (
                rf >= lax.psum(1.0, name))
        else:
            r = lax.psum(r, name)
    return r


# ---------------------------------------------------------------------------
# Convergence sentinels — the per-lane health word.
#
# The fused delta-reduce already computes one scalar per lane per sweep to
# drive the convergence condition; the sentinel reads THAT value (zero
# extra passes over the grid) and folds what it sees into a packed int32
# health word carried alongside (r, it, done):
#
#     bits 0..15   stall counter — consecutive sweeps whose reduce value
#                  failed to decrease (the divergence detector's memory)
#     bit  16      CONVERGED — the condition c fired for this lane
#     bit  17      POISONED — the reduce value went NaN/Inf
#     bit  18      DIVERGED — the stall counter hit the sentinel patience
#
# POISONED/DIVERGED quarantine the lane: the driver masks it done so it
# stops spinning (and, in the composed deployment, stops feeding the
# step-aligned ghost exchange with sweeps nobody needs).  A lane that
# hits max_iters with neither CONVERGED nor a fault bit reads as
# nonconverged — budget exhaustion needs no bit of its own.
# ---------------------------------------------------------------------------

HEALTH_STALL_MASK = (1 << 16) - 1
HEALTH_CONVERGED = 1 << 16
HEALTH_POISONED = 1 << 17
HEALTH_DIVERGED = 1 << 18

STATUS_OK = "ok"
STATUS_NONCONVERGED = "nonconverged"
STATUS_POISONED = "poisoned"


@dataclasses.dataclass(frozen=True)
class Sentinel:
    """Per-lane health policy riding the fused reduce.

    ``nan``       — poison a lane whose reduce value goes non-finite
                    (float reduce dtypes only; a bool/any-monoid reduce
                    has nothing to poison).
    ``patience``  — quarantine a lane whose reduce value has not
                    DECREASED for this many consecutive condition checks
                    (0 disables the divergence detector; leave it off
                    for oscillating but convergent measures).
    """
    nan: bool = True
    patience: int = 0


def health_update(hw, r_new, r_prev, live, converged, it, sentinel):
    """One sentinel step: fold this check's reduce value into the packed
    per-lane health words.  All inputs are (lanes,) vectors except
    ``sentinel`` (static) — jit/vmap/shard_map-safe, no collectives.

    Returns ``(hw', quarantine)`` where ``quarantine`` marks lanes the
    driver must mask done NOW (poisoned or diverged) — distinct from
    CONVERGED, which the driver's own done-mask already handles.
    """
    hw = jnp.asarray(hw, jnp.int32)
    stall = jnp.bitwise_and(hw, HEALTH_STALL_MASK)
    flags = hw - stall
    floatlike = jnp.issubdtype(jnp.asarray(r_new).dtype, jnp.floating)
    if sentinel is not None and sentinel.nan and floatlike:
        poison = jnp.logical_and(live, ~jnp.isfinite(r_new))
    else:
        poison = jnp.zeros(hw.shape, bool)
    if sentinel is not None and sentinel.patience > 0 and floatlike:
        # "non-decreasing" against the previous CHECK's value; the first
        # check compares against the identity element, which is not a
        # real iterate — let it pass
        stalled = jnp.logical_and(live,
                                  jnp.logical_and(it > 0, r_new >= r_prev))
        stall = jnp.where(live, jnp.where(stalled, stall + 1, 0), stall)
        diverged = stall >= sentinel.patience
    else:
        diverged = jnp.zeros(hw.shape, bool)
    flags = jnp.where(jnp.logical_and(live, converged),
                      jnp.bitwise_or(flags, HEALTH_CONVERGED), flags)
    flags = jnp.where(poison, jnp.bitwise_or(flags, HEALTH_POISONED),
                      flags)
    flags = jnp.where(diverged, jnp.bitwise_or(flags, HEALTH_DIVERGED),
                      flags)
    quarantine = jnp.logical_and(live, jnp.logical_or(poison, diverged))
    return jnp.bitwise_or(flags, stall), quarantine


def health_status(hw) -> str:
    """Host-side status taxonomy of one packed health word.  Poison wins
    over everything (a NaN result is never 'ok' however the condition
    read it); a clean CONVERGED bit is the only path to 'ok'."""
    hw = int(hw)
    if hw & HEALTH_POISONED:
        return STATUS_POISONED
    if hw & HEALTH_DIVERGED:
        return STATUS_NONCONVERGED
    if hw & HEALTH_CONVERGED:
        return STATUS_OK
    return STATUS_NONCONVERGED


_NATIVE_FOLDS = {operator.add: jnp.sum, operator.mul: jnp.prod,
                 jnp.maximum: jnp.max, jnp.minimum: jnp.min,
                 jnp.logical_or: jnp.any, jnp.logical_and: jnp.all}


def tree_reduce(op: Callable, a: jnp.ndarray, identity) -> jnp.ndarray:
    """Balanced-tree fold of the associative ⊕ over all items of ``a``.

    The named monoids lower to XLA's native reduction (itself a tree
    over the device's tiles).  Any other ⊕ folds pairwise in O(log n)
    vectorised ops — identical result structure to the paper's reduction
    tree and to :func:`repro.core.semantics.reduce_all`.  The strided
    halving of a flat array is layout-hostile on the TPU (seconds per
    call at 16384²), so it is kept for the combinators XLA has no
    reduction for.
    """
    native = _NATIVE_FOLDS.get(op)
    if native is not None:
        return native(a) if a.size else jnp.asarray(identity, a.dtype)
    flat = a.reshape(-1)
    n = flat.shape[0]
    size = 1 if n == 0 else 1 << (n - 1).bit_length()
    if size != n:
        flat = jnp.concatenate(
            [flat, jnp.full((size - n,), identity, dtype=flat.dtype)])
    while flat.shape[0] > 1:
        flat = op(flat[0::2], flat[1::2])
    return flat[0]


def two_phase_reduce(op: Callable, a: jnp.ndarray, identity,
                     tile: int = 4096) -> jnp.ndarray:
    """Paper's two-phase reduce: tile partials then final combine.

    Phase 1 mirrors the device-side partial reduce (each tile folds
    locally); phase 2 is the small final reduce.  Extensionally equal to
    :func:`tree_reduce` for associative+commutative ⊕.
    """
    flat = a.reshape(-1)
    n = flat.shape[0]
    ntiles = max(1, -(-n // tile))
    size = ntiles * tile
    if size != n:
        flat = jnp.concatenate(
            [flat, jnp.full((size - n,), identity, dtype=flat.dtype)])
    partials = flat.reshape(ntiles, tile)
    # phase 1: per-tile fold (vectorised across tiles)
    while partials.shape[1] > 1:
        half = partials.shape[1] // 2
        partials = op(partials[:, :half], partials[:, half:])
    # phase 2: final combine of the ntiles partials
    return tree_reduce(op, partials[:, 0], identity)
