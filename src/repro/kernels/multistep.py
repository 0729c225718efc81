"""Temporal-blocking stencil kernel: T iterations per VMEM residency.

Beyond-paper kernel optimisation for the memory-bound iterative stencil:
the single-step kernel moves the whole grid HBM↔VMEM once per iteration
(arithmetic intensity of a 5-point f32 Jacobi ≈ 4 FLOPs / 8 bytes → far
below the v5e ridge point of ~240 FLOPs/byte).  Temporal blocking loads
a tile-aligned (bm + 2·r0, bn + 2·c0) window once (the frame margin,
:mod:`repro.core.frames`), cuts its (bm + 2kT, bn + 2kT) halo region out
in VMEM and applies T sweeps there, shrinking the valid region by k per
side per sweep:

    HBM traffic/iter ≈ ((bm+2kT)(bn+2kT)/T + bm·bn/T) · bytes   (≈ ÷T)
    redundant compute ≈ ((bm+2kT)(bn+2kT)/(bm·bn) − 1)          (~13%
    at bm=bn=256, k=1, T=8)

Boundary (⊥) correctness: at global edges the ghost values must match the
boundary model of the *current* internal iterate after EVERY sweep (a
pre-padded initial window alone would let ghost values evolve freely).
Per model:

* ``zero`` / ``nan`` — re-assert the constant on out-of-domain cells
  (cheap ``where`` over the shrinking window);
* ``reflect`` — mirror the just-computed interior back onto the ghost
  cells.  The mirror source always lies inside the current window (depth-d
  ghost mirrors depth-d interior), realised as a static 2d-cell roll per
  depth d — only the k·(sweeps left) ghost cells a domain cell can still
  reach — with no flip or gather (neither lowers on the TPU);
* ``wrap`` — nothing per-sweep: a wrapped ghost ring is a patch of the
  torus, so ghost cells evolve *exactly* like their pre-images and the
  shrinking-window containment argument applies unchanged.  (Requires the
  frame's ghost ring and the env frames to be wrap-filled, which
  :func:`repro.core.frames.refresh_frame` / ``frame_env`` provide.)

``env`` tiles (the paper Fig. 2 read-only fields) are DMA'd as halo
windows alongside the state — intermediate sweeps evaluate f on a region
wider than the output tile, so env must cover the shrinking window at
every step.  Input DMA is double-buffered (revolving windows) like the
single-step kernel; the convergence reduce is fused and evaluated on the
final sweep only — semantically the pattern's ``unroll`` option (checks
every T iterations).

Validated against T× :func:`repro.core.stencil.stencil_taps` in
tests/kernels/test_multistep.py.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.frames import frame_spec, make_frame, frame_env, unframe
from repro.core.reduce import resolve_monoid
from .stencil2d import (ACC_TILE, decode_acc, grid_step, hbm_at,
                        lane_batched, reduce_epilogue, revolving_fetch,
                        tile_coords, tile_spec)


def _fix_boundary(cur, row_base, col_base, *, bounds, boundary, depth):
    """Re-assert ⊥ on out-of-domain cells of an internal sweep output.

    ``cur`` holds the sweep output whose [0, 0] cell sits at frame
    coordinates (row_base, col_base) — traced, tile-dependent.  The
    GLOBAL domain occupies frame rows [row_lo, row_hi) × cols
    [col_lo, col_hi), given by ``bounds`` — static ints on the
    single-device path, traced scalars (read from SMEM) on the sharded
    path, where interior shards carry ±2^30 sentinels so no cell is ever
    "outside" (their ghost cells are real neighbour cells and must evolve
    freely).  ``depth`` is how far outside the domain a ghost can still
    reach a domain cell in the sweeps left (k per sweep); deeper cells
    are inert.
    """
    if boundary == "wrap":
        return cur                      # torus continuation is exact
    row_lo, row_hi, col_lo, col_hi = bounds
    L, W = cur.shape
    rows = row_base + jax.lax.broadcasted_iota(jnp.int32, (L, W), 0)
    cols = col_base + jax.lax.broadcasted_iota(jnp.int32, (L, W), 1)
    if boundary in ("zero", "nan"):
        inside = ((rows >= row_lo) & (rows < row_hi)
                  & (cols >= col_lo) & (cols < col_hi))
        fill = jnp.asarray(0.0 if boundary == "zero" else jnp.nan, cur.dtype)
        return jnp.where(inside, cur, fill)
    if boundary != "reflect":
        raise ValueError(boundary)
    # reflect: a ghost e cells outside an edge mirrors the domain cell e
    # inside it (jnp.pad 'reflect', no edge repeat) — a static shift by
    # 2e along the axis, selected where the ghost sits.  Rows first, then
    # columns over the row-fixed values, so corners compose like jnp.pad.
    for e in range(1, depth + 1):
        cur = jnp.where(rows == row_lo - e, jnp.roll(cur, -2 * e, 0), cur)
        cur = jnp.where(rows == row_hi - 1 + e, jnp.roll(cur, 2 * e, 0),
                        cur)
    for e in range(1, depth + 1):
        cur = jnp.where(cols == col_lo - e, jnp.roll(cur, -2 * e, 1), cur)
        cur = jnp.where(cols == col_hi - 1 + e, jnp.roll(cur, 2 * e, 1),
                        cur)
    return cur


def _ms_kernel(x_hbm, *rest, f, measure, op, identity, k, T, origin, bm,
               bn, gm, gn, lanes, m, n, acc_dtype, boundary, n_env,
               double_buffer, has_bounds):
    env_hbm = rest[:n_env]
    pos = n_env
    if has_bounds:
        bounds_ref = rest[pos]
        pos += 1
    o_hbm, acc_ref, win, wsem = rest[pos:pos + 4]
    tail = rest[pos + 4:]
    ewins = tail[:n_env]
    esem = tail[n_env] if n_env else None
    ostage, osem = tail[-2:]
    r0, c0 = origin
    if has_bounds:
        bounds = (bounds_ref[0, 0], bounds_ref[0, 1],
                  bounds_ref[0, 2], bounds_ref[0, 3])
    else:
        bounds = (r0, r0 + m, c0, c0 + n)

    l, i, j, t = grid_step(lanes, gm, gn)
    pad = k * T
    wm, wn = bm + 2 * r0, bn + 2 * c0          # the aligned DMA window
    hm, hn = bm + 2 * pad, bn + 2 * pad        # its halo region
    halo = (slice(r0 - pad, r0 - pad + hm), slice(c0 - pad, c0 - pad + hn))

    def window_copies(s, slot):
        sl, si, sj = tile_coords(s, lanes, gm, gn)
        rows, cols = pl.ds(si * bm, wm), pl.ds(sj * bn, wn)
        cps = [pltpu.make_async_copy(hbm_at(x_hbm, sl, rows, cols),
                                     win.at[slot], wsem.at[slot])]
        for e in range(n_env):
            cps.append(pltpu.make_async_copy(
                hbm_at(env_hbm[e], sl, rows, cols),
                ewins[e].at[slot], esem.at[slot, e]))
        return cps

    slot = revolving_fetch(t, (lanes or 1) * gm * gn, window_copies,
                           double_buffer)
    cur = win[slot][halo]
    env_halos = [ewins[e][slot][halo] for e in range(n_env)]
    # frame coordinates of the halo region's cell (0, 0)
    row0, col0 = i * bm + r0 - pad, j * bn + c0 - pad
    prev_center = None
    for step in range(T):
        size_m = hm - 2 * k * (step + 1)
        size_n = hn - 2 * k * (step + 1)
        if step == T - 1:
            prev_center = cur[k:k + size_m, k:k + size_n]
        taps = _ShrinkTaps(cur, k, size_m, size_n)
        off = k * (step + 1)            # halo-local origin of this sweep
        envs = [e[off:off + size_m, off:off + size_n] for e in env_halos]
        new = f(taps, *envs)
        cur = _fix_boundary(
            new, row0 + off, col0 + off, bounds=bounds, boundary=boundary,
            depth=k * (T - 1 - step)).astype(cur.dtype)

    ostage[...] = cur.astype(ostage.dtype)    # (bm, bn) after T shrinks
    wr = pltpu.make_async_copy(
        ostage, hbm_at(o_hbm, l, pl.ds(r0 + i * bm, bm),
                       pl.ds(c0 + j * bn, bn)), osem)
    wr.start()
    wr.wait()

    reduce_epilogue(acc_ref, cur, prev_center, measure=measure, op=op,
                    identity=identity, i=i, j=j, bm=bm, bn=bn, m=m, n=n,
                    acc_dtype=acc_dtype)


class _ShrinkTaps:
    """Taps over the current (size+2k) window, producing (size) output."""

    def __init__(self, arr, k, size_m, size_n):
        self._a, self._k, self._m, self._n = arr, k, size_m, size_n

    def __call__(self, di, dj):
        k = self._k
        return self._a[k + di:k + di + self._m, k + dj:k + dj + self._n]

    @property
    def center(self):
        return self(0, 0)


def stencil2d_multistep_framed(frame: jnp.ndarray, f: Callable, spec, *,
                               T: int, env_framed=(), combine="sum",
                               identity=None,
                               measure: Optional[Callable] = None,
                               boundary: str = "zero",
                               domain_bounds=None,
                               acc_dtype=jnp.float32,
                               double_buffer: bool = True,
                               interpret: bool = False):
    """T fused sweeps on a persistent halo frame — frame in, frame out.

    ``spec`` must have ``pad == k*T``; ``env_framed`` are full-frame fields
    (``frame_env(..., halo=True)``).  Returns ``(new_frame, reduced)``
    with the reduce taken over ``measure(last, second-last)`` on the final
    sweep only.  Like the single-step framed kernel, the output ghost ring
    is left for the caller's ``refresh_frame``.

    ``domain_bounds`` (optional, (1, 4) int32, possibly traced) overrides
    where the per-sweep ⊥ re-assertion sees the GLOBAL domain edge in
    frame coordinates — the sharded deployment passes per-shard bounds
    (sentinels on interior sides) through SMEM; None keeps the
    single-device static bounds.
    """
    op, ident = resolve_monoid(combine, identity)
    k, bm, bn, gm, gn = spec.k, spec.bm, spec.bn, spec.gm, spec.gn
    assert spec.pad == k * T, (spec.pad, k, T)
    nbuf = 2 if double_buffer else 1
    r0, c0 = spec.origin
    wm, wn = bm + 2 * r0, bn + 2 * c0
    n_env = len(env_framed)
    has_bounds = domain_bounds is not None

    scratch = [pltpu.VMEM((nbuf, wm, wn), frame.dtype),
               pltpu.SemaphoreType.DMA((nbuf,))]
    scratch += [pltpu.VMEM((nbuf, wm, wn), e.dtype) for e in env_framed]
    if n_env:
        scratch.append(pltpu.SemaphoreType.DMA((nbuf, n_env)))
    scratch += [pltpu.VMEM((bm, bn), frame.dtype), pltpu.SemaphoreType.DMA]
    shared = []                       # operands every lane reads
    if has_bounds:
        shared.append(jnp.asarray(domain_bounds, jnp.int32))

    def call(lanes, frame, *rest):
        kernel = functools.partial(
            _ms_kernel, f=f, measure=measure, op=op, identity=ident, k=k,
            T=T, origin=spec.origin, bm=bm, bn=bn, gm=gm, gn=gn,
            lanes=lanes, m=spec.m, n=spec.n, acc_dtype=acc_dtype,
            boundary=boundary, n_env=n_env, double_buffer=double_buffer,
            has_bounds=has_bounds)
        stack = () if lanes is None else (lanes,)
        in_specs = [pl.BlockSpec(memory_space=pl.ANY)] * (1 + n_env)
        if has_bounds:
            in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        return pl.pallas_call(
            kernel,
            grid=(*stack, gm, gn),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                       tile_spec(lanes, ACC_TILE, lambda i, j: (0, 0))],
            out_shape=[jax.ShapeDtypeStruct(frame.shape, frame.dtype),
                       jax.ShapeDtypeStruct((*stack, *ACC_TILE),
                                            acc_dtype)],
            scratch_shapes=scratch,
            interpret=interpret,
            name="stencil2d_multistep_framed",
        )(frame, *rest)

    out, acc = lane_batched(call, 1 + n_env)(frame, *env_framed, *shared)
    return out, decode_acc(op, acc)


def stencil2d_multistep(a, f, *, env=(), k: int = 1, T: int = 4,
                        combine="sum", identity=None, measure=None,
                        boundary: str = "zero", block=(256, 256),
                        acc_dtype=jnp.float32, double_buffer: bool = True,
                        interpret: bool = False):
    """T fused sweeps per VMEM residency, all four ⊥ models, env tiles.

    Returns (array after T sweeps, /(⊕) of measure(last, second-last)).
    One-shot convenience around :func:`stencil2d_multistep_framed`;
    iterative callers should hold the frame across kernel calls instead —
    see :mod:`repro.core.executor`.
    """
    m, n = a.shape
    spec = frame_spec(m, n, k=k, block=block, sweeps=T)
    frame = make_frame(a, spec, boundary)
    env_framed = tuple(frame_env(e, spec, boundary, halo=True) for e in env)
    out, red = stencil2d_multistep_framed(
        frame, f, spec, T=T, env_framed=env_framed, combine=combine,
        identity=identity, measure=measure, boundary=boundary,
        acc_dtype=acc_dtype, double_buffer=double_buffer,
        interpret=interpret)
    return unframe(out, spec), red
