"""Fused stencil + partial-reduce Pallas TPU kernel (the paper's §3.3 core).

The paper fuses the stencil elemental function with the first (device-side)
phase of the reduce into one kernel — ``stencil<SUM_kernel, MF_kernel>`` —
so the convergence measure costs no extra memory pass.  TPU-native
re-thinking of that design:

* the global grid lives in HBM as a *persistent halo frame*
  (:mod:`repro.core.frames`): a (gm·bm + 2·r0, gn·bn + 2·c0) array whose
  k-deep ghost ring, inside an (8, 128)-aligned margin, realises ⊥.  Each
  grid step DMAs its margin-extended (bm + 2·r0, bn + 2·c0) window into
  VMEM with an explicit async copy (``pltpu.make_async_copy``)
  — the HBM→VMEM tier replaces the paper's global→local OpenCL memory
  staging, and the halo comes from the frame rather than inter-work-group
  synchronisation;
* the elemental function runs on the VPU/MXU over the whole VMEM tile
  (data-oriented, vectorised — not thread-oriented as in OpenCL);
* the output tile is staged in VMEM and DMA'd back **into the same frame
  layout**, so the frame is a fixed-point type: iterating the kernel needs
  no per-iteration ``jnp.pad``/slice (two full-grid HBM passes saved on an
  already memory-bound kernel) — only the O(m+n) ghost refresh between
  sweeps (:func:`repro.core.frames.refresh_frame`);
* the per-tile partial reduce accumulates in an (8, 128) VMEM output block
  carried across the **sequential TPU grid** (BlockSpec pinned to (0,0);
  every cell holds the running value, since Mosaic stores no scalars to
  VMEM) — phase one of the paper's two-phase reduce.  The wrapper reads
  one cell back and stays on device;
* optional **double-buffered DMA** (revolving windows) overlaps the next
  tile's copy with the current tile's compute — the TPU analogue of the
  paper's asynchronous H2D/D2H overlap via OpenCL events.

:func:`stencil2d_fused` keeps the one-shot (m, n) → (m, n) contract by
framing/unframing around one sweep; :func:`stencil2d_fused_framed` is the
zero-copy entry point the persistent engine (:mod:`repro.core.executor`)
iterates inside ``lax.while_loop``.

Validated in interpret mode against :mod:`repro.kernels.ref` (which is built
on :mod:`repro.core.stencil`, itself property-tested against the formal
semantics).
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


ACC_TILE = (8, 128)   # one vreg: the partial-reduce accumulator block


class KernelTaps:
    """Tap accessor over the margin-extended VMEM window (kernel-side twin
    of :class:`repro.core.stencil.TapAccessor`); the (bm, bn) output tile
    sits at window offset ``origin``."""

    def __init__(self, win, origin: tuple[int, int], bm: int, bn: int):
        self._w, self._o, self._bm, self._bn = win, origin, bm, bn

    def __call__(self, di: int, dj: int):
        (r0, c0), bm, bn = self._o, self._bm, self._bn
        return self._w[r0 + di:r0 + di + bm, c0 + dj:c0 + dj + bn]

    @property
    def center(self):
        return self(0, 0)


def tile_coords(s, lanes, gm, gn):
    """Decode a linear (lane-major) tile index into (lane, i, j); lane is
    None for an unbatched (``lanes=None``) kernel."""
    lane = None if lanes is None else s // (gm * gn)
    return lane, (s // gn) % gm, s % gn


def grid_step(lanes, gm, gn):
    """``(lane, i, j, t)`` of the current grid step, ``t`` linear.  The
    lane-batched kernels run a (lanes, gm, gn) grid; unbatched ones a
    (gm, gn) grid, with ``lane`` None."""
    if lanes is None:
        i, j = pl.program_id(0), pl.program_id(1)
        return None, i, j, i * gn + j
    l, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    return l, i, j, (l * gm + i) * gn + j


def hbm_at(ref, lane, rows, cols):
    """Window of an HBM frame (lane-stacked when ``lane`` is not None)."""
    return ref.at[rows, cols] if lane is None else ref.at[lane, rows, cols]


def tile_spec(lanes, block, index):
    """VMEM BlockSpec for one (lane, i, j) grid step; ``index(i, j)``
    names the block within one lane."""
    if lanes is None:
        return pl.BlockSpec(block, lambda i, j: index(i, j))
    return pl.BlockSpec((None, *block), lambda l, i, j: (l, *index(i, j)))


def revolving_fetch(t, tiles, make_copies, double_buffer):
    """Bring linear tile ``t``'s windows into VMEM; return the slot they
    landed in.  ``make_copies(s, slot)`` builds the async-copy list for
    linear tile ``s``.  With double buffering the next tile's copies —
    across lane boundaries too — are kicked off into the other slot
    before waiting on the current one (revolving windows over the
    sequential TPU grid).  Shared by the single-step and temporal-blocking
    kernels."""
    if double_buffer:
        # first tile of the whole grid: kick off slot 0
        @pl.when(t == 0)
        def _():
            for cp in make_copies(t, 0):
                cp.start()
        # prefetch the next tile into the other slot
        nt = t + 1

        @pl.when(nt < tiles)
        def _():
            for cp in make_copies(nt, nt % 2):
                cp.start()
        for cp in make_copies(t, t % 2):
            cp.wait()
        return t % 2
    cps = make_copies(t, 0)
    for cp in cps:
        cp.start()
    for cp in cps:
        cp.wait()
    return 0


def lane_batched(call, n_lane_args: int):
    """Make a Pallas kernel wrapper vmappable on the TPU.

    ``call(lanes, *args)`` runs the kernel on unbatched operands
    (``lanes=None``) or on lane-stacked ones (``lanes=L``, a leading axis
    of size L on the first ``n_lane_args`` operands; the rest are shared
    by every lane).  Mosaic cannot lower the generic Pallas batching rule
    for HBM (``pl.ANY``) operands, so ``vmap`` is routed to the kernel's
    own lane grid instead.
    """
    @jax.custom_batching.custom_vmap
    def kernel(*args):
        return call(None, *args)

    @kernel.def_vmap
    def _(axis_size, in_batched, *args):
        if any(in_batched[n_lane_args:]):
            raise NotImplementedError(
                "kernel operands shared by every lane cannot be batched")
        lane = [a if b else jnp.broadcast_to(a, (axis_size, *a.shape))
                for a, b in zip(args[:n_lane_args], in_batched)]
        return call(axis_size, *lane, *args[n_lane_args:]), (True, True)

    return kernel


def reduce_epilogue(acc_ref, new, prev_center, *, measure, op, identity,
                    i, j, bm, bn, m, n, acc_dtype, do_reduce=True):
    """Fused per-tile partial reduce (phase 1 of the paper's two-phase
    reduce), accumulated across the sequential grid into the
    :data:`ACC_TILE` block ``acc_ref`` (the tile's scalar is broadcast,
    so every cell carries the running value).  Cells beyond the (m, n)
    domain (block round-up) fold as ⊕'s identity.  ``do_reduce=False``
    only initialises the accumulator — used on intermediate unrolled
    sweeps, where the condition is not checked.  The accumulator starts
    at each lane's first tile."""
    # bool monoids accumulate as {0,1} indicators in acc_dtype (or ≡ max,
    # and ≡ min on {0,1}); decode_acc turns the result back into a bool
    if op is jnp.logical_or:
        op = jnp.maximum
    elif op is jnp.logical_and:
        op = jnp.minimum
    ident = jnp.asarray(identity, acc_dtype)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _():
        acc_ref[...] = jnp.full(acc_ref.shape, ident, acc_dtype)
    if not do_reduce:
        return
    meas = measure(new, prev_center) if measure is not None else new
    rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
    cols = j * bn + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
    valid = (rows < m) & (cols < n)
    meas = jnp.where(valid, meas.astype(acc_dtype), ident)
    acc_ref[...] = op(acc_ref[...], _tile_fold(op, meas, identity, acc_dtype))


def decode_acc(op, acc):
    """Read the reduced scalar out of the kernel's accumulator block and
    map it back to the monoid's carrier (bool monoids ride through VMEM
    as {0,1} indicators)."""
    red = acc[0, 0]
    if op in (jnp.logical_or, jnp.logical_and):
        return red >= 0.5
    return red


def _stencil_kernel(x_hbm, *rest, f, measure, op, identity, origin, bm,
                    bn, gm, gn, lanes, m, n, acc_dtype, double_buffer, n_env,
                    do_reduce):
    env = rest[:n_env]            # per-cell read-only fields (paper's `env`)
    o_hbm, acc_ref, win, wsem, ostage, osem = rest[n_env:]
    l, i, j, t = grid_step(lanes, gm, gn)
    r0, c0 = origin
    wm, wn = bm + 2 * r0, bn + 2 * c0

    def window_copies(s, slot):
        sl, si, sj = tile_coords(s, lanes, gm, gn)
        return [pltpu.make_async_copy(
            hbm_at(x_hbm, sl, pl.ds(si * bm, wm), pl.ds(sj * bn, wn)),
            win.at[slot], wsem.at[slot])]

    slot = revolving_fetch(t, (lanes or 1) * gm * gn, window_copies,
                           double_buffer)
    taps = KernelTaps(win[slot], origin, bm, bn)
    new = f(taps, *[e[...] for e in env])

    # write the tile back into the frame layout (ghost ring untouched —
    # the engine's O(m+n) refresh re-asserts it between sweeps)
    ostage[...] = new.astype(ostage.dtype)
    wr = pltpu.make_async_copy(
        ostage, hbm_at(o_hbm, l, pl.ds(r0 + i * bm, bm),
                       pl.ds(c0 + j * bn, bn)), osem)
    wr.start()
    wr.wait()

    reduce_epilogue(acc_ref, new, taps.center, measure=measure, op=op,
                    identity=identity, i=i, j=j, bm=bm, bn=bn, m=m, n=n,
                    acc_dtype=acc_dtype, do_reduce=do_reduce)


def _tile_fold(op, x2d, identity, acc_dtype):
    """Fold a 2-D VMEM tile down to a scalar (VPU-friendly fast paths)."""
    if op is jnp.maximum:
        return jnp.max(x2d)
    if op is jnp.minimum:
        return jnp.min(x2d)
    import operator
    if op is operator.add:
        return jnp.sum(x2d)
    if op is operator.mul:
        return jnp.prod(x2d)
    # generic associative combinator: balanced tree over the flat tile
    flat = x2d.reshape(-1)
    n = flat.shape[0]
    size = 1 << (n - 1).bit_length()
    if size != n:
        flat = jnp.concatenate(
            [flat, jnp.full((size - n,), identity, acc_dtype)])
    while flat.shape[0] > 1:
        flat = op(flat[0::2], flat[1::2])
    return flat[0]


def stencil2d_fused_framed(frame: jnp.ndarray, f: Callable, spec, *,
                           env_framed=(), combine="sum", identity=None,
                           measure: Optional[Callable] = None,
                           acc_dtype=jnp.float32, double_buffer: bool = True,
                           do_reduce: bool = True, interpret: bool = False):
    """One fused sweep on a persistent halo frame — frame in, frame out.

    ``frame`` has the layout of ``spec`` (:func:`repro.core.frames.
    frame_spec` with ``sweeps=1``); ``env_framed`` are block-rounded
    interior-only fields (:func:`repro.core.frames.frame_env`).  Returns
    ``(new_frame, reduced_scalar)``; the new frame's ghost ring is
    *unrefreshed* — callers re-assert it with ``refresh_frame`` before the
    next sweep.  No full-grid pad or slice happens here: this is the
    zero-copy loop body.

    ``do_reduce=False`` skips the fused measure+fold (the scalar returned
    is just ⊕'s identity) — used by the engine on intermediate unrolled
    sweeps, where the condition is not checked and the reduce would be
    wasted work.
    """
    op, ident = resolve_monoid(combine, identity)
    bm, bn, gm, gn = spec.bm, spec.bn, spec.gm, spec.gn
    (r0, c0), nbuf = spec.origin, 2 if double_buffer else 1

    def call(lanes, frame, *env_framed):
        kernel = functools.partial(
            _stencil_kernel, f=f, measure=measure, op=op, identity=ident,
            origin=spec.origin, bm=bm, bn=bn, gm=gm, gn=gn, lanes=lanes,
            m=spec.m, n=spec.n, acc_dtype=acc_dtype,
            double_buffer=double_buffer, n_env=len(env_framed),
            do_reduce=do_reduce)
        stack = () if lanes is None else (lanes,)
        return pl.pallas_call(
            kernel,
            grid=(*stack, gm, gn),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)]
            + [tile_spec(lanes, (bm, bn), lambda i, j: (i, j))
               for _ in env_framed],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                       tile_spec(lanes, ACC_TILE, lambda i, j: (0, 0))],
            out_shape=[jax.ShapeDtypeStruct(frame.shape, frame.dtype),
                       jax.ShapeDtypeStruct((*stack, *ACC_TILE),
                                            acc_dtype)],
            scratch_shapes=[pltpu.VMEM((nbuf, bm + 2 * r0, bn + 2 * c0),
                                       frame.dtype),
                            pltpu.SemaphoreType.DMA((nbuf,)),
                            pltpu.VMEM((bm, bn), frame.dtype),
                            pltpu.SemaphoreType.DMA],
            interpret=interpret,
            name="stencil2d_fused_framed",
        )(frame, *env_framed)

    n_lane = 1 + len(env_framed)
    out, acc = lane_batched(call, n_lane)(frame, *env_framed)
    return out, decode_acc(op, acc)


def stencil2d_fused(a: jnp.ndarray, f: Callable, *, env=(), k: int = 1,
                    combine="sum", identity=None,
                    measure: Optional[Callable] = None,
                    boundary: str = "zero",
                    block: tuple[int, int] = (256, 256),
                    acc_dtype=jnp.float32, double_buffer: bool = True,
                    interpret: bool = False):
    """One fused stencil+partial-reduce sweep over a 2-D array.

    Returns ``(new_array, reduced_scalar)`` where the scalar is
    ``/(⊕) : measure(new, old_center)`` (or of ``new`` when measure is None).

    ``f`` is a taps-style elemental function ``f(get, *env_tiles)`` (same
    protocol as :func:`repro.core.stencil.stencil_taps`, offsets within ±k).
    ``env`` holds per-cell read-only fields (the paper Fig. 2 ``env``
    argument — e.g. the Helmholtz forcing matrix, the restoration
    observation+mask); they are tiled like the output, without halo.

    One-shot convenience: frames the input (⊥ padding + block round-up),
    runs :func:`stencil2d_fused_framed` once, and slices the domain back.
    Iterative callers should hold the frame across sweeps instead — see
    :mod:`repro.core.executor`.
    """
    m, n = a.shape
    spec = frame_spec(m, n, k=k, block=block)
    frame = make_frame(a, spec, boundary)
    env_framed = tuple(frame_env(e, spec, boundary) for e in env)
    out, red = stencil2d_fused_framed(
        frame, f, spec, env_framed=env_framed, combine=combine,
        identity=identity, measure=measure, acc_dtype=acc_dtype,
        double_buffer=double_buffer, interpret=interpret)
    return unframe(out, spec), red
