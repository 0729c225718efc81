"""Persistent halo frames — the device-resident grid layout of the engine.

The paper's central performance claim is *device memory persistence*
(§3.3): the grid never leaves device memory between iterations.  The
original realisation still paid two full-grid passes per iteration on the
hot path — a ``jnp.pad`` before every sweep and an ``out[:m, :n]`` slice
after it.  This module hoists both out of the loop by making the *framed*
array the canonical loop-carried representation:

    ┌──────────────────────────────┐
    │ margin (r0 rows, c0 cols)    │   frame shape: (gm·bm + 2·r0,
    │  ┌────────────┬───────────┐  │                 gn·bn + 2·c0)
    │  │ domain     │ round-up  │  │
    │  │ (m, n)     │ (inert)   │  │   domain at [r0:r0+m, c0:c0+n]
    │  ├────────────┴───────────┤  │
    │  │ block round-up (inert) │  │
    │  └────────────────────────┘  │
    └──────────────────────────────┘

The margin holds the ghost ring (``pad = k·T`` deep, right around the
domain) and is rounded up to the TPU's (8, 128) tiling:
``r0 = ceil(pad / 8)·8``, ``c0 = ceil(pad / 128)·128``.  With (8, 128)-
aligned blocks every HBM↔VMEM window the kernels move — the
(bm + 2·r0, bn + 2·c0) input window at (i·bm, j·bn) and the (bm, bn)
output tile at (r0 + i·bm, c0 + j·bn) — then has an aligned shape and an
aligned offset, which Mosaic's DMA requires.  Margin cells outside the
ring are never read by a domain cell's dependency cone.

The frame is built **once** before the ``while_loop`` (:func:`make_frame`),
kernels read and write it directly, and only the ghost ring — O(m+n) edge
cells, not O(mn) — is re-asserted between sweeps (:func:`refresh_frame`).
The domain is sliced back out exactly once after convergence
(:func:`unframe`).

Boundary semantics match ``jnp.pad`` axis-sequential composition (corners
are boundary-of-boundary), which is what :class:`repro.core.stencil.
TapAccessor` and the formal semantics realise — so frames are drop-in for
the per-iteration padding they replace.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .semantics import Boundary
from .spans import scope


TILE = (8, 128)      # TPU (sublane, lane) tiling of a 32-bit array


def ceil_mul(x: int, q: int) -> int:
    """Round ``x`` up to the next multiple of ``q``."""
    return -(-x // q) * q


@dataclasses.dataclass(frozen=True)
class FrameSpec:
    """Static geometry of a persistent halo frame."""

    m: int          # logical domain rows
    n: int          # logical domain cols
    k: int          # stencil radius per sweep
    pad: int        # ghost-ring width (= k·sweeps for temporal blocking)
    bm: int         # tile rows
    bn: int         # tile cols
    gm: int         # grid rows
    gn: int         # grid cols

    @property
    def interior(self) -> tuple[int, int]:
        """Block-rounded interior (domain + round-up)."""
        return self.gm * self.bm, self.gn * self.bn

    @property
    def origin(self) -> tuple[int, int]:
        """Frame coordinates of domain cell (0, 0): the tile-aligned
        margin that holds the ``pad``-deep ghost ring."""
        return ceil_mul(self.pad, TILE[0]), ceil_mul(self.pad, TILE[1])

    @property
    def domain(self) -> tuple[slice, slice]:
        """Index of the (m, n) domain inside the frame."""
        r0, c0 = self.origin
        return slice(r0, r0 + self.m), slice(c0, c0 + self.n)

    @property
    def shape(self) -> tuple[int, int]:
        (mi, ni), (r0, c0) = self.interior, self.origin
        return mi + 2 * r0, ni + 2 * c0


def frame_spec(m: int, n: int, *, k: int = 1, block=(256, 256),
               sweeps: int = 1) -> FrameSpec:
    """Build the frame geometry for an (m, n) domain.

    ``block`` is clipped to the domain and rounded up to the (8, 128)
    tiling, so every window the kernels DMA is tile-aligned; ``sweeps``
    > 1 widens the ghost ring for temporal blocking.
    """
    bm = ceil_mul(min(block[0], m), TILE[0])
    bn = ceil_mul(min(block[1], n), TILE[1])
    gm, gn = -(-m // bm), -(-n // bn)
    pad = k * sweeps
    if pad >= min(m, n):
        raise ValueError(
            f"halo width k*sweeps={pad} must be < min(m, n)={min(m, n)}; "
            f"lower `unroll` or use a larger grid")
    return FrameSpec(m=m, n=n, k=k, pad=pad, bm=bm, bn=bn, gm=gm, gn=gn)


def make_frame(a: jnp.ndarray, spec: FrameSpec,
               boundary: Boundary | str) -> jnp.ndarray:
    """Embed ``a`` into a zero-initialised frame and refresh its ghosts.

    Runs once, before the loop — the only O(mn) staging cost of the
    persistent path.
    """
    frame = jnp.zeros(spec.shape, a.dtype)
    frame = jax.lax.dynamic_update_slice(frame, a, spec.origin)
    return refresh_frame(frame, spec, boundary)


def frame_env(e: jnp.ndarray, spec: FrameSpec, boundary: Boundary | str,
              halo: bool = False) -> jnp.ndarray:
    """Stage a read-only ``env`` field for the frame, once, outside the loop.

    Without ``halo`` the field is block-rounded only (single-step kernels
    evaluate f strictly on interior cells).  With ``halo`` it gets the full
    frame layout — temporal blocking evaluates f on ghost cells too, and
    under a ``wrap`` boundary those evaluations must see the wrapped env
    (for the other models ghost outputs are re-asserted each sweep, so the
    ghost env values are inert and a zero ring suffices).
    """
    mi, ni = spec.interior
    if not halo:
        return jnp.pad(e, ((0, mi - spec.m), (0, ni - spec.n)))
    b = Boundary(boundary)
    return make_frame(e, spec, b if b is Boundary.WRAP else Boundary.ZERO)


def refresh_frame(frame: jnp.ndarray, spec: FrameSpec,
                  boundary: Boundary | str) -> jnp.ndarray:
    """Re-assert the ⊥ ghost ring around the (m, n) domain — O(m+n) cells.

    Row strips are filled from domain rows first, then column strips run
    the ring's full height over the row-refreshed frame, so corners
    compose exactly like ``jnp.pad``'s axis-sequential modes.  Cells
    beyond the ``pad``-deep ring (margin and deep round-up garbage) are
    never read by any domain dependency cone and are left untouched.
    """
    boundary = Boundary(boundary)
    (r0, c0), p = spec.origin, spec.pad
    with scope("ghost_refresh"):
        frame = _refresh_axis_local(frame, spec, 0, boundary,
                                    c0, c0 + spec.n)
        return _refresh_axis_local(frame, spec, 1, boundary,
                                   r0 - p, r0 + spec.m + p)


def unframe(frame: jnp.ndarray, spec: FrameSpec) -> jnp.ndarray:
    """Slice the (m, n) domain back out — once, after convergence."""
    return frame[spec.domain]


# ---------------------------------------------------------------------------
# Lane-stacked frames — the 1:1 streaming deployment of the engine.
#
# A farm of convergence loops shares ONE done-masked while_loop whose
# carry is a stack of frames, one per lane *slot*.  The stack is
# allocated once per slot (zeros + first refill ≡ make_frame) and then
# *reused across stream items*: a finished lane's slot is refilled in
# place with the next item's (m, n) interior — an O(m·n) interior write
# plus the O(m+n) ghost refresh, with no jnp.pad, no re-allocation and
# no host round-trip of the frame.  Stale block-round-up cells from the
# previous item are inert by the same dependency-cone argument that lets
# :func:`refresh_frame` leave them untouched.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LaneFrameSpec:
    """Static geometry of a lane-stacked frame: ``lanes`` independent
    :class:`FrameSpec` frames carried as one (lanes, H, W) array."""

    lanes: int
    frame: FrameSpec

    @property
    def shape(self) -> tuple[int, int, int]:
        return (self.lanes, *self.frame.shape)


def alloc_lane_frames(lspec: LaneFrameSpec, dtype) -> jnp.ndarray:
    """Allocate the lane slots — once, at stream start (the only
    full-frame allocation of the streaming path)."""
    return jnp.zeros(lspec.shape, dtype)


def make_lane_frames(a: jnp.ndarray, spec: FrameSpec,
                     boundary: Boundary | str) -> jnp.ndarray:
    """Embed a (lanes, m, n) stack into lane frames (one-shot staging)."""
    return jax.vmap(lambda x: make_frame(x, spec, boundary))(a)


def refill_lane_frames(frames: jnp.ndarray, interiors: jnp.ndarray,
                       spec: FrameSpec,
                       boundary: Boundary | str) -> jnp.ndarray:
    """Refill lane slots in place with the next stream items' interiors.

    ``interiors`` is (lanes, m, n); the write lands at the domain offset
    of every slot via ONE dynamic_update_slice — O(lanes·m·n), strictly
    interior-sized — and the per-lane ghost rings are then re-asserted
    from the new interiors (O(lanes·(m+n))).  No pad primitive, no fresh
    frame allocation: under jit donation the slots update in place.
    """
    frames = jax.lax.dynamic_update_slice(
        frames, interiors.astype(frames.dtype), (0, *spec.origin))
    return jax.vmap(lambda f: refresh_frame(f, spec, boundary))(frames)


def unframe_lanes(frames: jnp.ndarray, spec: FrameSpec) -> jnp.ndarray:
    """Slice every lane's (m, n) domain back out."""
    return frames[(slice(None), *spec.domain)]


def refill_slot_frame(frames: jnp.ndarray, interior: jnp.ndarray,
                      idx, spec: FrameSpec,
                      boundary: Boundary | str) -> jnp.ndarray:
    """Refill ONE lane slot (dynamic index ``idx``) with the next item.

    The single-slot twin of :func:`refill_lane_frames`: the (m, n)
    interior lands at the slot's domain offset via one O(interior)
    dynamic_update_slice, then every lane's ghost ring is re-asserted —
    O(lanes·(m+n)), cheaper than slicing the one (H, W) frame out and
    back, and a no-op for untouched lanes (their ghosts already agree
    with their domains).  No pad, no full-frame copy, no re-framing; the
    same compilation serves every refill of the stream.
    """
    frames = jax.lax.dynamic_update_slice(
        frames, interior[None].astype(frames.dtype), (idx, *spec.origin))
    return jax.vmap(lambda f: refresh_frame(f, spec, boundary))(frames)


def refill_slot_env(env_frames: jnp.ndarray, e: jnp.ndarray, idx,
                    spec: FrameSpec, boundary: Boundary | str,
                    halo: bool = False) -> jnp.ndarray:
    """Refill ONE lane's env slot (single-slot twin of
    :func:`refill_lane_env`) — interior write at the dynamic index; with
    ``halo`` the ghost rings re-assert exactly as :func:`frame_env`."""
    if not halo:
        return jax.lax.dynamic_update_slice(
            env_frames, e[None].astype(env_frames.dtype), (idx, 0, 0))
    b = Boundary(boundary)
    ghost = b if b is Boundary.WRAP else Boundary.ZERO
    env_frames = jax.lax.dynamic_update_slice(
        env_frames, e[None].astype(env_frames.dtype), (idx, *spec.origin))
    return jax.vmap(lambda f: refresh_frame(f, spec, ghost))(env_frames)


def refill_lanes_masked(frames: jnp.ndarray, take: jnp.ndarray,
                        interiors: jnp.ndarray, spec: FrameSpec,
                        boundary: Boundary | str) -> jnp.ndarray:
    """Masked BATCH refill of many lane slots in one shot — the fused
    chained-dispatch twin of :func:`refill_slot_frame`.

    ``take`` is a (lanes,) bool mask naming the slots that receive new
    interiors this segment boundary; unmasked lanes write their CURRENT
    interiors back (a no-op value-wise), so one O(lanes·interior)
    select + :func:`refill_lane_frames` replaces a host-driven sequence
    of per-slot refill dispatches.  The all-lane ghost refresh is
    idempotent for untouched lanes (their rings already agree with
    their domains) — the same argument the per-slot refill relies on.
    """
    cur = unframe_lanes(frames, spec)
    new = jnp.where(take[:, None, None], interiors.astype(frames.dtype),
                    cur)
    return refill_lane_frames(frames, new, spec, boundary)


def refill_lanes_env_masked(env_frames: jnp.ndarray, take: jnp.ndarray,
                            e: jnp.ndarray, spec: FrameSpec,
                            boundary: Boundary | str,
                            halo: bool = False) -> jnp.ndarray:
    """Masked batch env refill (chained twin of :func:`refill_slot_env`):
    taken slots receive the staged env interiors, the rest keep their
    own — one fused select + :func:`refill_lane_env` write."""
    if not halo:
        cur = env_frames[:, :spec.m, :spec.n]
        new = jnp.where(take[:, None, None], e.astype(env_frames.dtype),
                        cur)
        return refill_lane_env(env_frames, new, spec, boundary,
                               halo=False)
    cur = unframe_lanes(env_frames, spec)
    new = jnp.where(take[:, None, None], e.astype(env_frames.dtype), cur)
    return refill_lane_env(env_frames, new, spec, boundary, halo=True)


# ---------------------------------------------------------------------------
# Staging ring — the device-resident refill queue of the chained
# dispatch path.
#
# The host pre-device_puts the next K items' PREPPED interiors (and env
# leaves) into a (K, m, n) ring ahead of need; the fused
# segment+refill entry then hands finished slots their next occupants
# straight from the ring via a device-side read cursor — no fresh host
# transfer, no host round trip, at any segment boundary in steady
# state.  The ring holds logical (m, n) interiors, not frames: the
# masked refill above re-derives ghosts/round-up exactly as a
# host-admitted item would, so ring-seated and host-seated occupants
# are bit-identical.
# ---------------------------------------------------------------------------


def alloc_stage_ring(depth: int, entry_shape: tuple,
                     dtype) -> jnp.ndarray:
    """Allocate a depth-K staging ring of per-item entries — once, at
    stream start (host-side zeros; callers device_put with their own
    sharding)."""
    import numpy as np
    return np.zeros((depth, *entry_shape), dtype)


def stage_ring_write(ring: jnp.ndarray, entry: jnp.ndarray,
                     pos) -> jnp.ndarray:
    """Write one prepped entry at ring position ``pos`` (a traced
    scalar — one compilation serves every stage of the stream; under
    jit donation the ring updates in place)."""
    return jax.lax.dynamic_update_slice(
        ring, entry[None].astype(ring.dtype),
        (pos,) + (0,) * entry.ndim)


def lane_env_frames(e: jnp.ndarray, spec: FrameSpec,
                    boundary: Boundary | str,
                    halo: bool = False) -> jnp.ndarray:
    """Stage a (lanes, m, n) stack of per-lane env fields (one-shot)."""
    return jax.vmap(lambda x: frame_env(x, spec, boundary, halo))(e)


def alloc_lane_env(lspec: LaneFrameSpec, dtype, halo: bool = False):
    """Zero-allocate the per-lane env slots (layout matches
    :func:`frame_env`: block-rounded interior, or full frame with
    ``halo``)."""
    shape = lspec.frame.shape if halo else lspec.frame.interior
    return jnp.zeros((lspec.lanes, *shape), dtype)


def refill_lane_env(env_frames: jnp.ndarray, e: jnp.ndarray,
                    spec: FrameSpec, boundary: Boundary | str,
                    halo: bool = False) -> jnp.ndarray:
    """Refill the env slots for the next items — interior write only (the
    round-up/ghost cells are inert or re-asserted, as in
    :func:`frame_env`)."""
    if not halo:
        return jax.lax.dynamic_update_slice(
            env_frames, e.astype(env_frames.dtype), (0, 0, 0))
    b = Boundary(boundary)
    ghost = b if b is Boundary.WRAP else Boundary.ZERO
    env_frames = jax.lax.dynamic_update_slice(
        env_frames, e.astype(env_frames.dtype), (0, *spec.origin))
    return jax.vmap(lambda f: refresh_frame(f, spec, ghost))(env_frames)


# ---------------------------------------------------------------------------
# Sharded frames — the 1:n deployment of the persistent-halo engine.
#
# Each shard carries its own frame; the ghost ring is re-asserted by a
# ppermute of O(pad·n) edge strips straight into the neighbour's ring
# (no concatenate, no jnp.pad, no full-block copy), with the global ⊥
# model applied locally only on shards that touch the global edge.  With
# temporal blocking (pad = k·T) one exchange feeds T fused sweeps —
# the communication-avoiding deep-halo schedule.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardedFrameSpec:
    """Per-shard frame geometry plus its embedding in the device mesh.

    ``local`` is the shard's own :class:`FrameSpec` (``m``/``n`` are the
    LOCAL domain extents); ``axis_names[ax]`` is the mesh axis that
    decomposes array axis ``ax`` (None = not decomposed); ``sizes[ax]``
    its arity.  All functions below run *inside* ``shard_map``.
    """

    local: FrameSpec
    axis_names: tuple          # per array axis: mesh axis name or None
    sizes: tuple               # per array axis: mesh axis arity (1 if local)

    @property
    def decomposed(self):
        return tuple(n for n in self.axis_names if n is not None)


def sharded_frame_spec(lm: int, ln: int, part, *, k: int = 1,
                       block=(256, 256), sweeps: int = 1) -> ShardedFrameSpec:
    """Frame geometry for one shard of an (lm·P, ln·Q) global domain.

    ``part`` carries ``axis_names``/``array_axes`` and the mesh (a
    :class:`repro.sharding.specs.GridPartition`).  The ghost ring must fit
    inside the *local* domain (pad = k·sweeps < min(lm, ln)) — deep
    temporal blocking wants coarse shards.
    """
    names = [None, None]
    sizes = [1, 1]
    for name, ax in zip(part.axis_names, part.array_axes):
        if ax not in (0, 1):
            raise ValueError(f"sharded frames are 2-D; array axis {ax}")
        names[ax] = name
        sizes[ax] = part.mesh.shape[name]
    spec = frame_spec(lm, ln, k=k, block=block, sweeps=sweeps)
    return ShardedFrameSpec(local=spec, axis_names=tuple(names),
                            sizes=tuple(sizes))


def _axslice(frame, axis, lo, hi, olo, ohi):
    """Static strip frame[lo:hi] along ``axis``, [olo:ohi] along the other."""
    idx = [slice(olo, ohi)] * 2
    idx[axis] = slice(lo, hi)
    return frame[tuple(idx)]


def _axset(frame, axis, lo, hi, olo, ohi, val):
    idx = [slice(olo, ohi)] * 2
    idx[axis] = slice(lo, hi)
    return frame.at[tuple(idx)].set(val)


def _refresh_axis_local(frame, spec: FrameSpec, axis: int,
                        boundary: Boundary, olo: int, ohi: int):
    """Local ⊥ fill of one axis's ghost strips (non-decomposed axis),
    restricted to [olo:ohi] along the other axis."""
    p = spec.pad
    d0 = spec.origin[axis]
    d1 = d0 + (spec.m, spec.n)[axis]
    if boundary in (Boundary.ZERO, Boundary.NAN):
        fill = 0.0 if boundary is Boundary.ZERO else jnp.nan
        frame = _axset(frame, axis, d0 - p, d0, olo, ohi, fill)
        return _axset(frame, axis, d1, d1 + p, olo, ohi, fill)
    if boundary is Boundary.REFLECT:
        # ghost d0-e mirrors domain d0+e (no edge repeat), as jnp.pad
        lo = jnp.flip(_axslice(frame, axis, d0 + 1, d0 + 1 + p, olo, ohi),
                      axis=axis)
        frame = _axset(frame, axis, d0 - p, d0, olo, ohi, lo)
        hi = jnp.flip(_axslice(frame, axis, d1 - 1 - p, d1 - 1, olo, ohi),
                      axis=axis)
        return _axset(frame, axis, d1, d1 + p, olo, ohi, hi)
    if boundary is Boundary.WRAP:
        frame = _axset(frame, axis, d0 - p, d0, olo, ohi,
                       _axslice(frame, axis, d1 - p, d1, olo, ohi))
        return _axset(frame, axis, d1, d1 + p, olo, ohi,
                      _axslice(frame, axis, d0, d0 + p, olo, ohi))
    raise ValueError(boundary)


@scope("halo_exchange")
def _refresh_axis_sharded(frame, sspec: ShardedFrameSpec, axis: int,
                          boundary: Boundary, olo: int, ohi: int):
    """ppermute one axis's ghost strips from the mesh neighbours.

    My last ``pad`` domain rows flow "down" into the next shard's leading
    ghost strip and vice versa — O(pad·width) cells on the wire, written
    straight into the ring.  Global-edge shards fill the missing side
    from the ⊥ model (constants / local mirror); WRAP closes the ring so
    the permutation is total.  All of it, strips, ppermutes and writes,
    is the device scope ``repro.halo_exchange``.
    """
    spec = sspec.local
    name = sspec.axis_names[axis]
    nsh = sspec.sizes[axis]
    p = spec.pad
    d0 = spec.origin[axis]
    d1 = d0 + (spec.m, spec.n)[axis]

    fwd = [(i, i + 1) for i in range(nsh - 1)]
    bwd = [(i + 1, i) for i in range(nsh - 1)]
    if boundary is Boundary.WRAP:
        fwd.append((nsh - 1, 0))
        bwd.append((0, nsh - 1))

    from_prev = jax.lax.ppermute(
        _axslice(frame, axis, d1 - p, d1, olo, ohi), name, fwd)
    from_next = jax.lax.ppermute(
        _axslice(frame, axis, d0, d0 + p, olo, ohi), name, bwd)

    if boundary in (Boundary.ZERO, Boundary.WRAP):
        pass    # ppermute zero-fills non-receivers; WRAP perms are total
    else:
        me = jax.lax.axis_index(name)
        if boundary is Boundary.NAN:
            lo_fill = jnp.full_like(from_prev, jnp.nan)
            hi_fill = jnp.full_like(from_next, jnp.nan)
        elif boundary is Boundary.REFLECT:
            lo_fill = jnp.flip(
                _axslice(frame, axis, d0 + 1, d0 + 1 + p, olo, ohi),
                axis=axis)
            hi_fill = jnp.flip(
                _axslice(frame, axis, d1 - 1 - p, d1 - 1, olo, ohi),
                axis=axis)
        else:
            raise ValueError(boundary)
        from_prev = jnp.where(me == 0, lo_fill, from_prev)
        from_next = jnp.where(me == nsh - 1, hi_fill, from_next)

    frame = _axset(frame, axis, d0 - p, d0, olo, ohi, from_prev)
    return _axset(frame, axis, d1, d1 + p, olo, ohi, from_next)


def refresh_frame_sharded(frame: jnp.ndarray, sspec: ShardedFrameSpec,
                          boundary: Boundary | str) -> jnp.ndarray:
    """Re-assert a sharded frame's ghost ring — the loop-body exchange.

    Axis 0 strips span the domain's column extent; axis 1 strips then run
    the ring's full height, so corner ghosts pick up the diagonal
    neighbour through the standard two-pass trick (and the local fills
    compose like ``jnp.pad``'s axis-sequential modes).  Decomposed axes
    exchange via ppermute; the rest fill locally.
    """
    boundary = Boundary(boundary)
    spec = sspec.local
    (r0, c0), p = spec.origin, spec.pad
    extents = ((c0, c0 + spec.n), (r0 - p, r0 + spec.m + p))
    with scope("ghost_refresh"):
        for axis in (0, 1):
            olo, ohi = extents[axis]
            if sspec.axis_names[axis] is None:
                frame = _refresh_axis_local(frame, spec, axis, boundary,
                                            olo, ohi)
            else:
                frame = _refresh_axis_sharded(frame, sspec, axis,
                                              boundary, olo, ohi)
    return frame


def make_frame_sharded(a_local: jnp.ndarray, sspec: ShardedFrameSpec,
                       boundary: Boundary | str) -> jnp.ndarray:
    """Embed one shard's block into its frame and refresh the ghosts.

    Runs once per shard, inside ``shard_map``, before the loop.
    """
    spec = sspec.local
    frame = jnp.zeros(spec.shape, a_local.dtype)
    frame = jax.lax.dynamic_update_slice(frame, a_local, spec.origin)
    return refresh_frame_sharded(frame, sspec, boundary)


def frame_env_sharded(e_local: jnp.ndarray, sspec: ShardedFrameSpec,
                      boundary: Boundary | str,
                      halo: bool = False) -> jnp.ndarray:
    """Stage one shard's slice of a read-only env field, once.

    With ``halo`` (temporal blocking) the ghost strips must hold the
    *neighbour's* env — intermediate sweeps evaluate f on ghost cells
    that are real domain cells of the adjacent shard — so the ring is
    filled by the same ppermute exchange; at global edges the env ghosts
    are inert (re-asserted each sweep) except under WRAP, which needs the
    torus continuation, exactly like :func:`frame_env`.
    """
    spec = sspec.local
    if not halo:
        mi, ni = spec.interior
        return jnp.pad(e_local, ((0, mi - spec.m), (0, ni - spec.n)))
    b = Boundary(boundary)
    frame = jnp.zeros(spec.shape, e_local.dtype)
    frame = jax.lax.dynamic_update_slice(frame, e_local, spec.origin)
    return refresh_frame_sharded(
        frame, sspec, b if b is Boundary.WRAP else Boundary.ZERO)


def refill_slot_frame_sharded(frames: jnp.ndarray, interior: jnp.ndarray,
                              li, owns, sspec: ShardedFrameSpec,
                              boundary: Boundary | str) -> jnp.ndarray:
    """Owner-masked refill of ONE lane slot of a SHARDED frame stack
    (runs inside ``shard_map``; the sharded twin of
    :func:`refill_slot_frame`).

    ``interior`` is this shard's LOCAL (lm, ln) block of the next item;
    ``li`` is the slot's local lane index (pre-clipped into range) and
    ``owns`` masks the write — every lane shard executes the same
    O(interior) read/select/write so the program stays SPMD-uniform, but
    only the owner's slot actually changes (non-owners write their
    current values back).  The ghost rings then re-assert through the
    SAME lane-batched edge-strip ppermute the loop body uses — O(pad·n)
    strips along the spatial axes only; nothing crosses the lane axis.
    No pad, no full-frame copy, one compilation per stream.
    """
    spec = sspec.local
    at = (li, *spec.origin)
    cur = jax.lax.dynamic_slice(frames, at, (1, spec.m, spec.n))
    new = jnp.where(owns, interior[None].astype(frames.dtype), cur)
    frames = jax.lax.dynamic_update_slice(frames, new, at)
    return jax.vmap(
        lambda f: refresh_frame_sharded(f, sspec, boundary))(frames)


def refill_slot_env_sharded(env_frames: jnp.ndarray, e: jnp.ndarray,
                            li, owns, sspec: ShardedFrameSpec,
                            boundary: Boundary | str,
                            halo: bool = False) -> jnp.ndarray:
    """Owner-masked single-slot env refill inside ``shard_map`` (the
    sharded twin of :func:`refill_slot_env`): the owner lane
    shard's slot takes this shard's LOCAL env block; with ``halo`` the
    ghost strips re-assert via the ppermute exchange as
    :func:`frame_env_sharded`."""
    spec = sspec.local
    if not halo:
        cur = jax.lax.dynamic_slice(env_frames, (li, 0, 0),
                                    (1, spec.m, spec.n))
        new = jnp.where(owns, e[None].astype(env_frames.dtype), cur)
        return jax.lax.dynamic_update_slice(env_frames, new, (li, 0, 0))
    b = Boundary(boundary)
    ghost = b if b is Boundary.WRAP else Boundary.ZERO
    at = (li, *spec.origin)
    cur = jax.lax.dynamic_slice(env_frames, at, (1, spec.m, spec.n))
    new = jnp.where(owns, e[None].astype(env_frames.dtype), cur)
    env_frames = jax.lax.dynamic_update_slice(env_frames, new, at)
    return jax.vmap(
        lambda f: refresh_frame_sharded(f, sspec, ghost))(env_frames)


def shard_domain_bounds(sspec: ShardedFrameSpec) -> jnp.ndarray:
    """(1, 4) int32 ``[row_lo, row_hi, col_lo, col_hi]`` of the GLOBAL
    domain in this shard's frame coordinates.

    Sides that continue into a neighbour shard get ±2^30 sentinels so the
    kernel's per-sweep ⊥ re-assertion never fires there — interior ghost
    cells are real cells of the adjacent shard and must evolve freely
    (the shrinking-window containment argument).  Traced (axis_index
    dependent): feeds the kernel through SMEM.
    """
    spec = sspec.local
    big = jnp.int32(2 ** 30)
    vals = []
    for ax, (d0, dom) in enumerate(zip(spec.origin, (spec.m, spec.n))):
        name = sspec.axis_names[ax]
        if name is None:
            lo = jnp.int32(d0)
            hi = jnp.int32(d0 + dom)
        else:
            me = jax.lax.axis_index(name)
            nsh = sspec.sizes[ax]
            lo = jnp.where(me == 0, jnp.int32(d0), -big)
            hi = jnp.where(me == nsh - 1, jnp.int32(d0 + dom), big)
        vals += [lo, hi]
    return jnp.stack(vals).astype(jnp.int32).reshape(1, 4)
