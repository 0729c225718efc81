"""Persistent-halo execution engine — the backend axis of the pattern.

This is the seam between :class:`repro.core.pattern.LoopOfStencilReduce`
and its realisations.  Three backends:

``"jnp"``
    The shift-algebra path (:func:`repro.core.stencil.stencil_taps`): XLA
    fuses the shifts, padding happens per application.  Reference
    semantics; also the fallback for non-2D arrays and non-taps modes.

``"pallas"``
    The fused single-step Pallas kernel iterated on a **persistent halo
    frame**: two padded, block-rounded frames (:mod:`repro.core.frames`)
    are the ``while_loop`` carry and swap roles every sweep (each sweep
    writes the frame the previous one read), so no ``jnp.pad``, full-grid
    slice, select or copy appears inside the loop body — the paper's
    device-memory persistence taken to the HBM-traffic level.  Only the
    O(m+n) ghost ring is re-asserted between sweeps.

``"pallas-multistep"``
    Temporal blocking: the pattern's ``unroll=T`` becomes the fused sweep
    count of :func:`repro.kernels.multistep.stencil2d_multistep_framed`,
    cutting HBM traffic per iteration by ≈T at ~(1 + 2kT/b)² redundant
    compute.  The convergence reduce fires every T sweeps — exactly the
    pattern's unroll semantics.

``"pallas-sharded"``
    The 1:n deployment of the persistent engine
    (:class:`ShardedStencilEngine`): the whole loop runs *inside*
    ``shard_map``, each shard's while-carry is its local halo frame, the
    ghost refresh is a ppermute of O(pad·n) edge strips straight into the
    neighbour's ring, and the fused delta-reduce composes with the
    monoid's native collective (``psum``/``pmax``/``pmin``) so the
    condition is evaluated identically on every shard with no host in
    the loop.  ``unroll=T`` reuses the temporal-blocking kernel with a
    k·T-deep halo exchanged once per T fused sweeps — ICI messages drop
    ≈T× for ~(1 + 2kT/b)² redundant compute (communication-avoiding).

The engine is deliberately array-in/array-out and stateless across calls
(the :class:`FrameSpec` travels alongside the frame), so streaming
executors can drop in behind the same seam.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from .frames import (FrameSpec, LaneFrameSpec, ShardedFrameSpec, ceil_mul,
                     frame_spec, make_frame, frame_env, frame_env_sharded,
                     lane_env_frames, make_frame_sharded, make_lane_frames,
                     refresh_frame, refresh_frame_sharded,
                     shard_domain_bounds, sharded_frame_spec, unframe,
                     unframe_lanes)
from .reduce import collective_combine, resolve_monoid
from .semantics import Boundary

BACKENDS = ("jnp", "pallas", "pallas-multistep", "pallas-sharded")


def _default_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def local_extents(m: int, n: int, part) -> tuple[int, int]:
    """Per-shard domain extents of an (m, n) grid under ``part`` (a
    :class:`repro.sharding.specs.GridPartition`); (m, n) when None."""
    lm, ln = m, n
    if part is not None:
        for name, ax in zip(part.axis_names, part.array_axes):
            nsh = part.mesh.shape[name]
            if ax == 0:
                lm = m // nsh
            elif ax == 1:
                ln = n // nsh
    return lm, ln


def auto_unroll(m: int, n: int, *, k: int = 1, block=(256, 256),
                part=None, cap: int = 8,
                redundancy_limit: float = 1.5,
                segment: Optional[int] = None,
                dispatch_amortize: int = 64) -> int:
    """Cost-heuristic temporal-blocking depth T for the persistent
    backends (``unroll="auto"``).

    Each extra fused sweep saves one ghost exchange — a full ICI
    latency·hop round on the sharded backend (per decomposed mesh axis),
    an HBM round-trip on "pallas-multistep" — at ~(1 + 2kT/bm)(1 + 2kT/bn)
    redundant compute per shard.  Exchanges are latency-bound and compute
    is throughput-bound, so deepening pays until the redundancy factor
    bites: take the largest T with

    * k·T < min(local m, local n)   (the frame_spec feasibility ceiling —
      a shard's halo cannot exceed its own domain), and
    * redundancy ≤ ``redundancy_limit``  (default 1.5: at most half the
      VPU throughput spent recomputing neighbour cells).

    The mesh shape enters through the LOCAL extents: more shards → smaller
    local domains → smaller feasible/profitable T, which is exactly the
    ceiling the ROADMAP notes (8 shards of a 64-row grid cap T at 4·k).

    With ``segment`` set (continuous farms: ``segment`` body steps per
    dispatch, so ``segment·T`` sweeps amortize one dispatch) the heuristic
    additionally folds the PER-DISPATCH cost in: when the tuned
    ``T·segment`` lands under ``dispatch_amortize`` sweeps, T is pushed
    back up toward ``ceil(dispatch_amortize / segment)`` — feasibility
    still binds (the halo must fit the local domain) but the redundancy
    limit is deliberately ignored, because in that regime the dispatch
    overhead, not the VPU, is the bottleneck: redundant ghost compute is
    free relative to a host round trip per segment.
    """
    lm, ln = local_extents(m, n, part)
    if min(lm, ln) <= k:
        raise ValueError(
            f"stencil radius k={k} does not fit the local domain "
            f"({lm}x{ln}): even T=1 needs k < min(local m, n); use a "
            f"coarser decomposition or a larger grid")
    bm = min(block[0], ceil_mul(lm, 8))
    bn = min(block[1], ceil_mul(ln, 128))
    best = 1
    for T in range(1, cap + 1):
        if k * T >= min(lm, ln):
            break
        if (1 + 2 * k * T / bm) * (1 + 2 * k * T / bn) > redundancy_limit:
            break
        best = T
    if segment is not None and best * segment < dispatch_amortize:
        want = -(-dispatch_amortize // segment)        # ceil division
        T = best
        while T < min(want, cap) and k * (T + 1) < min(lm, ln):
            T += 1
        best = T
    return best


def check_unroll_feasible(m: int, n: int, unroll: int, *, k: int = 1,
                          part=None) -> None:
    """Loud feasibility check for an explicit ``unroll=T`` — raises with
    the mesh context and the feasible ceiling instead of letting
    ``frame_spec`` fail with local-only numbers deep inside shard_map."""
    lm, ln = local_extents(m, n, part)
    if k * unroll < min(lm, ln):
        return
    tmax = max((min(lm, ln) - 1) // k, 0)
    where = (f"each of the {tuple(part.shards)} shards holds a local "
             f"{lm}x{ln} block of the {m}x{n} grid" if part is not None
             else f"the {m}x{n} grid")
    raise ValueError(
        f"unroll={unroll} is infeasible: the k*T={k * unroll}-deep halo "
        f"must fit inside the local domain, but {where} "
        f"(k*T < min(local m, n) = {min(lm, ln)} requires T <= {tmax}). "
        f"Lower unroll, pass unroll='auto', or use a coarser "
        f"decomposition.")


@dataclasses.dataclass
class StencilEngine:
    """Lowers fused stencil+reduce sweeps onto a chosen backend.

    ``delta``/``measure`` mirror the pattern's -d variant: the fused reduce
    folds ``delta(new, old)`` (elementwise, old = previous iterate) or
    ``measure(new)``; with neither, it folds ``new`` itself.
    """

    f: Callable
    k: int = 1
    boundary: Boundary | str = Boundary.ZERO
    combine: Any = "sum"
    identity: Any = None
    delta: Optional[Callable] = None
    measure: Optional[Callable] = None
    block: tuple[int, int] = (256, 256)
    unroll: int = 1
    backend: str = "pallas"
    interpret: Optional[bool] = None
    acc_dtype: Any = jnp.float32
    double_buffer: bool = True

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {self.backend!r}; choose from {BACKENDS}")
        self.boundary = Boundary(self.boundary)
        self._interp = _default_interpret(self.interpret)
        if self.delta is not None:
            self._kernel_measure = self.delta
        elif self.measure is not None:
            meas = self.measure
            self._kernel_measure = lambda new, old: meas(new)
        else:
            self._kernel_measure = None

    # -- frame staging (once, outside the loop) -------------------------
    def prepare(self, a: jnp.ndarray, env=()):
        """Stage ``a`` and the env fields into frames.  O(mn), runs once."""
        m, n = a.shape
        multistep = self.backend == "pallas-multistep"
        spec = frame_spec(m, n, k=self.k, block=self.block,
                          sweeps=self.unroll if multistep else 1)
        frame = make_frame(a, spec, self.boundary)
        env_frames = tuple(frame_env(e, spec, self.boundary, halo=multistep)
                           for e in env)
        return frame, env_frames, spec

    # -- the loop body (zero-copy) --------------------------------------
    def sweeps(self, frame: jnp.ndarray, env_frames, spec: FrameSpec):
        """``unroll`` stencil applications; returns (frame', reduced).

        The reduce covers the final application (measure against the
        second-to-last iterate).  The returned frame's ghost ring is
        already refreshed — it is a valid input for the next call.
        """
        from repro.kernels.multistep import stencil2d_multistep_framed
        from repro.kernels.stencil2d import stencil2d_fused_framed

        if self.backend == "pallas-multistep":
            frame, red = stencil2d_multistep_framed(
                frame, self.f, spec, T=self.unroll, env_framed=env_frames,
                combine=self.combine, identity=self.identity,
                measure=self._kernel_measure,
                boundary=self.boundary.value, acc_dtype=self.acc_dtype,
                double_buffer=self.double_buffer, interpret=self._interp)
            return refresh_frame(frame, spec, self.boundary), red
        red = None
        for s in range(self.unroll):
            # the condition only sees the final application's reduce —
            # intermediate sweeps skip the fused measure+fold entirely
            frame, red = stencil2d_fused_framed(
                frame, self.f, spec, env_framed=env_frames,
                combine=self.combine, identity=self.identity,
                measure=self._kernel_measure, acc_dtype=self.acc_dtype,
                double_buffer=self.double_buffer,
                do_reduce=(s == self.unroll - 1), interpret=self._interp)
            frame = refresh_frame(frame, spec, self.boundary)
        return frame, red

    def unframe(self, frame: jnp.ndarray, spec: FrameSpec) -> jnp.ndarray:
        """Slice the domain back out — once, after convergence."""
        return unframe(frame, spec)

    # -- the lane axis (1:1 streaming farm) ------------------------------
    @property
    def _halo_env(self) -> bool:
        return self.backend == "pallas-multistep"

    def lane_spec(self, lanes: int, m: int, n: int) -> LaneFrameSpec:
        """Frame geometry for ``lanes`` independent (m, n) stream items."""
        spec = frame_spec(m, n, k=self.k, block=self.block,
                          sweeps=self.unroll if self._halo_env else 1)
        return LaneFrameSpec(lanes=lanes, frame=spec)

    def prepare_lanes(self, a: jnp.ndarray, env=()):
        """Stage a (lanes, m, n) stack into lane frames (one-shot)."""
        lanes, m, n = a.shape
        lspec = self.lane_spec(lanes, m, n)
        frames = make_lane_frames(a, lspec.frame, self.boundary)
        env_frames = tuple(
            lane_env_frames(e, lspec.frame, self.boundary,
                            halo=self._halo_env) for e in env)
        return frames, env_frames, lspec

    def sweeps_lanes(self, frames, env_frames, lspec: LaneFrameSpec):
        """``unroll`` sweeps on every lane; returns (frames', (lanes,) r).

        One vmapped kernel launch covers the whole farm — the lane axis
        becomes an extra TPU grid dimension, not a Python loop.
        """
        return jax.vmap(
            lambda fr, *efs: self.sweeps(fr, tuple(efs), lspec.frame)
        )(frames, *env_frames)

    def unframe_lanes(self, frames, lspec: LaneFrameSpec):
        """Slice every lane's domain back out — the only per-item O(m·n)
        device→host candidate of the streaming path (the frames stay)."""
        return unframe_lanes(frames, lspec.frame)


@dataclasses.dataclass
class ShardedStencilEngine:
    """The 1:n persistent engine: per-shard frames, ppermute ghost swap.

    Every method runs *inside* ``shard_map`` (the mesh axes of ``part``
    must be bound).  The loop body is: kernel sweep(s) on the local frame
    → O(pad·n) ppermute edge-strip exchange → monoid collective of the
    fused partial reduce.  With ``unroll=T > 1`` the temporal-blocking
    kernel runs T sweeps per exchange over a k·T-deep halo
    (communication-avoiding: 1/T the ICI rounds per sweep).
    """

    f: Callable
    part: Any                        # GridPartition (mesh + decomposition)
    k: int = 1
    boundary: Boundary | str = Boundary.ZERO
    combine: Any = "sum"
    identity: Any = None
    delta: Optional[Callable] = None
    measure: Optional[Callable] = None
    block: tuple[int, int] = (256, 256)
    unroll: int = 1
    interpret: Optional[bool] = None
    acc_dtype: Any = jnp.float32
    double_buffer: bool = True

    def __post_init__(self):
        self.boundary = Boundary(self.boundary)
        self._interp = _default_interpret(self.interpret)
        self._op, self._id = resolve_monoid(self.combine, self.identity)
        if self.delta is not None:
            self._kernel_measure = self.delta
        elif self.measure is not None:
            meas = self.measure
            self._kernel_measure = lambda new, old: meas(new)
        else:
            self._kernel_measure = None

    @property
    def _multistep(self) -> bool:
        return self.unroll > 1

    # -- per-shard frame staging (once, inside shard_map) ---------------
    def prepare(self, a_local: jnp.ndarray, env_local=()):
        """Stage this shard's block and env slices into frames."""
        lm, ln = a_local.shape
        sspec = sharded_frame_spec(
            lm, ln, self.part, k=self.k, block=self.block,
            sweeps=self.unroll if self._multistep else 1)
        frame = make_frame_sharded(a_local, sspec, self.boundary)
        env_frames = tuple(
            frame_env_sharded(e, sspec, self.boundary,
                              halo=self._multistep)
            for e in env_local)
        return frame, env_frames, sspec

    # -- the loop body (zero-copy, communication-avoiding) --------------
    def sweeps(self, frame: jnp.ndarray, env_frames,
               sspec: ShardedFrameSpec):
        """``unroll`` sweeps + ONE ghost exchange + the global combine."""
        from repro.kernels.multistep import stencil2d_multistep_framed
        from repro.kernels.stencil2d import stencil2d_fused_framed

        spec = sspec.local
        if self._multistep:
            frame, red = stencil2d_multistep_framed(
                frame, self.f, spec, T=self.unroll,
                env_framed=env_frames, combine=self.combine,
                identity=self.identity, measure=self._kernel_measure,
                boundary=self.boundary.value,
                domain_bounds=shard_domain_bounds(sspec),
                acc_dtype=self.acc_dtype,
                double_buffer=self.double_buffer, interpret=self._interp)
        else:
            frame, red = stencil2d_fused_framed(
                frame, self.f, spec, env_framed=env_frames,
                combine=self.combine, identity=self.identity,
                measure=self._kernel_measure, acc_dtype=self.acc_dtype,
                double_buffer=self.double_buffer, interpret=self._interp)
        frame = refresh_frame_sharded(frame, sspec, self.boundary)
        red = collective_combine(self._op, red, self.part.axis_names)
        return frame, red

    def unframe(self, frame: jnp.ndarray,
                sspec: ShardedFrameSpec) -> jnp.ndarray:
        """Slice this shard's local domain back out, after convergence."""
        return unframe(frame, sspec.local)

    # -- the lane axis (lanes × spatial decomposition) -------------------
    # All lane methods run inside ``shard_map`` with the partition's mesh
    # axes bound; the lane stack holds this shard's LOCAL lanes and the
    # vmap batches the ppermute exchange + monoid collective per lane.

    def lane_sspec(self, lm: int, ln: int) -> ShardedFrameSpec:
        """Per-shard frame geometry for one lane's local (lm, ln) block."""
        return sharded_frame_spec(
            lm, ln, self.part, k=self.k, block=self.block,
            sweeps=self.unroll if self._multistep else 1)

    def sweeps_lanes(self, frames, env_frames, sspec: ShardedFrameSpec):
        """``unroll`` sweeps + ONE lane-batched ghost exchange + the
        global combine; returns (frames', (local_lanes,) r).  The combine
        makes r identical across the spatial shards of each lane, so a
        lane-done condition stays SPMD-uniform within its exchange group
        (the while trip counts may diverge across LANE shards — there are
        no collectives along the lane axis)."""
        return jax.vmap(
            lambda fr, *efs: self.sweeps(fr, tuple(efs), sspec)
        )(frames, *env_frames)


def sweep_once(a, f, *, env=(), k=1, combine="sum", identity=None,
               measure=None, boundary="zero", block=(256, 256),
               backend="pallas", unroll=1, interpret=None,
               double_buffer=True, acc_dtype=jnp.float32):
    """One fused stencil+reduce application through the backend axis.

    The fused-application entry point for non-iterative uses (Sobel, the
    AMF detection pass): returns ``(new, reduced)``.

    NOTE on naming: ``measure`` here is the *kernel* convention —
    a two-argument ``measure(new, old_center)`` (e.g. ``ref.abs_delta``),
    matching ``stencil2d_fused``.  The loop-level APIs
    (:class:`StencilEngine`, :class:`repro.core.pattern.
    LoopOfStencilReduce`) split this into ``delta`` (two-argument) and
    ``measure`` (one-argument, of the new iterate only) — pass a
    two-argument function as ``delta`` there, not ``measure``.

    ``unroll`` applies
    that many sweeps on every backend (fused into one kernel on
    "pallas-multistep", sequential otherwise), with the reduce taken on
    the final one — same contract as the pattern's unroll.
    ``backend="jnp"`` runs the oracle path; the Pallas backends
    frame/unframe per call, so a one-shot costs the same staging as the
    old per-iteration kernels — the persistent win applies to loops (use
    :class:`StencilEngine` / the pattern's ``backend=`` for those).
    """
    interp = _default_interpret(interpret)
    if backend == "pallas-multistep":
        from repro.kernels.multistep import stencil2d_multistep
        return stencil2d_multistep(
            a, f, env=env, k=k, T=unroll, combine=combine,
            identity=identity, measure=measure, boundary=boundary,
            block=block, acc_dtype=acc_dtype,
            double_buffer=double_buffer, interpret=interp)
    if backend == "jnp":
        from repro.kernels import ref as R
        step = lambda x: R.stencil2d_fused_ref(
            x, f, env=env, k=k, combine=combine, identity=identity,
            measure=measure, boundary=boundary, acc_dtype=acc_dtype)
    elif backend == "pallas":
        from repro.kernels.stencil2d import stencil2d_fused
        step = lambda x: stencil2d_fused(
            x, f, env=env, k=k, combine=combine, identity=identity,
            measure=measure, boundary=boundary, block=block,
            acc_dtype=acc_dtype, double_buffer=double_buffer,
            interpret=interp)
    else:
        # "pallas-sharded" is loop-only (it needs a mesh partition and a
        # while-carry); one-shot sweeps stay single-device
        raise ValueError(
            f"unknown backend {backend!r} for sweep_once; choose from "
            "('jnp', 'pallas', 'pallas-multistep')")
    new, red = step(a)
    for _ in range(unroll - 1):
        new, red = step(new)
    return new, red
