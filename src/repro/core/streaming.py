"""The streaming farm: :class:`FarmEngine`, a farm of convergence loops.

The paper's two-tier model [1]: data-parallel patterns (stencil, reduce,
Loop-of-stencil-reduce) nest inside stream-parallel ones (pipe, farm).
Its experiments use two compositions:

    pipe(read, sobel, write)                       (§4.2)
    pipe(read, detect, ofarm(restore), write)      (§4.3)

Both run on :class:`FarmEngine`.  Loop-of-stencil-reduce subsumes map,
so the Sobel stream is a farm of one-sweep loops (``max_iters=1``); the
restoration's detection pass is the farm's per-item ``prep``; ofarm's
order is the ``index`` every result carries.

L lane *slots* hold persistent halo frames (:mod:`repro.core.frames`).
The farm advances in bounded early-exit segments of ONE done-masked
lane loop over the stacked (lanes, frame) carry
(:meth:`repro.core.pattern.LoopOfStencilReduce.lane_segment`).  A lane
whose loop finishes hands its slot to the next item mid-flight (the
FastFlow farm's worker refill): an O(interior) write into the slot, no
re-pad, no re-allocation, no host round trip of the frame.  Results
emit in completion order, so throughput does not depend on the spread
of the items' trip counts; ``stats["wasted_lane_steps"]`` counts the
done-masked sweeps the farm still runs.

Two dispatchers drive the segments:

* **chained** (the default): ONE donated jitted entry per segment runs
  the segment, captures the finished lanes' results and seats their
  successors from a device staging ring the host fills ahead of need.
  The host reads one packed metadata vector per segment, after the
  next segment is in flight.
* **synchronous** (``chained=False``, the composed lanes × spatial
  ``pallas-sharded`` farm, and the repair phase after a retry): one
  segment, a blocking read, then a per-slot refill of each finished
  lane.  In the composed farm the refill scatters each finished lane's
  LOCAL interior blocks inside ``shard_map`` with owner masking
  (:func:`repro.core.frames.refill_slot_frame_sharded`), and the ghost
  rings re-assert through the same O(k·n) edge-strip ppermute the loop
  body uses.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from itertools import islice
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .reduce import HEALTH_CONVERGED, HEALTH_DIVERGED, HEALTH_POISONED
from .spans import span


class NonFiniteItemError(ValueError):
    """A stream item carried NaN/Inf leaves at the prep boundary.  The
    farm routes the item to the dead-letter list with
    ``status="rejected"`` and keeps streaming."""


def item_status(hw: int, iters: int, max_iters: int) -> str:
    """The streaming status taxonomy of one finished item, from its
    packed health word + trip count: ``ok`` (condition fired, no fault),
    ``poisoned`` (NaN/Inf reduce), ``nonconverged`` (sentinel divergence
    quarantine), ``timed_out`` (iteration budget exhausted)."""
    hw = int(hw)
    if hw & HEALTH_POISONED:
        return "poisoned"
    if hw & HEALTH_DIVERGED:
        return "nonconverged"
    if hw & HEALTH_CONVERGED:
        return "ok"
    return "timed_out" if int(iters) >= max_iters else "nonconverged"


# ---------------------------------------------------------------------------
# FarmEngine — the lane-resident streaming engine.
# ---------------------------------------------------------------------------


def _default_prep(item):
    """Identity prep.  A bare array IS the loop input; a TUPLE stream
    item carries its own read-only env fields along — ``(a, *env)`` —
    for streams whose env is produced upstream (an external detector)
    rather than derived from the item on device."""
    if isinstance(item, tuple):
        return item[0], tuple(item[1:])
    return item, ()


def _as_item(item):
    """Normalise one stream item to ndarray leaves (tuple items keep
    their env leaves alongside the main array)."""
    if isinstance(item, (tuple, list)):
        return tuple(np.asarray(leaf) for leaf in item)
    return np.asarray(item)


def _item_leaves(item) -> tuple:
    return item if isinstance(item, tuple) else (item,)


def _item_nbytes(item) -> int:
    return sum(leaf.nbytes for leaf in _item_leaves(item))


@dataclasses.dataclass
class StreamResult:
    """One farm emission: the item's stream position plus the fields of
    :class:`~repro.core.pattern.LoopResult`.  The farm emits in
    COMPLETION order (that is the point — a 1-sweep item must not wait
    behind a 200-sweep straggler), so the index carries the ofarm
    identity.

    ``status`` is the failure-semantics verdict (see :func:`item_status`
    plus ``"rejected"`` for items that failed the admission-time finite
    check); ``attempts`` counts slot occupations (> 1 means the item was
    retried on a fresh slot after a non-ok finish).  ``error`` carries
    the host-side exception text when a result had to be degraded (a
    raising sink — the result lives on ``dead_letter`` instead of being
    lost with the stream)."""
    index: int
    a: Any
    reduced: Any
    iters: Any
    status: str = "ok"
    attempts: int = 1
    error: Optional[str] = None


@dataclasses.dataclass
class FarmEngine:
    """Lane-resident streaming farm: persistent-frame lane slots with
    device-side slot refill and host-side double buffering.

    ``loop`` is the per-item worker (a :class:`~repro.core.pattern.
    LoopOfStencilReduce`); ``lanes`` is the number of device-resident
    slots.  :meth:`run` streams: the farm advances as a *segmented*
    loop (:meth:`~repro.core.pattern.LoopOfStencilReduce.lane_segment`)
    that returns to the dispatcher as soon as any lane finishes (bounded
    by ``segment`` body steps); ONLY the finished lanes' slots are
    refilled in place — an O(interior) write each, no re-pad, no
    re-framing — and the SAME carry resumes.  Each result is emitted as
    a :class:`StreamResult` (completion order, stream index attached)
    the moment its lane finishes.  One compilation serves every segment
    and every refill of the stream.

    ``prep`` optionally maps a raw stream item to ``(a0, env_tuple)`` on
    device, one item at a time — the farm's per-item read stage (e.g.
    the §4.3 detection pass feeding restoration).  ``prep`` runs on the
    WHOLE item before any spatial decomposition, so stencil-shaped preps
    (halo-dependent, like AMF detection) see their full neighbourhood
    even under the composed sharded deployment.  Stream items may also
    be TUPLES ``(a, *env_items)`` carrying externally produced env
    fields; the default prep splits them, a user ``prep`` receives the
    whole tuple.
    Every leaf — main and env alike — is shape/dtype-guarded against
    mid-stream drift (build a fresh engine per item geometry).

    Deployments:

    * ``mesh=None`` — single device, lanes on the vmapped kernel grid.
    * ``mesh=`` with a single-device backend ("jnp"/"pallas"/
      "pallas-multistep") — lanes spread over ``mesh[lane_axis]`` via
      ``shard_map`` (the 1:1 mode across devices: each shard owns
      lanes/P slots and its own while trip count — no collectives cross
      the lane axis).
    * ``loop.backend == "pallas-sharded"`` — the two-tier composition:
      lanes over ``lane_axis`` × each lane's frame spatially decomposed
      over ``loop.partition``'s axes (all on the same ``mesh``), with the
      lane-batched ppermute ghost exchange inside the shared while body.
      It runs the synchronous dispatcher: the refill scatters a finished
      lane's LOCAL interior blocks per shard (owner-masked, inside
      ``shard_map``) and re-asserts the ghosts through the same
      edge-strip ppermute — the segmented while runs per lane shard with
      no cross-lane collectives.
    """

    loop: Any                          # LoopOfStencilReduce worker
    lanes: int = 4
    prep: Optional[Callable] = None    # item -> (a0, env tuple), on device
    mesh: Optional[Mesh] = None
    lane_axis: str = "data"
    segment: int = 16                  # max body steps between
                                       # dispatcher check-ins
    max_attempts: int = 1              # slot occupations per item: a
                                       # non-ok item re-enters the retry
                                       # queue (fresh slot) until this
                                       # cap, then dead-letters
    slot_patience: int = 3             # consecutive non-ok finishes on
                                       # one slot before the slot itself
                                       # is quarantined (retired from
                                       # the refill rotation)
    check_finite: bool = True          # admission-time NaN/Inf guard on
                                       # every item leaf (host-side
                                       # O(item) scan)
    chained: bool = True               # chain segments through
                                       # the fused segment+refill
                                       # entry (device staging ring, no
                                       # blocking host sync in steady
                                       # state); False restores the
                                       # classic dispatch→sync→per-slot-
                                       # refill loop.  The composed
                                       # pallas-sharded deployment always
                                       # runs the classic loop (its
                                       # fixed-step segments have no
                                       # early exit to chain past, and
                                       # its refill must stay inside the
                                       # spatial shard_map).
    stage_depth: Optional[int] = None  # staging-ring depth K (chained
                                       # mode); None = max(2*lanes, 2)

    def __post_init__(self):
        loop = self.loop
        if loop.state_init is not None:
            raise ValueError("FarmEngine does not support the -s variant "
                             "(per-lane loop states are ambiguous)")
        if loop.mode != "taps" and loop.backend != "jnp":
            raise ValueError("FarmEngine needs mode='taps' on the pallas "
                             f"backends; got mode={loop.mode!r}")
        if self.mesh is not None:
            if isinstance(self.mesh, Mesh):
                from repro.sharding.specs import auto_mesh
                self.mesh = auto_mesh(self.mesh)
            if self.lane_axis not in self.mesh.axis_names:
                raise ValueError(
                    f"lane_axis {self.lane_axis!r} not in mesh axes "
                    f"{self.mesh.axis_names}")
            if self.lanes % self.mesh.shape[self.lane_axis]:
                raise ValueError(
                    f"lanes={self.lanes} must divide evenly over mesh "
                    f"axis {self.lane_axis!r} "
                    f"(size {self.mesh.shape[self.lane_axis]})")
        if loop.backend == "pallas-sharded":
            if self.mesh is None:
                raise ValueError(
                    "backend='pallas-sharded' lanes need mesh= (carrying "
                    "the lane axis AND the partition's spatial axes)")
            part = loop.partition
            for name in part.axis_names:
                if name == self.lane_axis:
                    raise ValueError(
                        f"partition axis {name!r} collides with "
                        f"lane_axis; use distinct mesh axes for lanes "
                        "and the spatial decomposition")
                if name not in self.mesh.axis_names:
                    raise ValueError(
                        f"partition axis {name!r} missing from mesh "
                        f"axes {self.mesh.axis_names}")
        if self.segment < 1:
            raise ValueError(f"segment must be >= 1; got {self.segment}")
        if self.max_attempts < 1:
            raise ValueError(
                f"max_attempts must be >= 1; got {self.max_attempts}")
        if self.slot_patience < 1:
            raise ValueError(
                f"slot_patience must be >= 1; got {self.slot_patience}")
        if self.stage_depth is not None and self.stage_depth < 1:
            raise ValueError(
                f"stage_depth must be >= 1; got {self.stage_depth}")
        self.dead_letter: list = []     # items that exhausted retries /
                                        # were rejected at admission
                                        # (their emitted StreamResults)
        self._prep1 = self.prep or _default_prep
        self._bound = False
        self._frames = None
        self._env_frames = ()
        # one jit wrapper per entry point for the stream's lifetime:
        # every segment / refill hits the same compilation (trace-count
        # regression-tested); the slot buffers are donated so refills
        # update them in place
        self._segment_fn = jax.jit(self._segment_entry,
                                   donate_argnums=(0, 1, 2, 3, 4, 5))
        self._refill_fn = jax.jit(self._refill_impl,
                                  donate_argnums=(0, 1, 2, 3, 4, 5))
        self._restore_fn = jax.jit(self._restore_impl,
                                   donate_argnums=(0, 1, 2, 3, 4, 5))
        self._extract_fn = jax.jit(self._extract_impl)
        # the chained dispatch path: ONE fused segment + masked batch
        # refill + emission-capture entry (slot buffers AND the staging
        # ring donated — everything updates in place, segment to
        # segment, with only async metadata reads on the host side)
        self._chain_fn = jax.jit(self._chain_entry,
                                 donate_argnums=(0, 1, 2, 3, 4, 5, 6, 7))
        self._stage_fn = jax.jit(self._stage_impl, donate_argnums=(0, 1))
        self.stats = {"items": 0, "h2d_bytes": 0, "d2h_bytes": 0,
                      "segments": 0, "refills": 0,
                      "lane_steps": 0, "wasted_lane_steps": 0,
                      "quarantined_lane_steps": 0, "retries": 0,
                      "rejected": 0, "quarantined_slots": 0,
                      "segment_traces": 0, "refill_traces": 0,
                      "chain_traces": 0, "stage_traces": 0,
                      "sink_errors": 0, "snapshots": 0,
                      "replayed_items": 0, "recovered_occupants": 0,
                      "recovery_seconds": 0.0}
        self._resume_state = None       # staged by restore()
        self._rt_capture = None         # live snapshot closure, set by
                                        # run for snapshot()

    # -- static geometry (first item binds the shapes) -------------------
    def _bind(self, item):
        L = self.lanes
        item = _as_item(item)
        self._item_avals = tuple(
            jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
            for leaf in _item_leaves(item))
        items_aval = jax.tree.map(
            lambda leaf: jax.ShapeDtypeStruct((L, *leaf.shape),
                                              leaf.dtype), item)
        a_aval, env_avals = jax.eval_shape(jax.vmap(self._prep1),
                                           items_aval)
        if len(a_aval.shape) != 3:
            raise ValueError(
                f"stream items must be 2-D grids; prep produced "
                f"{a_aval.shape}")
        m, n = a_aval.shape[1:]
        # the segment length folds into unroll="auto": the tuned segment
        # (T·segment sweeps per dispatch) amortizes the remaining
        # per-dispatch cost of the chained path
        self._loop = self.loop._resolve_unroll((m, n), segment=self.segment)
        loop = self._loop
        self._prep_avals = (a_aval, env_avals)
        self._nshards = (1 if self.mesh is None
                         else self.mesh.shape[self.lane_axis])

        if loop.backend == "jnp":
            self._eng, self._lspec = None, None
            self._frames = jnp.zeros((), a_aval.dtype)
            self._env_frames = ()
        elif loop.backend == "pallas-sharded":
            from .executor import ShardedStencilEngine, local_extents

            part = loop.partition
            for name, ax in zip(part.axis_names, part.array_axes):
                nsh = part.mesh.shape[name]
                if (m, n)[ax] % nsh:
                    raise ValueError(
                        f"array axis {ax} (size {(m, n)[ax]}) must "
                        f"divide evenly over mesh axis {name!r} "
                        f"(size {nsh})")
            lm, ln = local_extents(m, n, part)
            self._eng = ShardedStencilEngine(
                f=loop.f, part=part, k=loop.k, boundary=loop.boundary,
                combine=loop.combine, identity=loop.identity,
                delta=loop.delta, measure=loop.measure, block=loop.block,
                unroll=loop.unroll, interpret=loop.interpret)
            self._lspec = self._eng.lane_sspec(lm, ln)
            spatial = [None, None]
            for name, ax in zip(part.axis_names, part.array_axes):
                spatial[ax] = name
            self._spatial = tuple(spatial)
            arity = tuple(part.mesh.shape[s] if s else 1 for s in spatial)

            def stitched(local_shape):
                """Global shape of a lane-stacked per-shard buffer."""
                return (L, local_shape[0] * arity[0],
                        local_shape[1] * arity[1])

            self._frames = jax.device_put(
                np.zeros(stitched(self._lspec.local.shape), a_aval.dtype),
                NamedSharding(self.mesh, self._fspec()))
            # env slots: per-shard layout matches frame_env_sharded
            # (block-rounded interior, or full frame under temporal
            # blocking) — prep produced the avals from WHOLE items, the
            # spatial split happens at the shard_map boundary
            env_local = (self._lspec.local.shape if self._eng._multistep
                         else self._lspec.local.interior)
            self._env_frames = tuple(
                jax.device_put(np.zeros(stitched(env_local), e.dtype),
                               NamedSharding(self.mesh, self._fspec()))
                for e in env_avals)
        else:
            from .executor import StencilEngine
            from .frames import alloc_lane_env

            self._eng = StencilEngine(
                f=loop.f, k=loop.k, boundary=loop.boundary,
                combine=loop.combine, identity=loop.identity,
                delta=loop.delta, measure=loop.measure, block=loop.block,
                unroll=loop.unroll, backend=loop.backend,
                interpret=loop.interpret)
            self._lspec = self._eng.lane_spec(L // self._nshards, m, n)
            frames = np.zeros((L, *self._lspec.frame.shape), a_aval.dtype)
            envs = tuple(
                np.zeros((L,) + tuple(
                    alloc_lane_env(self._lspec, e.dtype,
                                   self._eng._halo_env).shape[1:]),
                    e.dtype)
                for e in env_avals)
            if self.mesh is None:
                self._frames = jnp.asarray(frames)
                self._env_frames = tuple(jnp.asarray(e) for e in envs)
            else:
                lane_sh = NamedSharding(self.mesh, P(self.lane_axis))
                self._frames = jax.device_put(frames, lane_sh)
                self._env_frames = tuple(
                    jax.device_put(e, lane_sh) for e in envs)
        self._bound = True

    def _fspec(self) -> P:
        """PartitionSpec of the lane-stacked frames/interiors (composed
        sharded mode: lanes × spatial)."""
        return P(self.lane_axis, *self._spatial)

    # -- lane-step accounting ---------------------------------------------
    @property
    def wasted_lane_steps(self) -> int:
        """Total done-masked / idle-slot lane sweeps executed so far."""
        return self.stats["wasted_lane_steps"]

    @property
    def lane_steps(self) -> int:
        """Total lane sweeps executed (useful + wasted)."""
        return self.stats["lane_steps"]

    @property
    def quarantined_lane_steps(self) -> int:
        """Lane sweeps burned on occupants that finished non-ok
        (poisoned / diverged / timed out) — the fault-waste axis next
        to ``wasted_lane_steps``."""
        return self.stats["quarantined_lane_steps"]

    # -- the segmented loop + per-slot refill (synchronous path) ---------
    def _lane_step(self, env_frames):
        """The per-body-step farm advance for the resident carry: the
        vmapped persistent-kernel sweep (pallas backends) or the vmapped
        shift-algebra step (jnp — the (lanes, m, n) stack IS the carry).
        """
        loop = self._loop
        if loop.backend == "jnp":
            return loop._lane_step_jnp(env_frames)
        return lambda fr: self._eng.sweeps_lanes(fr, env_frames,
                                                 self._lspec)

    def _local_segment(self, frames, env_frames, r, it, done, hw):
        """One bounded early-exit slice of the resident lane loop
        (directly, or per-shard inside shard_map).  Returns the resumed
        carry plus the (1,) body-step count — per shard, because lane
        shards exit their segments independently (no collectives cross
        the lane axis).  The composed backend runs the uniform-schedule
        variant instead (exactly ``segment`` done-masked steps): its
        body ppermutes ghost strips along the spatial axes, and a
        data-dependent early exit on one lane shard would leave the
        other shards' exchange rendezvous waiting — a fixed step count
        keeps every shard's collective schedule aligned with still no
        collective crossing the lane axis."""
        loop = self._loop
        (a, r, it, done, hw), steps = loop.lane_segment(
            (frames, r, it, done, hw), step=self._lane_step(env_frames),
            segment=self.segment,
            early_exit=loop.backend != "pallas-sharded")
        return a, env_frames, r, it, done, hw, steps[None]

    def _segment_entry(self, frames, env_frames, r, it, done, hw):
        self.stats["segment_traces"] += 1      # traced once per stream
        return self._segment_body(frames, env_frames, r, it, done, hw)

    def _segment_body(self, frames, env_frames, r, it, done, hw):
        if self.mesh is None:
            return self._local_segment(frames, env_frames, r, it, done,
                                       hw)
        from repro.sharding.specs import shard_map

        lane_spec = P(self.lane_axis)
        # composed mode: frames carry the spatial axes too; the segment
        # still runs per LANE shard (spatial shards of one lane group
        # share their trip counts through the collective reduce, so the
        # early exit stays SPMD-uniform within each exchange group)
        fr_spec = (self._fspec()
                   if self._loop.backend == "pallas-sharded"
                   else lane_spec)
        env_specs = tuple(fr_spec for _ in env_frames)
        fn = shard_map(
            self._local_segment, mesh=self.mesh,
            in_specs=(fr_spec, env_specs, lane_spec, lane_spec,
                      lane_spec, lane_spec),
            out_specs=(fr_spec, env_specs, lane_spec, lane_spec,
                       lane_spec, lane_spec, lane_spec))
        return fn(frames, env_frames, r, it, done, hw)

    def _refill_impl(self, frames, env_frames, r, it, done, hw, idx,
                     item):
        """Hand ONE finished lane's slot (dynamic index) to the next
        stream item and re-arm its carry — O(interior) writes, no pad,
        no re-framing, one compilation for every refill.  ``prep`` runs
        here, on the whole item (halo-aware by construction).  The
        health word re-arms to 0 with the rest of the carry: a slot's
        faults do not follow it onto the next occupant."""
        self.stats["refill_traces"] += 1       # traced once per stream
        loop = self._loop
        a0, envs = self._prep1(item)
        return self._slot_write(frames, env_frames, r, it, done, hw, idx,
                                a0, envs, loop._id, 0, 0)

    def _restore_impl(self, frames, env_frames, r, it, done, hw, idx,
                      item, a_mid, rv, iv, hv):
        """Re-seat a snapshotted in-flight occupant into a (possibly
        different) slot: the saved mid-flight LOGICAL interior ``a_mid``
        takes the place of a fresh item's prepped ``a0`` and the carry
        re-arms with the saved ``(reduce, iter, health)`` instead of the
        identity — the convergence loop continues from iteration ``iv``
        exactly as if the preemption never happened.  Ghost/boundary
        cells are re-derived by the same refill machinery a fresh item
        uses (they are a function of interior + boundary spec, and the
        next sweep re-asserts them before reading), which is what makes
        snapshots topology-free: this path repacks the interior onto
        whatever lane count / mesh the RESUMED engine runs.  ``prep``
        re-derives the env fields from the raw item (prep must be
        deterministic — the same property retries already rely on)."""
        _, envs = self._prep1(item)
        return self._slot_write(frames, env_frames, r, it, done, hw, idx,
                                a_mid, envs, rv, iv, hv)

    def _slot_write(self, frames, env_frames, r, it, done, hw, idx,
                    a0, envs, rv, iv, hv):
        from .frames import refill_slot_env, refill_slot_frame

        loop = self._loop
        if loop.backend == "pallas-sharded":
            return self._refill_sharded(frames, env_frames, r, it, done,
                                        hw, idx, a0, envs, rv, iv, hv)
        if loop.backend == "jnp":
            frames = jax.lax.dynamic_update_slice(
                frames, a0[None].astype(frames.dtype), (idx, 0, 0))
            env_frames = tuple(
                jax.lax.dynamic_update_slice(
                    ef, e[None].astype(ef.dtype), (idx,) + (0,) * e.ndim)
                for ef, e in zip(env_frames, envs))
        else:
            spec = self._lspec.frame
            frames = refill_slot_frame(frames, a0, idx, spec,
                                       loop.boundary)
            env_frames = tuple(
                refill_slot_env(ef, e, idx, spec, loop.boundary,
                                halo=self._eng._halo_env)
                for ef, e in zip(env_frames, envs))
        r = r.at[idx].set(jnp.asarray(rv, r.dtype))
        it = it.at[idx].set(jnp.asarray(iv, it.dtype))
        done = done.at[idx].set(False)
        hw = hw.at[idx].set(jnp.asarray(hv, hw.dtype))
        return frames, env_frames, r, it, done, hw

    def _refill_sharded(self, frames, env_frames, r, it, done, hw, idx,
                        a0, envs, rv, iv, hv):
        """Composed-mode slot hand-off: ``prep`` already ran on the
        WHOLE item (halo-aware); its (m, n) result splits at the
        shard_map boundary, each spatial shard scatters its LOCAL
        interior block into the owner lane shard's slot (owner-masked —
        every shard runs the same O(interior) program, only the owner's
        slot changes), and the ghost rings re-assert through the SAME
        O(k·n) edge-strip ppermute the loop body uses.  The carry
        re-arms with a masked select on the (local lanes,) vectors — no
        collective crosses the lane axis, one compilation per stream."""
        from repro.sharding.specs import local_slot, shard_map
        from .frames import (refill_slot_env_sharded,
                             refill_slot_frame_sharded)

        loop = self._loop
        fspec = self._fspec()
        lane_spec = P(self.lane_axis)
        spatial_spec = P(*self._spatial)
        local_L = self.lanes // self._nshards
        halo_env = self._eng._multistep

        def local_refill(frames, env_frames, r, it, done, hw, idx,
                         a_loc, env_loc, rv, iv, hv):
            owns, li = local_slot(idx, local_L, self.lane_axis)
            frames = refill_slot_frame_sharded(
                frames, a_loc, li, owns, self._lspec, loop.boundary)
            env_frames = tuple(
                refill_slot_env_sharded(ef, e, li, owns, self._lspec,
                                        loop.boundary, halo=halo_env)
                for ef, e in zip(env_frames, env_loc))
            upd = jnp.logical_and(owns,
                                  jnp.arange(r.shape[0]) == li)
            r = jnp.where(upd, jnp.asarray(rv, r.dtype), r)
            it = jnp.where(upd, jnp.asarray(iv, it.dtype), it)
            done = jnp.where(upd, jnp.zeros_like(done), done)
            hw = jnp.where(upd, jnp.asarray(hv, hw.dtype), hw)
            return frames, env_frames, r, it, done, hw

        env_specs = tuple(fspec for _ in env_frames)
        fn = shard_map(
            local_refill, mesh=self.mesh,
            in_specs=(fspec, env_specs, lane_spec, lane_spec, lane_spec,
                      lane_spec, P(), spatial_spec,
                      tuple(spatial_spec for _ in envs), P(), P(), P()),
            out_specs=(fspec, env_specs, lane_spec, lane_spec,
                       lane_spec, lane_spec))
        return fn(frames, env_frames, r, it, done, hw, idx, a0, envs,
                  jnp.asarray(rv), jnp.asarray(iv), jnp.asarray(hv))

    def _extract_impl(self, frames, idx):
        """Slice ONE lane's (m, n) domain out at a dynamic index — the
        only per-item device→host payload of the synchronous path."""
        if self._loop.backend == "jnp":
            return jax.lax.dynamic_index_in_dim(frames, idx, axis=0,
                                                keepdims=False)
        if self._loop.backend == "pallas-sharded":
            from repro.sharding.specs import local_slot, shard_map

            spec = self._lspec.local
            local_L = self.lanes // self._nshards

            def local_extract(fr, idx):
                _, li = local_slot(idx, local_L, self.lane_axis)
                return jax.lax.dynamic_slice(fr, (li, *spec.origin),
                                             (1, spec.m, spec.n))

            fn = shard_map(local_extract, mesh=self.mesh,
                           in_specs=(self._fspec(), P()),
                           out_specs=P(self.lane_axis, *self._spatial))
            # every lane shard contributes ITS li-slot's stitched (m, n)
            # plane; the owner's plane is the result
            planes = fn(frames, idx)
            owner = idx // jnp.asarray(local_L, idx.dtype)
            return jax.lax.dynamic_index_in_dim(planes, owner, axis=0,
                                                keepdims=False)
        spec = self._lspec.frame
        return jax.lax.dynamic_slice(
            frames, (idx, *spec.origin), (1, spec.m, spec.n))[0]

    # -- chained dispatch: fused segment + ring refill + capture ---------
    def _unframe_all(self, frames):
        """Every lane's (m, n) domain as one (lanes, m, n) stack — the
        chained path's emission payload, captured INSIDE the fused entry
        (pre-refill, so it is value-identical to what the classic
        per-slot ``_extract_fn`` would have sliced)."""
        if self._loop.backend == "jnp":
            return frames
        from .frames import unframe_lanes
        return unframe_lanes(frames, self._lspec.frame)

    def _chain_refill(self, frames, env_frames, take, interiors,
                      env_sel):
        """Masked batch refill of every taken slot in ONE shot — the
        fused replacement for the host loop's per-finished-slot
        ``_refill_fn`` dispatches.  ``interiors``/``env_sel`` are the
        staging-ring gathers ((lanes, m, n) — junk rows where ``~take``
        are masked out by the select)."""
        loop = self._loop
        if loop.backend == "jnp":
            frames = jnp.where(take[:, None, None],
                               interiors.astype(frames.dtype), frames)
            env_frames = tuple(
                jnp.where(take.reshape((-1,) + (1,) * (ef.ndim - 1)),
                          e.astype(ef.dtype), ef)
                for ef, e in zip(env_frames, env_sel))
            return frames, env_frames
        from .frames import refill_lanes_env_masked, refill_lanes_masked
        spec = self._lspec.frame
        frames = refill_lanes_masked(frames, take, interiors, spec,
                                     loop.boundary)
        env_frames = tuple(
            refill_lanes_env_masked(ef, take, e, spec, loop.boundary,
                                    halo=self._eng._halo_env)
            for ef, e in zip(env_frames, env_sel))
        return frames, env_frames

    def _chain_entry(self, frames, env_frames, r, it, done, hw, ring,
                     ring_envs, rd, wr, live):
        """ONE donated jitted dispatch of the chained path: run a
        segment, CAPTURE the finished lanes' emission payloads (domains,
        reduce/iter/health — all pre-refill), then hand every finished
        live slot its next occupant straight from the staging ring via
        the device-side cursor ``rd`` — a masked batch refill, no host
        round trip, no per-slot dispatch.

        ``rd`` is device-resident (threaded call to call — only the
        device knows how many slots each segment finished); ``wr`` is
        the host's staged-count watermark, pushed as a fresh scalar per
        dispatch.  ``live`` masks quarantined slots out of the seating —
        it lags one in-flight dispatch behind the host's quarantine
        decisions (documented divergence from the classic loop: a
        just-quarantined slot may be seated once more before the mask
        catches up).  Seating follows lane order over the finished live
        slots — exactly the order the classic loop's ascending
        admit-per-slot produced, which is what keeps the two paths
        bit-identical on fault-free streams.  Returns the resumed carry
        plus ``(meta, r_pre, outs)`` for the host's ASYNC drain —
        ``meta`` is one packed int32 vector (fin | it | hw | take |
        steps), so the steady-state drain is a single small D2H."""
        self.stats["segment_traces"] += 1      # traced once per stream
        self.stats["chain_traces"] += 1
        loop = self._loop
        (frames, env_frames, r, it, done, hw,
         steps) = self._segment_body(frames, env_frames, r, it, done,
                                     hw)
        fin = jnp.logical_or(done, it >= loop.max_iters)
        outs = self._unframe_all(frames)
        r_pre, it_pre, hw_pre = r, it, hw
        elig = jnp.logical_and(fin, live)
        e32 = elig.astype(jnp.int32)
        rank = jnp.cumsum(e32) - e32
        take = jnp.logical_and(elig, rank < (wr - rd))
        K = self._ring_depth
        pos = jnp.where(take, (rd + rank) % K, 0)
        interiors = ring[pos]
        env_sel = tuple(re_[pos] for re_ in ring_envs)
        frames, env_frames = self._chain_refill(frames, env_frames,
                                                take, interiors,
                                                env_sel)
        r = jnp.where(take, jnp.asarray(loop._id, r.dtype), r)
        it = jnp.where(take, jnp.zeros_like(it), it)
        done = jnp.where(take, jnp.zeros_like(done), done)
        hw = jnp.where(take, jnp.zeros_like(hw), hw)
        rd = rd + jnp.sum(take.astype(jnp.int32))
        # ONE packed int32 metadata word per segment: the drain's whole
        # decision state (finished mask, pre-refill iters/health, seat
        # mask, per-shard step counts) crosses the device boundary as a
        # single small transfer — payloads (outs, r) stay device-side
        # until an emission actually needs them
        meta = jnp.concatenate([
            fin.astype(jnp.int32), it_pre.astype(jnp.int32),
            hw_pre.astype(jnp.int32), take.astype(jnp.int32),
            steps.astype(jnp.int32)])
        return (frames, env_frames, r, it, done, hw, ring, ring_envs,
                rd, meta, r_pre, outs)

    def _stage_impl(self, ring, ring_envs, pos, item):
        """Pre-stage one stream item's PREPPED interior/env into the
        ring at ``pos`` — the host's read stage running AHEAD of need
        (one compilation for every stage of the stream; the ring is
        donated, so the write is in place)."""
        self.stats["stage_traces"] += 1        # traced once per stream
        from .frames import stage_ring_write
        a0, envs = self._prep1(item)
        ring = stage_ring_write(ring, a0, pos)
        ring_envs = tuple(stage_ring_write(re_, e, pos)
                          for re_, e in zip(ring_envs, envs))
        return ring, ring_envs

    def _meta_read(self, *arrs):
        """THE single device→host transfer of one chained-segment drain:
        every metadata read of a drained segment funnels through here
        (the steady-state no-host-sync guard wraps it — one call per
        segment, issued only AFTER the next segment is in flight)."""
        return jax.device_get(arrs)

    def _check_item(self, item):
        """Guard EVERY leaf of a stream item — the main array AND any
        env leaves a tuple item carries — against mid-stream shape/dtype
        drift.  Without the env check a drifted env leaf sails into the
        jitted refill and dies as an opaque XLA shape error."""
        leaves = _item_leaves(item)
        if len(leaves) != len(self._item_avals):
            raise ValueError(
                f"stream item arity changed mid-stream: slots are bound "
                f"to {len(self._item_avals)} leaves (main + env), got "
                f"{len(leaves)} (build a fresh FarmEngine per item "
                "geometry)")
        for i, (leaf, aval) in enumerate(zip(leaves, self._item_avals)):
            if leaf.shape != aval.shape or leaf.dtype != aval.dtype:
                which = ("stream item" if i == 0
                         else f"env stream item {i - 1}")
                raise ValueError(
                    f"{which} shape changed mid-stream: slots are bound "
                    f"to {aval.shape}/{aval.dtype}, got "
                    f"{leaf.shape}/{leaf.dtype} (build a fresh "
                    "FarmEngine per item geometry)")
        if self.check_finite:
            for i, leaf in enumerate(leaves):
                if np.issubdtype(leaf.dtype, np.floating) and \
                        not np.isfinite(leaf).all():
                    which = ("stream item" if i == 0
                             else f"env stream item {i - 1}")
                    raise NonFiniteItemError(
                        f"{which} carries NaN/Inf input values — "
                        "rejected at the prep boundary (a non-finite "
                        "item poisons its lane and, on the sharded "
                        "deployments, leaks into neighbour shards "
                        "through the ghost exchange; pass "
                        "check_finite=False to admit it anyway under "
                        "sentinel quarantine)")

    def _bind_carry(self):
        """Allocate the stream's carry around the bound slots: the jnp
        backend's resident (lanes, m, n) stack (the pallas backends reuse
        the lane frames ``_bind`` staged) plus the per-lane (r, it, done)
        vectors — all slots start retired (done, unoccupied)."""
        loop = self._loop
        if self.chained and loop.backend != "pallas-sharded" \
                and getattr(self, "_ring", None) is None:
            # the staging ring: K prepped (m, n) interiors (+ env
            # leaves) ahead of need, allocated once, donated in place
            # ever after.  Replicated under a lane mesh — every lane
            # shard gathers its own seats from the same ring.
            from .frames import alloc_stage_ring
            a_aval, env_avals = self._prep_avals
            K = self.stage_depth or max(2 * self.lanes, 2)
            self._ring_depth = K
            ring = alloc_stage_ring(K, a_aval.shape[1:], a_aval.dtype)
            rengs = tuple(alloc_stage_ring(K, e.shape[1:], e.dtype)
                          for e in env_avals)
            if self.mesh is None:
                self._ring = jnp.asarray(ring)
                self._ring_envs = tuple(jnp.asarray(x) for x in rengs)
            else:
                rep = NamedSharding(self.mesh, P())
                self._ring = jax.device_put(ring, rep)
                self._ring_envs = tuple(jax.device_put(x, rep)
                                        for x in rengs)
        if getattr(self, "_cont_carry", None) is not None:
            return          # slots + carry persist across streams: the
                            # end state (all lanes retired) is exactly a
                            # valid start state for the next stream
        a_aval, env_avals = self._prep_avals
        L = self.lanes
        if loop.backend == "jnp":
            frames = np.zeros(a_aval.shape, a_aval.dtype)
            envs = tuple(np.zeros(e.shape, e.dtype) for e in env_avals)
            if self.mesh is None:
                self._frames = jnp.asarray(frames)
                self._env_frames = tuple(jnp.asarray(e) for e in envs)
            else:
                lane_sh = NamedSharding(self.mesh, P(self.lane_axis))
                self._frames = jax.device_put(frames, lane_sh)
                self._env_frames = tuple(
                    jax.device_put(e, lane_sh) for e in envs)
        if loop.backend == "pallas-sharded":
            # the per-lane reduce dtype, evaluated abstractly through
            # the same shard_map the segments run in (the lane frames
            # _bind staged are already the stream's slots)
            from repro.sharding.specs import shard_map

            fspec = self._fspec()
            fn = shard_map(
                lambda fr, efs: self._eng.sweeps_lanes(
                    fr, efs, self._lspec)[1],
                mesh=self.mesh,
                in_specs=(fspec, tuple(fspec for _ in self._env_frames)),
                out_specs=P(self.lane_axis))
            r_aval = jax.eval_shape(fn, self._frames, self._env_frames)
        else:
            r_aval = jax.eval_shape(
                lambda fr, ef: self._lane_step(ef)(fr)[1],
                self._frames, self._env_frames)
        r0 = np.full((L,), loop._id, np.dtype(r_aval.dtype))
        it0 = np.zeros((L,), np.int32)
        d0 = np.ones((L,), bool)
        hw0 = np.zeros((L,), np.int32)
        if self.mesh is None:
            carry = tuple(jnp.asarray(x) for x in (r0, it0, d0, hw0))
        else:
            lane_sh = NamedSharding(self.mesh, P(self.lane_axis))
            carry = tuple(jax.device_put(x, lane_sh)
                          for x in (r0, it0, d0, hw0))
        self._cont_carry = carry

    # -- snapshot / restore (preemption recovery) ------------------------
    def snapshot(self) -> dict:
        """The in-flight stream state as ONE logical tree:
        every occupied slot's mid-flight interior (extracted UNSHARDED,
        whatever the deployment), its ``(reduce, iter, health)`` carry
        and raw item, the retry queue, and the source cursor
        (``next_index``).  Everything is topology-free — a snapshot
        taken at lanes=L over mesh=M restores onto any other lane
        count / mesh (:meth:`restore` repacks the interiors through the
        same refill machinery fresh items use).  Slot quarantine and
        bad-slot sets are deliberately NOT captured: they describe the
        old process's physical slots, not the stream.

        Only meaningful at a segment boundary — call it from an
        ``on_segment`` callback (or pass ``recovery=`` to
        :meth:`run`, which snapshots automatically)."""
        if self._rt_capture is None:
            raise ValueError(
                "snapshot() captures in-flight stream state; nothing "
                "has streamed yet — call run (pass recovery= "
                "to persist snapshots automatically)")
        return self._rt_capture()

    def restore(self, state: dict) -> "FarmEngine":
        """Stage a :meth:`snapshot` tree; the next :meth:`run`
        resumes from it: the source is fast-forwarded past the snapshot's
        cursor, in-flight occupants re-enter fresh slots mid-iteration,
        and pre-crash retries keep their attempt counts.  The engine's
        own geometry may differ from the snapshotting engine's (elastic
        resume); the ITEM geometry may not."""
        if not isinstance(state, dict) or state.get("kind") != "farm":
            raise ValueError("not a FarmEngine snapshot tree")
        if int(state.get("version", -1)) != 1:
            raise ValueError("unsupported FarmEngine snapshot version "
                             f"{state.get('version')!r}")
        self._resume_state = state
        return self

    def run(self, source, sink, *, recovery=None, resume: bool = False,
            on_segment: Optional[Callable] = None,
            continuous: bool = True) -> int:
        """Drive a whole stream with per-lane refill.

        ``source`` yields items (a callable returning an iterator, or an
        iterable).  ``sink`` receives one :class:`StreamResult` per
        stream item — EXACTLY once each, in COMPLETION order (``.index``
        is the stream position).  Protocol: the farm advances in bounded
        segments; the moment a lane's convergence loop finishes, its
        (m, n) result is extracted, the next queued item takes over the
        slot in place, and the SAME carry resumes — the other lanes
        never notice.  One compilation serves every segment, refill and
        extraction.

        Failure semantics (DESIGN.md §Failure semantics): a lane the
        sentinel quarantined (poisoned / diverged) or that exhausted its
        iteration budget finishes with a non-ok ``status``.  With
        ``max_attempts > 1`` such an item re-enters a bounded retry
        queue and is re-admitted into a FRESH slot (a fault pinned to a
        slot must not follow the item); once its attempts are exhausted
        it is emitted with its final non-ok status and recorded on
        ``dead_letter``.  A slot that fails ``slot_patience``
        CONSECUTIVE occupants is itself quarantined — retired from the
        refill rotation (``stats["quarantined_slots"]``) — unless it is
        the last slot standing.  Items failing the admission-time
        finite check emit ``status="rejected"`` without touching a
        slot.  Sweeps burned on non-ok occupants are booked as
        ``stats["quarantined_lane_steps"]`` next to
        ``stats["wasted_lane_steps"]``.

        Preemption recovery (DESIGN.md §Recovery): with ``recovery=``
        (a :class:`repro.resilience.recovery.RecoveryConfig`) every
        emitted result is write-ahead journaled (fsync'd, CRC-framed)
        BEFORE it reaches the sink, and the whole in-flight state — see
        :meth:`snapshot` — is published atomically every
        ``snapshot_every`` segments.  ``resume=True`` restarts a killed
        run: the journal replays pre-crash results to the sink (each
        index suppressed from re-emission — exactly-once across
        restarts), the source is fast-forwarded past the snapshot
        cursor (it must re-yield the same items from position 0 —
        deterministic sources, the property retries already rely on),
        and occupants continue mid-iteration.  The resumed engine may
        run a DIFFERENT lane count or mesh (elastic resume).  RPO: at
        most ``snapshot_every`` segments of compute are redone; no
        emitted result is ever emitted twice.  ``on_segment`` is called
        with the cumulative segment count at every segment boundary —
        the seam ``FaultPlan.preempt_hook`` kills through, and where a
        caller may take its own :meth:`snapshot`.  ``continuous=True``
        changes nothing; ``False`` raises (the farm has no other mode).
        """
        import time as _time

        if not continuous:
            raise ValueError("FarmEngine.run streams continuously only; "
                             "continuous=False is not supported")

        t_resume0 = _time.perf_counter()
        state = None
        if self._resume_state is not None:
            state, self._resume_state = self._resume_state, None
        elif recovery is not None and resume:
            from repro.resilience.recovery import load_snapshot
            state = load_snapshot(recovery.snap_dir)

        journal = None
        emitted_pre: set = set()
        n_out = 0

        def deliver(res, journal_rec=True):
            """WAL-ordered emission: journal (fsync'd) FIRST, then the
            sink.  A raising sink degrades the result to ``dead_letter``
            with its error attached instead of killing the stream and
            losing the in-flight slots' items — the journal already
            holds the payload, so a resumed run re-delivers it."""
            nonlocal n_out
            if journal is not None and journal_rec:
                journal.append({
                    "index": int(res.index), "status": res.status,
                    "attempts": int(res.attempts),
                    "iters": int(res.iters), "reduced": res.reduced,
                    "a": res.a, "error": res.error})
            try:
                sink(res)
            except Exception as e:
                self.stats["sink_errors"] += 1
                res = dataclasses.replace(
                    res,
                    status="failed" if res.status == "ok" else res.status,
                    error=f"sink raised: {type(e).__name__}: {e}")
            if res.status != "ok":
                self.dead_letter.append(res)
            n_out += 1

        if recovery is not None and resume:
            from repro.resilience.recovery import Journal
            for rec in Journal.replay(recovery.journal_path):
                ridx = int(rec["index"])
                if ridx in emitted_pre:
                    continue
                emitted_pre.add(ridx)
                deliver(StreamResult(
                    index=ridx, a=rec.get("a"),
                    reduced=rec.get("reduced"),
                    iters=np.int32(rec.get("iters") or 0),
                    status=rec.get("status", "ok"),
                    attempts=int(rec.get("attempts") or 1),
                    error=rec.get("error")), journal_rec=False)
                self.stats["replayed_items"] += 1
        if recovery is not None:
            from repro.resilience.recovery import Journal
            journal = Journal(recovery.journal_path,
                              fsync=recovery.fsync)

        if state is not None and state.get("complete"):
            # the preempted run had already drained its stream; the
            # journal replay above re-delivered every result (the
            # segment counter still restores — snapshot step numbering
            # stays monotonic if this engine runs again)
            self.stats["segments"] = int(state.get("segments", 0))
            if journal is not None:
                journal.close()
            self.stats["items"] += n_out
            self.stats["recovery_seconds"] += (
                _time.perf_counter() - t_resume0)
            return n_out

        stream = iter(source() if callable(source) else source)
        pending = None
        saved_occ = list(state.get("occupants") or ()) if state else []
        saved_retry = list(state.get("retry") or ()) if state else []
        if state is not None:
            # fast-forward the source cursor: positions below
            # next_index were pulled pre-crash — each is either in the
            # snapshot (in flight / queued) or in the journal (emitted)
            next_index = int(state["next_index"])
            stream = islice(stream, next_index, None)
            probe = None
            if saved_occ or saved_retry:
                probe = _as_item((saved_occ + saved_retry)[0]["item"])
            else:
                first = next(stream, None)
                if first is not None:
                    pending = probe = _as_item(first)
        else:
            next_index = 0
            probe = None
            first = next(stream, None)
            if first is not None:
                pending = probe = _as_item(first)
        if probe is None:      # nothing in flight AND stream drained
            if journal is not None:
                journal.close()
            self.stats["items"] += n_out
            return n_out
        if not self._bound:
            self._bind(probe)
        self._bind_carry()
        loop = self._loop
        L, unroll = self.lanes, loop.unroll
        frames, env_frames = self._frames, self._env_frames
        r, itv, done, hw = self._cont_carry
        occupants: list = [None] * L      # slot -> in-flight entry
        slot_dead = [False] * L           # quarantined slots
        slot_fails = [0] * L              # consecutive non-ok finishes
        retry_q: list = []
        staged: deque = deque()           # entries resident in the
                                          # staging ring (chained mode),
                                          # ring-FIFO order
        pending_entries: deque = deque()  # entries pulled off the
                                          # stream but unstaged (repair
                                          # rewinds the ring through
                                          # here) — ahead of the cursor
        prev_it = np.zeros((L,), np.int64)

        if state is not None:
            # restored occupants re-enter through the retry-first
            # admission path, carrying their saved mid-flight state (a
            # resumed engine with FEWER lanes simply keeps the excess
            # queued); plain retries keep their attempt counts.  Slot
            # quarantine / bad-slot sets are physical facts about the
            # dead process's hardware and do not survive.
            self.stats["segments"] = int(state.get("segments", 0))
            for e in saved_occ:
                retry_q.append({
                    "index": int(e["index"]), "item": e["item"],
                    "attempts": int(e["attempts"]), "bad_slots": set(),
                    "carry": (e["a"], e["r"], int(e["it"]),
                              int(e["hw"]))})
            for e in saved_retry:
                retry_q.append({
                    "index": int(e["index"]), "item": e["item"],
                    "attempts": int(e["attempts"]), "bad_slots": set()})

        def pull_stream():
            """Next stream item as an in-flight entry (index assigned at
            pull time — the emission contract is exactly-once per
            index, whatever slots or retries it passes through)."""
            nonlocal pending, next_index
            if pending is not None:
                x, pending = pending, None
            else:
                x = next(stream, None)
                x = None if x is None else _as_item(x)
            if x is None:
                return None
            entry = {"index": next_index, "item": x, "attempts": 0,
                     "bad_slots": set()}
            next_index += 1
            return entry

        def next_entry(slot):
            """Retry entries first (fresh slots only), then the stream.
            A retry whose bad-slot set covers this slot re-enters it
            only as a last resort — stream drained AND no other live
            slot that could ever take it (the lanes=1 degenerate)."""
            for i, e in enumerate(retry_q):
                if slot not in e["bad_slots"]:
                    return retry_q.pop(i)
            if pending_entries:     # unstaged ring entries precede the
                return pending_entries.popleft()   # stream cursor
            e = pull_stream()
            if e is not None:
                return e
            others_live = any(
                occupants[s] is not None and not slot_dead[s]
                for s in range(L) if s != slot)
            if retry_q and not others_live:
                return retry_q.pop(0)
            return None

        def emit(entry, status, a=None, reduced=None, iters=0):
            deliver(StreamResult(index=entry["index"], a=a,
                                 reduced=reduced, iters=np.int32(iters),
                                 status=status,
                                 attempts=entry["attempts"]))

        def refill(slot, entry):
            nonlocal frames, env_frames, r, itv, done, hw
            carry = entry.pop("carry", None)
            if carry is None:
                entry["attempts"] += 1
                frames, env_frames, r, itv, done, hw = self._refill_fn(
                    frames, env_frames, r, itv, done, hw,
                    jnp.asarray(slot, jnp.int32),
                    jax.tree.map(jnp.asarray, entry["item"]))
                prev_it[slot] = 0
            else:
                # a snapshotted occupant continues its SAME occupation
                # (attempts unchanged) from its saved iteration
                a_mid, rs, its, hws = carry
                frames, env_frames, r, itv, done, hw = self._restore_fn(
                    frames, env_frames, r, itv, done, hw,
                    jnp.asarray(slot, jnp.int32),
                    jax.tree.map(jnp.asarray, entry["item"]),
                    jnp.asarray(a_mid), jnp.asarray(rs),
                    jnp.asarray(its, jnp.int32),
                    jnp.asarray(hws, jnp.int32))
                prev_it[slot] = int(its)
                self.stats["recovered_occupants"] += 1
            occupants[slot] = entry
            self.stats["h2d_bytes"] += _item_nbytes(entry["item"])
            self.stats["refills"] += 1

        def admit(slot):
            """Fill one free slot, skipping past items the admission
            guard rejects (they emit + dead-letter without consuming
            the slot; drift errors still raise) and items whose final
            result was journaled pre-crash (already re-delivered by the
            replay — recomputing them would break exactly-once)."""
            while True:
                entry = next_entry(slot)
                if entry is None:
                    return
                if entry["index"] in emitted_pre:
                    continue
                try:
                    self._check_item(entry["item"])
                except NonFiniteItemError:
                    self.stats["rejected"] += 1
                    emit(entry, "rejected")
                    continue
                refill(slot, entry)
                return

        def capture(complete=None):
            """Build the :meth:`snapshot` tree from the live run state.
            Interiors extract through the un-donated ``_extract_fn`` —
            the resident frames stay untouched."""
            r_cur = np.asarray(r)
            it_cur = np.asarray(itv).astype(np.int64)
            hw_cur = np.asarray(hw)
            occ = []
            for s in range(L):
                e = occupants[s]
                if e is None:
                    continue
                a_mid = np.asarray(self._extract_fn(
                    frames, jnp.asarray(s, jnp.int32)))
                occ.append({"index": int(e["index"]),
                            "attempts": int(e["attempts"]),
                            "item": e["item"], "a": a_mid,
                            "r": r_cur[s], "it": int(it_cur[s]),
                            "hw": int(hw_cur[s])})
            queued = list(retry_q) + list(staged) + list(pending_entries)
            if complete is None:
                complete = not occ and not queued
            return {"kind": "farm", "version": 1,
                    "segments": int(self.stats["segments"]),
                    "next_index": int(next_index), "n_out": int(n_out),
                    "occupants": occ,
                    # retries first, then ring-staged / unstaged entries
                    # in stream order — a staged-but-unseated item is
                    # queued work the resumed run must not lose
                    "retry": [{"index": int(e["index"]),
                               "attempts": int(e["attempts"]),
                               "item": e["item"]} for e in queued],
                    "complete": bool(complete)}

        self._rt_capture = capture

        def persist(complete=None):
            if recovery is None:
                return
            from repro.resilience.recovery import save_snapshot
            save_snapshot(recovery.snap_dir, self.stats["segments"],
                          capture(complete), keep=recovery.keep)
            self.stats["snapshots"] += 1

        ring = getattr(self, "_ring", None)
        ring_envs = getattr(self, "_ring_envs", ())

        def run_chained():
            """The chained dispatch pipeline: stage(t+1) ∥ run(t) ∥
            drain(t−1).  Every steady-state segment boundary is ONE
            donated ``_chain_fn`` dispatch — segment, emission capture
            and masked batch refill from the device staging ring fused
            into a single jitted call — and the host touches segment
            t's results only through a non-blocking metadata read issued
            AFTER segment t+1 is already in flight.  Retries drop to a
            synchronous repair phase (classic retry-first / bad-slot /
            quarantine admission, ring rewound through
            ``pending_entries``), then the chain resumes."""
            nonlocal frames, env_frames, r, itv, done, hw, prev_it
            nonlocal ring, ring_envs
            K = self._ring_depth
            local_L = L // self._nshards
            rd = jnp.asarray(0, jnp.int32)   # device-side read cursor
            if self.mesh is not None:        # as the entry returns it:
                rd = jax.device_put(         # one trace per stream
                    rd, NamedSharding(self.mesh, P()))
            wr_host = 0                     # staged-count watermark
            rd_host = 0                      # host mirror of rd (lags
                                             # by the in-flight takes)
            inflight: deque = deque()        # dispatched, undrained

            def stage_next():
                """Admission-checked staging of ONE entry into the ring
                (the chained twin of ``admit``): rejected items emit
                without touching the ring, journal-replayed indexes
                skip, everything else device_puts AHEAD of need."""
                nonlocal ring, ring_envs, wr_host
                while True:
                    if pending_entries:
                        entry = pending_entries.popleft()
                    else:
                        entry = pull_stream()
                    if entry is None:
                        return False
                    if entry["index"] in emitted_pre:
                        continue
                    try:
                        with span("farm.check"):
                            self._check_item(entry["item"])
                    except NonFiniteItemError:
                        self.stats["rejected"] += 1
                        emit(entry, "rejected")
                        continue
                    break
                # item leaves ride as numpy through the jit fast path —
                # no eager per-leaf device_put on the host's stage side
                with span("farm.prep"):
                    ring, ring_envs = self._stage_fn(
                        ring, ring_envs, np.int32(wr_host % K),
                        entry["item"])
                staged.append(entry)
                wr_host += 1
                self.stats["h2d_bytes"] += _item_nbytes(entry["item"])
                return True

            def top_up():
                # rd_host is a conservative lower bound on the device
                # cursor, so staying < K deep can never overwrite a
                # ring position an in-flight chain might still read
                while wr_host - rd_host < K:
                    with span("farm.stage"):
                        if not stage_next():
                            return

            def unstage_all():
                """Rewind the ring at a repair boundary: un-seated
                entries re-queue (stream order) ahead of the cursor,
                their device copies are abandoned, and the watermark
                drops back to the mirror cursor — safe because the
                pipeline is fully drained here."""
                nonlocal wr_host
                while staged:
                    pending_entries.appendleft(staged.pop())
                wr_host = rd_host

            # the live mask changes only on quarantine — cache its
            # device copy so the steady-state dispatch pays no per-call
            # host→device conversion (the per-dispatch `wr` watermark
            # rides as a numpy scalar through the jit fast path)
            live_cache = [None, None]          # (key, device array)

            def live_mask():
                key = tuple(slot_dead)
                if live_cache[0] != key:
                    live_cache[0] = key
                    live_cache[1] = jnp.asarray(
                        np.logical_not(slot_dead))
                return live_cache[1]

            def dispatch():
                nonlocal frames, env_frames, r, itv, done, hw
                nonlocal ring, ring_envs, rd
                with span("farm.dispatch"):
                    (frames, env_frames, r, itv, done, hw, ring,
                     ring_envs, rd, meta, r_pre, outs) = self._chain_fn(
                         frames, env_frames, r, itv, done, hw, ring,
                         ring_envs, rd, np.int32(wr_host), live_mask())
                self.stats["segments"] += 1
                if on_segment is not None:
                    # the preemption seam, as in the classic loop:
                    # fires while the segment's results are still
                    # un-journaled (redone from the last snapshot,
                    # never re-emitted)
                    on_segment(self.stats["segments"])
                inflight.append((meta, r_pre, outs))

            def drain_one():
                """Consume the OLDEST in-flight segment: one async
                metadata read (``_meta_read`` — by now the next segment
                is dispatched, so the device never idles on this),
                then classic emission / retry / quarantine bookkeeping
                and the host-mirror replay of the device's ring seats
                (lane order over the finished live slots = the device's
                rank order)."""
                nonlocal prev_it, rd_host
                with span("farm.drain"):
                    meta_d, r_d, outs_d = inflight.popleft()
                    (meta_h,) = self._meta_read(meta_d)
                    fin_h = meta_h[0:L] != 0
                    it_h = meta_h[L:2 * L].astype(np.int64)
                    hw_h = meta_h[2 * L:3 * L]
                    took_h = meta_h[3 * L:4 * L] != 0
                    steps_h = meta_h[4 * L:]
                    for s in range(self._nshards):
                        sl = slice(s * local_L, (s + 1) * local_L)
                        total = int(steps_h[s]) * unroll * local_L
                        useful = int((it_h[sl] - prev_it[sl]).sum())
                        self.stats["lane_steps"] += total
                        self.stats["wasted_lane_steps"] += total - useful
                    prev_it = np.where(took_h, 0, it_h)
                    outs_h = r_h = None
                    for slot in range(L):
                        entry = occupants[slot]
                        if entry is None or not fin_h[slot]:
                            continue
                        occupants[slot] = None
                        status = item_status(hw_h[slot], it_h[slot],
                                             loop.max_iters)
                        if status != "ok":
                            self.stats["quarantined_lane_steps"] += \
                                int(it_h[slot])
                            slot_fails[slot] += 1
                        else:
                            slot_fails[slot] = 0
                        if status != "ok" and \
                                entry["attempts"] < self.max_attempts:
                            entry["bad_slots"].add(slot)
                            retry_q.append(entry)
                            self.stats["retries"] += 1
                        else:
                            if outs_h is None:   # ONE payload pull per
                                outs_h, r_h = jax.device_get(  # drained seg
                                    (outs_d, r_d))
                            out = outs_h[slot]
                            self.stats["d2h_bytes"] += (
                                out.nbytes + r_h[slot].nbytes + 4)
                            emit(entry, status, a=out, reduced=r_h[slot],
                                 iters=it_h[slot])
                        if (not slot_dead[slot]
                                and slot_fails[slot] >= self.slot_patience
                                and L - sum(slot_dead) > 1):
                            # quarantine lags one in-flight dispatch: the
                            # chain already in flight may seat one more
                            # occupant here before the live mask catches up
                            slot_dead[slot] = True
                            self.stats["quarantined_slots"] += 1
                    for slot in range(L):
                        if not took_h[slot]:
                            continue
                        assert staged, "device seated more than was staged"
                        entry = staged.popleft()
                        entry["attempts"] += 1
                        occupants[slot] = entry
                        self.stats["refills"] += 1
                        rd_host += 1

            while True:
                dispatched = False
                if retry_q:
                    # repair: drain the pipeline, rewind the ring, and
                    # run synchronously on classic admission until the
                    # retry queue is dry (quarantine-exact, retry-first,
                    # bad-slot-aware — the fault contracts unchanged)
                    while inflight:
                        drain_one()
                    unstage_all()
                    for slot in range(L):
                        if occupants[slot] is None \
                                and not slot_dead[slot]:
                            admit(slot)
                    if not any(o is not None for o in occupants):
                        break
                    dispatch()
                    dispatched = True
                    drain_one()
                else:
                    top_up()
                    work = (any(o is not None for o in occupants)
                            or bool(staged) or bool(pending_entries))
                    if not work and not inflight:
                        break
                    if work:
                        dispatch()
                        dispatched = True
                    # lag-1 drain: with a fresh dispatch in flight,
                    # consume only the PREVIOUS segment — the read
                    # overlaps the device's current segment.  With no
                    # dispatch left (tail), flush what remains.
                    if len(inflight) > (1 if dispatched else 0):
                        drain_one()
                if dispatched and recovery is not None and \
                        self.stats["segments"] % \
                        recovery.snapshot_every == 0:
                    # snapshot boundary: ONE explicit pipeline drain
                    # (instead of the classic loop's implicit blocking
                    # sync every segment), then capture a consistent
                    # boundary state
                    while inflight:
                        drain_one()
                    persist()

        try:
            local_L = L // self._nshards
            use_chain = (self.chained
                         and loop.backend != "pallas-sharded")
            # a FRESH chained stream seats its whole first cohort
            # through the staging ring: every slot starts retired, and
            # the first chain dispatch (a zero-step segment) batch-seats
            # from the ring — one fused call instead of L sequential
            # put + per-slot-refill dispatches.  Resumed runs keep the
            # classic admission: mid-flight occupants re-enter through
            # the carry-aware restore path the ring knows nothing about.
            chain_seed = use_chain and state is None and not resume
            if chain_seed:
                r = jnp.full_like(r, loop._id)
                itv = jnp.full_like(itv, loop.max_iters)
                done = jnp.ones_like(done)
                hw = jnp.zeros_like(hw)
            else:
                for slot in range(L):
                    admit(slot)
                    if occupants[slot] is None:  # stream already drained
                        break
            # retired slots may carry iteration counts from a previous
            # stream — baseline the useful-work deltas on the real carry
            prev_it = np.asarray(itv).astype(np.int64)
            persist(complete=False)   # RPO anchor: recoverable before
                                      # the first segment even starts
            if state is not None or resume:
                self.stats["recovery_seconds"] += (
                    _time.perf_counter() - t_resume0)

            if use_chain:
                # composed pallas-sharded farms stay on the classic
                # loop below: their fixed-step segments have no early
                # exit to chain past, and refill must live inside the
                # spatial shard_map
                run_chained()
            else:
                while any(o is not None for o in occupants):
                    (frames, env_frames, r, itv, done, hw,
                     steps) = self._segment_fn(frames, env_frames, r,
                                               itv, done, hw)
                    self.stats["segments"] += 1
                    if on_segment is not None:
                        # the preemption seam: fires BEFORE this
                        # segment's results are journaled — the
                        # harshest crash point (computed-but-
                        # unjournaled work is redone from the last
                        # snapshot, never re-emitted)
                        on_segment(self.stats["segments"])
                    done_h = np.asarray(done)
                    it_h = np.asarray(itv).astype(np.int64)
                    r_h = np.asarray(r)
                    hw_h = np.asarray(hw)
                    steps_h = np.asarray(steps).astype(np.int64)
                    # lane-step accounting: every body step advances
                    # (or idles) every lane of its shard by `unroll`
                    # sweeps
                    for s in range(self._nshards):
                        sl = slice(s * local_L, (s + 1) * local_L)
                        total = int(steps_h[s]) * unroll * local_L
                        useful = int((it_h[sl] - prev_it[sl]).sum())
                        self.stats["lane_steps"] += total
                        self.stats["wasted_lane_steps"] += \
                            total - useful
                    prev_it = it_h.copy()
                    finished = done_h | (it_h >= loop.max_iters)
                    for slot in range(L):
                        entry = occupants[slot]
                        if entry is None or not finished[slot]:
                            continue
                        occupants[slot] = None
                        status = item_status(hw_h[slot], it_h[slot],
                                             loop.max_iters)
                        if status != "ok":
                            # sweeps burned on a doomed occupant
                            self.stats["quarantined_lane_steps"] += \
                                int(it_h[slot])
                            slot_fails[slot] += 1
                        else:
                            slot_fails[slot] = 0
                        if status != "ok" and \
                                entry["attempts"] < self.max_attempts:
                            entry["bad_slots"].add(slot)
                            retry_q.append(entry)
                            self.stats["retries"] += 1
                        else:
                            out = np.asarray(self._extract_fn(
                                frames, jnp.asarray(slot, jnp.int32)))
                            self.stats["d2h_bytes"] += (
                                out.nbytes + r_h[slot].nbytes + 4)
                            emit(entry, status, a=out,
                                 reduced=r_h[slot], iters=it_h[slot])
                        if (not slot_dead[slot]
                                and slot_fails[slot] >=
                                self.slot_patience
                                and L - sum(slot_dead) > 1):
                            # the failures track the SLOT, not its
                            # items: retire it from the rotation
                            # (never the last slot standing)
                            slot_dead[slot] = True
                            self.stats["quarantined_slots"] += 1
                            continue
                        if not slot_dead[slot]:
                            admit(slot)
                    if recovery is not None and \
                            self.stats["segments"] % \
                            recovery.snapshot_every == 0:
                        persist()
            persist(complete=True)
        finally:
            # locals always name the LIVE buffers (the donated inputs
            # were consumed by the calls that produced these), so a
            # raising sink / shape check cannot strand the engine on
            # deleted device buffers
            self._frames, self._env_frames = frames, env_frames
            self._cont_carry = (r, itv, done, hw)
            if ring is not None:
                self._ring, self._ring_envs = ring, ring_envs
            if journal is not None:
                journal.close()
        self.stats["items"] += n_out
        return n_out
