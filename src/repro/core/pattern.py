"""The Loop-of-stencil-reduce pattern — production implementation.

Pattern semantics (paper §3.1, all variants, composable):

    repeat
        a = stencil(σ_k, f) : a          # -i: f also sees absolute indexes
        [d = α(δ) : ⟨a_new, a_old⟩]      # -d: measure the change
        [s = update(s, ...)]             # -s: global loop state
    until c(/⊕ : a_or_d [, s])

The whole loop lowers into a single ``jax.lax.while_loop`` — the TPU
realisation of the paper's *device memory persistence*: the grid never
leaves HBM, buffers are swapped by XLA, and (beyond the paper) even the
convergence reduce + condition stay on device.

The ``backend`` axis picks the loop-body realisation (see
:mod:`repro.core.executor`): ``"jnp"`` applies the stencil through the
shift algebra (pad per application); ``"pallas"`` iterates the fused
Pallas kernel on a *persistent halo frame* — padding and block round-up
happen once before the loop, two frames that swap roles every sweep are
the while-carry, and only the O(m+n) ghost ring is re-asserted per
sweep; ``"pallas-multistep"`` additionally fuses ``unroll`` sweeps per
HBM round-trip (temporal blocking).  Read-only per-cell fields (the
paper's ``env``) enter through ``run(..., env=(...))`` and are staged
once alongside the frame.

The pattern is ``vmap``-safe: under ``farm`` (streaming 1:1 mode) each
stream item runs to its own trip count while vmap executes until all are
done (JAX's batched ``while_loop`` keeps a finished lane's carry).
:meth:`LoopOfStencilReduce.farm_run` makes that mode first-class — ONE
while_loop over a stacked (lanes, frame) carry with per-lane done masks —
and
:class:`repro.core.streaming.FarmEngine` streams through it with lane
slots that persist (and are refilled in place) across stream items.

``step`` mode generalises the stencil to an arbitrary pytree transformer —
the k=0 map-reduce case the paper notes is subsumed — which is how the
trainer (:mod:`repro.train.trainer`) and the decode engine
(:mod:`repro.serve.engine`) instantiate the pattern.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from .executor import BACKENDS
from .reduce import (HEALTH_STALL_MASK, health_update, resolve_monoid,
                     tree_reduce)
from .semantics import Boundary
from .spans import scope
from .stencil import stencil_taps, stencil_windows, stencil_indexed


def segmented_while(body, carry, *, finished, segment, early_exit=True):
    """Bounded early-exit slice of a done-masked lane loop.

    The continuous-refill primitive shared by the farm tier
    (:meth:`LoopOfStencilReduce.lane_segment`) and the serve tier
    (:class:`repro.serve.engine.ContinuousEngine`): run ``body`` (carry →
    carry) until

    * any lane **newly** satisfies ``finished(carry)`` (a (lanes,) bool —
      the dispatcher must be told so it can refill that lane's slot), or
    * no unfinished lane remains (nothing left to advance), or
    * ``segment`` body steps have elapsed (the bounded-latency knob: the
      dispatcher regains control at least this often even when nothing
      converges, e.g. to admit work that arrived after the segment was
      dispatched).

    Lanes already finished at entry do NOT trigger the early exit — only
    a 0→1 transition of the finished mask does, so a segment entered with
    retired lanes (queue drained) keeps advancing the live ones.
    Returns ``(carry', steps)``; the carry shapes round-trip unchanged,
    so ONE compilation serves every segment.

    ``early_exit=False`` runs EXACTLY ``segment`` done-masked body steps
    instead (a ``fori_loop`` — no data-dependent trip count).  This is
    the uniform-schedule variant for deployments whose body carries
    collectives that must stay step-aligned across independently paced
    shard groups: the composed lanes × spatial farm exchanges ghost
    strips by ppermute inside the body, so a data-dependent early exit
    on one lane shard would desynchronise the other shards' exchange
    rendezvous (the convergence masks still freeze each lane at its own
    trip count — only the *schedule* is fixed).
    """
    if not early_exit:
        carry = jax.lax.fori_loop(0, segment, lambda _, c: body(c), carry)
        return carry, jnp.asarray(segment, jnp.int32)
    fin0 = finished(carry)

    def seg_body(c):
        inner, steps = c
        return body(inner), steps + 1

    def seg_cond(c):
        inner, steps = c
        fin = finished(inner)
        newly = jnp.any(jnp.logical_and(fin, jnp.logical_not(fin0)))
        return jnp.logical_and(
            jnp.any(jnp.logical_not(fin)),
            jnp.logical_and(steps < segment, jnp.logical_not(newly)))

    carry, steps = jax.lax.while_loop(
        seg_cond, seg_body, (carry, jnp.asarray(0, jnp.int32)))
    return carry, steps


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LoopResult:
    """Final state of a Loop-of-stencil-reduce run (a pytree: farm/vmap-able)."""
    a: Any                 # the converged array (or pytree in step mode)
    reduced: jnp.ndarray   # last /⊕ value (what the condition saw)
    iters: jnp.ndarray     # number of stencil iterations executed
    state: Any = None      # final loop state (-s variant), None otherwise
    health: Any = None     # packed per-lane health word(s) — decode with
                           # repro.core.reduce.health_status


@dataclasses.dataclass
class LoopOfStencilReduce:
    """Loop-of-stencil-reduce(k, f, ⊕, c, a) with -i / -d / -s variants.

    Parameters
    ----------
    f:        elemental function.  Signature depends on ``mode``:
                taps    — f(get) -> array              (fast shift algebra)
                windows — f(w) -> array                (materialised σ_k)
                indexed — f(w, idx) -> array           (-i variant, σ̄_k)
                step    — f(a) -> a                    (generalised map step)
    k:        stencil radius (halo depth).  Ignored in step mode.
    combine:  ⊕ — a monoid name ('sum','max','min','any','all','prod') or a
              binary associative callable (then ``identity`` is required).
    cond:     c — termination condition.  c(reduced) or c(reduced, state)
              when ``state_init`` is given.  Loop stops when it returns True
              (paper's repeat/until: the body always runs at least once).
    delta:    δ — optional; switches on the -d variant: the reduce runs over
              ``delta(a_new, a_old)`` instead of ``a_new``.
    measure:  optional map from the post-step value to the array the reduce
              folds (needed in step mode when ``a`` is a pytree).
    state_init / state_update: the -s variant.  ``state_update(s, reduced_
              input_array, it)`` runs after the stencil, before the reduce
              feeds the condition.
    boundary: ⊥ model at the domain edge (zero/nan/reflect/wrap).
    max_iters: hard iteration cap (safety net; the paper's runtime has the
              same guard in the iteration-condition plumbing).
    unroll:   check the condition every ``unroll`` stencil applications
              (beyond-paper optimisation: amortises the reduce+condition;
              may overshoot convergence by < unroll iterations).  Under
              ``backend="pallas-multistep"`` this is also the temporal-
              blocking depth T (sweeps fused per HBM round-trip).
              ``unroll="auto"`` picks T from the cost heuristic
              (:func:`repro.core.executor.auto_unroll`: mesh-aware
              k·T < min(local m, n) ceiling + redundant-compute limit) at
              ``run`` time, once the grid shape is known; an explicit
              infeasible T raises with the feasible ceiling spelled out.
    backend:  loop-body realisation — "jnp" (shift algebra), "pallas"
              (fused kernel on a persistent halo frame),
              "pallas-multistep" (temporal blocking), or "pallas-sharded"
              (the 1:n deployment: the whole loop inside ``shard_map``,
              per-shard frames, ppermute ghost exchange, collective
              reduce; requires ``partition``).  Pallas backends require
              ``mode="taps"`` and a 2-D array.
    partition: a :class:`repro.sharding.specs.GridPartition` describing
              the mesh decomposition — required by (and only meaningful
              for) ``backend="pallas-sharded"``.
    block:    Pallas tile shape (clipped to the rounded domain).
    interpret: force Pallas interpret mode (None = auto: interpret
              everywhere but TPU).
    sentinel: a :class:`repro.core.reduce.Sentinel` health policy, or
              None (the default — only the CONVERGED bit is tracked).
              The sentinel reads the SAME fused reduce value the
              condition sees (zero extra passes): a lane whose reduce
              goes NaN/Inf (``nan=True``) or fails to decrease for
              ``patience`` consecutive checks is QUARANTINED — masked
              done immediately so it stops spinning (and, in the
              composed deployment, stops feeding the step-aligned ghost
              exchange).  Decode the per-lane outcome from
              ``LoopResult.health`` with :func:`repro.core.reduce.
              health_status`.
    fault_hook: deterministic fault-injection seam (lane paths only):
              ``hook(r, it) -> r`` intercepts the (lanes,) reduce vector
              after each check — see :mod:`repro.resilience.faults`.
              Production deployments leave it None.
    """

    f: Callable
    k: int = 1
    combine: Any = "sum"
    identity: Any = None
    cond: Callable = None
    mode: str = "taps"
    delta: Optional[Callable] = None
    measure: Optional[Callable] = None
    state_init: Optional[Callable] = None
    state_update: Optional[Callable] = None
    boundary: Boundary | str = Boundary.ZERO
    max_iters: int = 10_000
    unroll: int = 1
    backend: str = "jnp"
    partition: Optional[Any] = None
    block: tuple = (256, 256)
    interpret: Optional[bool] = None
    sentinel: Optional[Any] = None
    fault_hook: Optional[Callable] = None

    def __post_init__(self):
        self._op, self._id = resolve_monoid(self.combine, self.identity)
        self.boundary = Boundary(self.boundary)
        if self.cond is None:
            raise ValueError("a termination condition c is required")
        if self.mode not in ("taps", "windows", "indexed", "step"):
            raise ValueError(f"unknown mode {self.mode!r}")
        if self.backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {self.backend!r}; choose from {BACKENDS}")
        if self.backend == "pallas-sharded" and self.partition is None:
            raise ValueError(
                "backend='pallas-sharded' needs a partition= "
                "(repro.sharding.specs.GridPartition)")
        if self.unroll != "auto" and (not isinstance(self.unroll, int)
                                      or self.unroll < 1):
            raise ValueError(
                f"unroll must be a positive int or 'auto'; "
                f"got {self.unroll!r}")
        if self.sentinel is not None and not (
                0 <= self.sentinel.patience <= HEALTH_STALL_MASK):
            raise ValueError(
                f"sentinel patience {self.sentinel.patience} outside "
                f"[0, {HEALTH_STALL_MASK}] (the health word's stall "
                "counter width)")

    # -- single stencil application ------------------------------------
    def _apply(self, a, env=()):
        f = self.f if not env else (lambda *args: self.f(*args, *env))
        if self.mode == "taps":
            return stencil_taps(f, a, self.k, self.boundary)
        if self.mode == "windows":
            return stencil_windows(f, a, self.k, self.boundary)
        if self.mode == "indexed":
            return stencil_indexed(f, a, self.k, self.boundary)
        return f(a)  # step mode

    def _measure(self, a_new, a_old):
        if self.delta is not None:
            m = self.delta(a_new, a_old)
        elif self.measure is not None:
            m = self.measure(a_new)
        else:
            m = a_new
        if not isinstance(m, jnp.ndarray) and not hasattr(m, "reshape"):
            raise TypeError(
                "reduce input must be an array; supply `measure` for pytrees")
        return m

    def _reduce(self, m):
        return tree_reduce(self._op, m, self._id)

    def _cond_value(self, r, s):
        c = self.cond(r, s) if self.state_init is not None else self.cond(r)
        return jnp.asarray(c, dtype=bool).reshape(())

    # -- the loop --------------------------------------------------------
    def run(self, a0, state0=None, *, env=()) -> LoopResult:
        """Execute the pattern on ``a0`` (device-resident end to end).

        ``env`` holds read-only per-cell fields passed to ``f`` after its
        positional arguments (the paper Fig. 2 ``env`` schema).  On the
        Pallas backends they are staged into device frames once, before
        the loop.
        """
        if self.state_init is not None and state0 is None:
            state0 = self.state_init()
        resolved = self._resolve_unroll(getattr(a0, "shape", None))
        if resolved is not self:
            return resolved.run(a0, state0, env=env)
        if self.backend != "jnp":
            if self.mode != "taps" or getattr(a0, "ndim", None) != 2:
                raise ValueError(
                    "pallas backends require mode='taps' and a 2-D array; "
                    f"got mode={self.mode!r}, "
                    f"ndim={getattr(a0, 'ndim', None)}")
            if self.backend == "pallas-sharded":
                return self._run_sharded(a0, state0, env)
            return self._run_persistent(a0, state0, env)

        def one_iter(a):
            """unroll× stencil applications + the fused measure/reduce of
            the final one (against the second-to-last iterate)."""
            a_prev = a
            for _ in range(self.unroll):
                a_prev, a = a, self._apply(a, env)
            return a, self._reduce(self._measure(a, a_prev))

        return self._drive(a0, state0, step=one_iter,
                           state_view=lambda a: a,
                           finalize=lambda a: a)

    # -- unroll resolution (the T auto-tuner seam) -----------------------
    def _resolve_unroll(self, shape,
                        segment=None) -> "LoopOfStencilReduce":
        """Resolve ``unroll="auto"`` against the grid shape (and mesh for
        the sharded backend), and fail loudly on an infeasible explicit T.
        Returns ``self`` when nothing changes, else a resolved copy.
        ``segment`` (continuous farms: body steps per dispatch) folds the
        per-dispatch cost into the tuning — see
        :func:`~repro.core.executor.auto_unroll`."""
        from .executor import auto_unroll, check_unroll_feasible

        if shape is None or len(shape) < 2:
            if self.unroll == "auto":
                return dataclasses.replace(self, unroll=1)
            return self
        m, n = shape[-2], shape[-1]
        part = (self.partition if self.backend == "pallas-sharded"
                else None)
        if self.unroll == "auto":
            deep = self.backend in ("pallas-multistep", "pallas-sharded")
            T = auto_unroll(m, n, k=self.k, block=self.block,
                            part=part, segment=segment) if deep else 1
            return dataclasses.replace(self, unroll=T)
        if self.backend in ("pallas", "pallas-multistep",
                            "pallas-sharded"):
            sweeps = (self.unroll
                      if self.backend != "pallas" else 1)
            check_unroll_feasible(m, n, max(sweeps, 1), k=self.k,
                                  part=part)
        return self

    # -- the persistent-halo loop (pallas backends) ----------------------
    def _run_persistent(self, a0, state0, env) -> LoopResult:
        """Zero-copy realisation: two halo frames are the while-carry.

        Padding/round-up happens once in ``prepare``; the loop body is
        kernel sweeps, each writing the frame the last one read, + O(m+n)
        ghost refresh — no ``jnp.pad``, full-grid slice, select or copy per
        iteration (:meth:`_drive_frames`).  The domain is sliced back
        exactly once after convergence.  (The -s variant's
        ``state_update`` still sees the (m, n) view each check, which
        costs a slice — avoid combining a per-iteration state with the
        persistent backends on hot paths.)
        """
        from .executor import StencilEngine

        eng = StencilEngine(
            f=self.f, k=self.k, boundary=self.boundary,
            combine=self.combine, identity=self.identity, delta=self.delta,
            measure=self.measure, block=self.block, unroll=self.unroll,
            backend=self.backend, interpret=self.interpret)
        frame0, env_frames, spec = eng.prepare(a0, env)
        return self._drive_frames(
            frame0, state0,
            step=lambda fr: eng.sweeps(fr, env_frames, spec),
            unframe=lambda fr: eng.unframe(fr, spec))

    # -- the sharded persistent loop (1:n deployment) --------------------
    def _run_sharded(self, a0, state0, env) -> LoopResult:
        """The whole repeat/until runs INSIDE ``shard_map``: each shard's
        while-carry is its two local halo frames, the per-check ghost refresh
        is a ppermute of edge strips, and the fused reduce composes with
        the monoid collective so every shard evaluates the identical
        condition — one SPMD program, no host (and no full-block copy)
        in the loop.
        """
        from repro.sharding.specs import shard_map
        from .executor import ShardedStencilEngine

        if self.state_init is not None or state0 is not None:
            raise ValueError(
                "the -s variant is not supported on backend="
                "'pallas-sharded' (per-shard state views are ambiguous)")
        part = self.partition
        for name, ax in zip(part.axis_names, part.array_axes):
            nsh = part.mesh.shape[name]
            if a0.shape[ax] % nsh:
                raise ValueError(
                    f"array axis {ax} (size {a0.shape[ax]}) must divide "
                    f"evenly over mesh axis {name!r} (size {nsh})")
        eng = ShardedStencilEngine(
            f=self.f, part=part, k=self.k, boundary=self.boundary,
            combine=self.combine, identity=self.identity, delta=self.delta,
            measure=self.measure, block=self.block, unroll=self.unroll,
            interpret=self.interpret)

        def local_run(block, *env_local):
            frame0, env_frames, sspec = eng.prepare(block, env_local)
            # every shard reads the same combined reduce, so both steps
            # of the two-frame body stay in step mesh-wide
            res = self._drive_frames(
                frame0, None,
                step=lambda fr: eng.sweeps(fr, env_frames, sspec),
                unframe=lambda fr: eng.unframe(fr, sspec))
            return res.a, res.reduced, res.iters, res.health

        from jax.sharding import PartitionSpec as P
        pspec = part.pspec
        # reduced/iters/health are shard-invariant (the collective
        # combine hands every shard the identical reduce value, so the
        # sentinel folds identically everywhere)
        fn = shard_map(local_run, mesh=part.mesh,
                       in_specs=(pspec,) * (1 + len(env)),
                       out_specs=(pspec, P(), P(), P()))
        a, r, it, hw = fn(a0, *env)
        return LoopResult(a=a, reduced=r, iters=it, state=None, health=hw)

    # -- the lane-stacked loop (1:1 streaming farm) ----------------------
    def farm_run(self, a0, *, env=(), done0=None) -> LoopResult:
        """Run a FARM of convergence loops as ONE done-masked while_loop
        over a stacked (lanes, ...) carry — the paper's 1:1 streaming
        mode on the persistent engine.

        ``a0`` carries a leading lane axis ((lanes, m, n) on the array
        backends; any pytree of lane-stacked leaves in step mode), and so
        does every ``env`` field (stream items bring their own env).  On
        the Pallas backends the lane frames are built once and every
        sweep is ONE vmapped kernel launch; each lane runs to its own
        trip count (``done0`` pre-masks lanes — the streaming engine uses
        it for ragged final rounds).  Results match ``vmap(self.run)``
        lane for lane; ordering is positional (ofarm's contract).

        The sharded 1:n×1:1 composition (lanes spread over a mesh axis)
        lives in :class:`repro.core.streaming.FarmEngine`, which also
        adds the cross-item slot reuse.
        """
        if self.state_init is not None:
            raise ValueError(
                "the -s variant is not supported on farm_run "
                "(per-lane states do not compose with a shared loop "
                "state)")
        if self.backend == "pallas-sharded":
            raise ValueError(
                "backend='pallas-sharded' lanes are driven by "
                "repro.core.streaming.FarmEngine (they need a mesh "
                "carrying both the lane and the spatial axes)")
        resolved = self._resolve_unroll(
            getattr(a0, "shape", None) and a0.shape[1:])
        if resolved is not self:
            return resolved.farm_run(a0, env=env, done0=done0)

        if self.backend != "jnp":
            if self.mode != "taps" or getattr(a0, "ndim", None) != 3:
                raise ValueError(
                    "pallas farm_run requires mode='taps' and a "
                    "(lanes, m, n) stack; got mode="
                    f"{self.mode!r}, ndim={getattr(a0, 'ndim', None)}")
            from .executor import StencilEngine

            eng = StencilEngine(
                f=self.f, k=self.k, boundary=self.boundary,
                combine=self.combine, identity=self.identity,
                delta=self.delta, measure=self.measure, block=self.block,
                unroll=self.unroll, backend=self.backend,
                interpret=self.interpret)
            frames, env_frames, lspec = eng.prepare_lanes(a0, env)
            return self._drive_lanes(
                frames,
                step=lambda fr: eng.sweeps_lanes(fr, env_frames, lspec),
                finalize=lambda fr: eng.unframe_lanes(fr, lspec),
                done0=done0)

        return self._drive_lanes(a0, step=self._lane_step_jnp(env),
                                 finalize=lambda a: a, done0=done0)

    def _lane_step_jnp(self, env):
        """Vmapped ``unroll``-deep step over a lane-stacked carry on the
        jnp backend (``env`` fields lane-stacked alongside) — the step
        both :meth:`farm_run` and the continuous streaming engine drive."""
        def one(a1, *e):
            a_prev = a1
            for _ in range(self.unroll):
                a_prev, a1 = a1, self._apply(a1, e)
            return a1, self._reduce(self._measure(a1, a_prev))
        return lambda a: jax.vmap(one)(a, *env)

    def _lane_body(self, step, lanes: int):
        """The shared done-masked lane body: one ``step`` over the stacked
        carry with per-lane freeze.  ``carry = (a, r, it, done, hw)``; a
        lane whose flag (or iteration cap) has fired keeps its slice
        frozen while the others run on.  ``hw`` is the packed per-lane
        health word the sentinel maintains on the reduce value the
        condition already computes — a POISONED or DIVERGED lane is
        masked done on the spot (quarantined) instead of spinning to the
        iteration cap or feeding further exchanges."""

        def lane_where(live, old, new):
            return jax.tree.map(
                lambda o, n: jnp.where(
                    live.reshape((lanes,) + (1,) * (o.ndim - 1)), n, o),
                old, new)

        def body(carry):
            a, r, it, done, hw = carry
            live = jnp.logical_and(~done, it < self.max_iters)
            a_new, r_new = step(a)
            if self.fault_hook is not None:
                r_new = self.fault_hook(r_new, it)
            done_new = jax.vmap(self._cond_value, in_axes=(0, None))(
                r_new, None)
            hw_new, quar = health_update(hw, r_new, r, live, done_new,
                                         it, self.sentinel)
            retire = jnp.logical_or(done_new, quar)
            with scope("done_mask"):
                return (lane_where(live, a, a_new),
                        jnp.where(live, r_new, r),
                        jnp.where(live, it + self.unroll, it),
                        jnp.where(live, jnp.logical_or(done, retire),
                                  done),
                        jnp.where(live, hw_new, hw))

        return body

    def _lane_finished(self, carry):
        """Per-lane 'this lane needs the dispatcher' mask: condition fired
        OR iteration cap hit (a capped lane will never fire its flag, so
        the continuous dispatcher must retire it like a converged one).
        Quarantined lanes arrive here already done-masked."""
        it, done = carry[2], carry[3]
        return jnp.logical_or(done, it >= self.max_iters)

    def _drive_lanes(self, a0, *, step, finalize, done0=None,
                     cond_fold=None) -> LoopResult:
        """Lane-stacked repeat/until: ``step(carry) -> (carry', r)`` with
        ``r`` of shape (lanes,); each lane owns a done flag and an
        iteration counter, and a lane whose flag (or iteration cap) has
        fired keeps its carry frozen while the others run on — the
        while_loop exits when no live lane remains.  Semantically
        identical to ``vmap``-ing :meth:`_drive` lane by lane, but shaped
        so a streaming executor can hold the stacked carry across items.

        ``cond_fold`` optionally folds the scalar any-live predicate
        across shard groups (inside ``shard_map``): the composed farm
        passes a lane-axis ``pmax`` so every shard runs the SAME trip
        count — its body carries spatial ppermutes whose rendezvous must
        stay step-aligned mesh-wide (done-masking keeps per-lane results
        unchanged; the extra sweeps are the barrier's waste).
        """
        r_aval = jax.eval_shape(lambda a: step(a)[1], a0)
        lanes = r_aval.shape[0]
        r0 = jnp.full((lanes,), self._id, dtype=r_aval.dtype)
        it0 = jnp.zeros((lanes,), jnp.int32)
        d0 = (jnp.zeros((lanes,), bool) if done0 is None
              else jnp.asarray(done0, bool).reshape((lanes,)))
        hw0 = jnp.zeros((lanes,), jnp.int32)
        body = self._lane_body(step, lanes)

        def cond_fun(carry):
            it, done = carry[2], carry[3]
            live = jnp.any(jnp.logical_and(~done, it < self.max_iters))
            return live if cond_fold is None else cond_fold(live)

        a, r, it, _, hw = jax.lax.while_loop(cond_fun, body,
                                             (a0, r0, it0, d0, hw0))
        return LoopResult(a=finalize(a), reduced=r, iters=it, state=None,
                          health=hw)

    def lane_segment(self, carry, *, step, segment: int,
                     early_exit: bool = True):
        """One bounded slice of the lane loop — the continuous-refill tier.

        Runs the same done-masked body as :meth:`_drive_lanes` but hands
        control back to the dispatcher as soon as any lane *newly*
        finishes (condition fired or iteration cap hit), after at most
        ``segment`` body steps, or immediately when no live lane remains.
        ``carry = (a, r, it, done, hw)`` round-trips unchanged in shape, so a
        streaming executor resumes the SAME carry after refilling only
        the finished lanes' slots in place — one compilation serves every
        segment of the stream.  Returns ``(carry', steps)`` with
        ``steps`` the number of body steps executed (each ``unroll``
        sweeps deep).  ``early_exit=False`` runs exactly ``segment``
        done-masked steps (see :func:`segmented_while` — the
        uniform-schedule variant for collective-carrying bodies).
        """
        lanes = carry[3].shape[0]
        return segmented_while(
            self._lane_body(step, lanes), carry,
            finished=self._lane_finished, segment=segment,
            early_exit=early_exit)

    # -- shared while_loop scaffold (all backends) -----------------------
    def _advance(self, step, state_view, a, r, it, s, hw, live):
        """One check of the loop: ``step``, the -s update, the condition
        and the sentinel.  Returns ``(a', (r', it', s', done', hw'))``,
        ``done'`` true when the condition fired or the sentinel
        quarantined the loop."""
        a_new, r_new = step(a)
        it_new = it + self.unroll
        s_new = (self.state_update(s, state_view(a_new), it_new)
                 if self.state_update is not None else s)
        done = self._cond_value(r_new, s_new)
        hw_new, quar = health_update(hw, r_new, r, live, done, it,
                                     self.sentinel)
        return a_new, (r_new, it_new, s_new, jnp.logical_or(done, quar),
                       hw_new)

    def _drive(self, a0, state0, *, step, state_view, finalize
               ) -> LoopResult:
        """The repeat/until driver: ``step(a) -> (a_new, reduced)`` does
        ``unroll`` stencil applications in whatever representation the
        backend carries; ``state_view`` maps that representation to what
        -s state updates see; ``finalize`` maps the converged carry to
        the result array.  The jnp backend and the halo deployment
        (:mod:`repro.core.halo`) drive through here; the frame engines
        use :meth:`_drive_frames`.

        The done mask is not what makes ``vmap`` safe: the body runs only
        while ``done`` is False, and under ``vmap`` JAX's batched
        ``while_loop`` already keeps each finished lane's carry.  It stays
        because XLA fuses it into the stencil's own fusion here."""

        def body(carry):
            a, r, it, s, done, hw = carry
            a_new, (r_new, it_new, s_new, done_new, hw_new) = self._advance(
                step, state_view, a, r, it, s, hw, ~done)
            keep = lambda old, new: jax.tree.map(
                lambda o, n: jnp.where(done, o, n), old, new)
            with scope("done_mask"):
                return (keep(a, a_new), jnp.where(done, r, r_new),
                        jnp.where(done, it, it_new), keep(s, s_new),
                        jnp.logical_or(done, done_new),
                        jnp.where(done, hw, hw_new))

        def cond_fun(carry):
            _, _, it, _, done, _ = carry
            return jnp.logical_and(~done, it < self.max_iters)

        # identity element typed like the actual reduce output so the
        # while_loop carry is type-stable (e.g. bool for the 'any' monoid)
        r_shape = jax.eval_shape(lambda a: step(a)[1], a0)
        r0 = jnp.asarray(self._id, dtype=r_shape.dtype)
        carry0 = (a0, r0, jnp.asarray(0, jnp.int32), state0,
                  jnp.asarray(False), jnp.asarray(0, jnp.int32))
        a, r, it, s, _, hw = jax.lax.while_loop(cond_fun, body, carry0)
        return LoopResult(a=finalize(a), reduced=r, iters=it, state=s,
                          health=hw)

    # -- the two-frame loop (frame engines) ------------------------------
    def _drive_frames(self, frame0, state0, *, step, unframe
                      ) -> LoopResult:
        """:meth:`_drive` for the frame engines, with no whole-frame select.

        ``step`` is :meth:`_drive`'s; ``unframe`` slices the domain out of
        a frame (what -s state updates see, and the answer).  The carry
        holds two frames: each body iteration runs A → B, then B → A, so
        every kernel output lands in the carry slot of a frame nothing
        reads any more, and XLA writes it there.  A one-frame carry needs
        a select or a copy for that, since the kernel reads its input
        while it writes.  After each step the condition, the sentinel and
        the -s update run exactly as in :meth:`_drive`.  When the first
        step stops the loop (condition, quarantine or cap), scalar picks
        keep its reduce, count, health word and state, and ``odd``
        records that B holds the answer; the second step's sweep is
        thrown away.

        Results equal :meth:`_drive`'s bitwise for any trip count, at the
        cap and under ``vmap``.  Only frame cells beyond the domain's
        ghost ring (round-up, margin) may differ, and no domain cell
        depends on them."""

        # the body runs only for a live loop (under vmap, JAX keeps a
        # finished lane's carry), so every check in it is live
        def body(carry):
            a, _, r, it, s, _, hw, _ = carry
            b, first = self._advance(step, unframe, a, r, it, s, hw, True)
            r1, it1, s1, done1, hw1 = first
            a, second = self._advance(step, unframe, b, r1, it1, s1, hw1,
                                      True)
            stop = jnp.logical_or(done1, it1 >= self.max_iters)
            with scope("done_mask"):
                picked = jax.tree.map(
                    lambda x, y: jnp.where(stop, x, y), first, second)
            return (a, b, *picked, stop)

        def cond_fun(carry):
            it, done = carry[3], carry[5]
            return jnp.logical_and(~done, it < self.max_iters)

        r_shape = jax.eval_shape(lambda fr: step(fr)[1], frame0)
        r0 = jnp.asarray(self._id, dtype=r_shape.dtype)
        carry0 = (frame0, jnp.zeros_like(frame0), r0,
                  jnp.asarray(0, jnp.int32), state0, jnp.asarray(False),
                  jnp.asarray(0, jnp.int32), jnp.asarray(False))
        a, b, r, it, s, _, hw, odd = jax.lax.while_loop(cond_fun, body,
                                                        carry0)
        # pick after the slice: one domain-sized pass, no frame copy (a
        # cond over the frames would hoist the slice and copy a frame)
        return LoopResult(a=jnp.where(odd, unframe(b), unframe(a)),
                          reduced=r, iters=it, state=s, health=hw)

    # convenience: a jitted runner
    def jit_run(self, donate: bool = True):
        return jax.jit(self.run, donate_argnums=(0,) if donate else ())


# ---------------------------------------------------------------------------
# Functional front-ends (match the paper's procedure signatures).
# ---------------------------------------------------------------------------

def loop_of_stencil_reduce(k, f, combine, c, a, *, identity=None,
                           boundary="zero", max_iters=10_000, mode="taps",
                           unroll=1, backend="jnp", env=()) -> LoopResult:
    """LOOP-OF-STENCIL-REDUCE(k, f, ⊕, c, a) — base variant."""
    return LoopOfStencilReduce(
        f=f, k=k, combine=combine, identity=identity, cond=c, mode=mode,
        boundary=boundary, max_iters=max_iters, unroll=unroll,
        backend=backend).run(a, env=env)


def loop_of_stencil_reduce_d(k, f, delta, combine, c, a, *, identity=None,
                             boundary="zero", max_iters=10_000,
                             mode="taps", unroll=1, backend="jnp",
                             env=()) -> LoopResult:
    """-D variant: convergence measured on δ between successive iterates."""
    return LoopOfStencilReduce(
        f=f, k=k, combine=combine, identity=identity, cond=c, delta=delta,
        mode=mode, boundary=boundary, max_iters=max_iters,
        unroll=unroll, backend=backend).run(a, env=env)


def loop_of_stencil_reduce_s(k, f, combine, c, a, *, init, update,
                             identity=None, boundary="zero",
                             max_iters=10_000, mode="taps",
                             unroll=1, backend="jnp", env=()) -> LoopResult:
    """-S variant: a global state participates in the condition."""
    return LoopOfStencilReduce(
        f=f, k=k, combine=combine, identity=identity, cond=c,
        state_init=init, state_update=update, mode=mode, boundary=boundary,
        max_iters=max_iters, unroll=unroll, backend=backend).run(a, env=env)
