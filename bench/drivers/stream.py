"""The ``stream`` driver: a restoration stream through ``FarmEngine``.

The farm is the program's continuous, chained ``FarmEngine`` over the
restoration loop (``LoopOfStencilReduce`` of ``restore_taps``), with the
program's adaptive median detection as its ``prep``.  Set-up makes the
traffic's pool of noisy frames on the device from its ``pool_seed``,
keeps it on the host with the rows of each frame flipped as the seed
says, builds the farm and warms it with one stream of ``lanes`` pool
frames, which compiles every program the window runs.

The window is one stream from a closed-loop backlog: the source hands
the farm the pool's frames in order, pass after pass, whenever the farm
takes one, and ends the stream at the first pass boundary after
``seconds``.  ``frames_per_s`` is the frames of those whole passes over
the time from the window's start to the emission of the last of them;
``frame_p95_ms`` is the 95th percentile of each frame's time from the
farm's pull of it (the start of its staging) to its emission.  The sink
records every emission and copies out the results the check compares:
every frame of the first pass, and one in ``check_every`` of the later
ones, drawn from the seed.

The probe then streams ``probe`` frames drawn from the seed itself
through the same farm, and the check holds them, with the window's, to
``bench/reference_restore.py``.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from bench import generators, reference_restore
from bench.common import Check, Window

STALL_S = 0.1     # gaps between two emissions this long are counted


def detect(frame, kmax):
    """The farm's prep: the program's adaptive median detection; the
    detected frame is the loop's start and, with the noise mask, its
    read-only input."""
    from repro.kernels import ops

    mask, repaired = ops.adaptive_median_detect(frame, kmax=kmax)
    return repaired, (repaired, mask)


def _abstract(x):
    import jax

    aval = jax.typeof(x)
    return jax.ShapeDtypeStruct(aval.shape, aval.dtype,
                                weak_type=aval.weak_type,
                                sharding=getattr(x, "sharding", None))


class Cell:
    def __init__(self, config, traffic, seed, devices):
        from repro.core import FarmEngine, LoopOfStencilReduce
        from repro.kernels import ref as R

        self.config, self.traffic, self.seed = config, traffic, seed
        self.shape = tuple(config["frame"])
        self.make = getattr(generators, traffic["generator"])
        self.pool = self.make(seed, self.shape, int(traffic["pool"]),
                              int(traffic["pool_seed"]),
                              float(traffic["level"]))
        tol, kmax = float(config["tol"]), int(config["amf_kmax"])
        loop = LoopOfStencilReduce(
            f=R.restore_taps(float(config["beta"])), k=1, combine="max",
            delta=R.abs_delta, cond=lambda r: r < tol,
            boundary=config["boundary"], max_iters=int(config["max_iters"]),
            backend=traffic["engine"])
        self.lanes = int(traffic["lanes"])
        self.engine = FarmEngine(loop, lanes=self.lanes,
                                 prep=lambda x: detect(x, kmax))
        self.engine.run(self.pool[:self.lanes], lambda res: None,
                        continuous=True)
        bits = np.random.SeedSequence([int(seed), 6]).generate_state(4096)
        self._sampled = (bits % int(traffic["check_every"])) == 0
        self.records = []    # (index, emitted at, iters, status)
        self.kept = {}       # index -> result, for the check
        self.pulled = []     # pulled at, by index
        self.probe_out = []  # (probe index, result, iters)

    def _keep(self, index: int) -> bool:
        return index < len(self.pool) or \
            bool(self._sampled[index % len(self._sampled)])

    def window(self, seconds: float) -> Window:
        eng = self.engine
        count = len(self.pool)
        before = dict(eng.stats)
        compiles = _compiles()
        pulled, records, kept = self.pulled, self.records, self.kept

        def source():
            i = 0
            while not (i % count == 0 and i and time.perf_counter() >= stop):
                pulled.append(time.perf_counter())
                yield self.pool[i % count]
                i += 1

        def sink(res):
            records.append((res.index, time.perf_counter(), int(res.iters),
                            res.status))
            if self._keep(res.index):
                kept[res.index] = np.array(res.a)

        t0 = time.perf_counter()
        stop = t0 + seconds
        eng.run(source(), sink, continuous=True)
        compiles = None if compiles is None else _compiles() - compiles
        frames = len(records)
        emitted = sorted(t for _, t, _, _ in records)
        latency = [t - pulled[i] for i, t, _, _ in records]
        gaps = np.diff(emitted)     # from the first emission on
        ok = {i for i, _, _, status in records if status == "ok"}
        bad = {r.index for r in eng.dead_letter}
        longest = ", ".join(f"{1e3 * g:.1f}" for g in sorted(gaps)[-3:])
        print(f"window: {frames} frames ({frames // count} passes of "
              f"{count}) in {emitted[-1] - t0:.3f} s, the first emitted "
              f"after {1e3 * (emitted[0] - t0):.1f} ms; longest gaps "
              f"between emissions {longest} ms, "
              f"{int((gaps > STALL_S).sum())} over {1e3 * STALL_S:.0f} ms; "
              f"compiles in the window: {compiles}",
              file=sys.stderr, flush=True)
        counters = {k: eng.stats[k] - before[k]
                    for k in ("segments", "lane_steps", "wasted_lane_steps")}
        counters["frames"] = frames
        counters["lanes"] = self.lanes
        counters["chain_entry"] = self._chain_entry()
        return Window(
            e2e={"frames_per_s": frames / (emitted[-1] - t0),
                 "frame_p95_ms": 1e3 * float(np.percentile(latency, 95))},
            attempted=len(pulled),
            failed=sum(1 for i in range(len(pulled))
                       if i not in ok or i in bad),
            counters=counters)

    def _chain_entry(self):
        """The farm's chained dispatch entry and the abstract arguments
        of its calls, from which a reader rebuilds the executable the
        window ran (a compile-cache hit) to map its op names to the
        program's scopes.  The arguments are the farm's slots, carry and
        staging ring, its device-side ring cursor, the host's watermark
        and the live-slot mask (``FarmEngine._chain_entry``)."""
        import jax
        import jax.numpy as jnp

        eng = self.engine
        rd = jax.device_put(jnp.int32(0), eng._frames.sharding)
        args = jax.tree.map(_abstract, (eng._frames, eng._env_frames,
                                        *eng._cont_carry, eng._ring,
                                        eng._ring_envs, rd))
        return eng._chain_fn, (*args, jax.ShapeDtypeStruct((), np.int32),
                               _abstract(jnp.ones((self.lanes,), bool)))

    def probe(self):
        """After the window: ``probe`` frames drawn from the seed itself,
        streamed through the window's farm, kept for the check."""
        frames = self.make(self.seed, self.shape, int(self.traffic["probe"]),
                           generators.seed_draw(self.seed),
                           float(self.traffic["level"]))
        self.engine.run(frames, lambda res: self.probe_out.append(
            (res.index, np.array(res.a), int(res.iters))), continuous=True)
        self.probe_frames = frames

    def release(self):
        """Free the farm's device state before the reference runs."""
        import jax

        for value in vars(self.engine).values():
            for leaf in jax.tree.leaves(value):
                if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                    leaf.delete()
        self.engine = None

    def check(self, control: bool = False) -> list[Check]:
        """Every result the window and the probe emitted against the
        plain reference, one reference run per pool frame and per probe
        frame: the largest gap of a copied-out result (every frame of
        the first pass and the seed's sample of the others, the probe's
        all), the largest gap of any frame's sweep count, and the
        indices not emitted exactly once.  ``control`` puts the reference
        in the next precision down in the program's place."""
        import jax.numpy as jnp

        c = self.config
        kw = dict(kmax=int(c["amf_kmax"]), beta=float(c["beta"]),
                  tol=float(c["tol"]), max_iters=int(c["max_iters"]))

        def ref(x, dtype):
            a, it = reference_restore.restore_frame(
                x, dtype=getattr(jnp, dtype), **kw)
            return np.asarray(a), int(it)

        t_check = time.perf_counter()
        count = len(self.pool)
        # (input, {index: result or None}, {index: iters}) per input
        cases = [(self.pool[p], {}, {}) for p in range(count)]
        for i, _, iters, _ in self.records:
            cases[i % count][1][i] = self.kept.get(i)
            cases[i % count][2][i] = iters
        for i, a, iters in self.probe_out:
            cases.append((self.probe_frames[i], {i: a}, {i: iters}))
        err = iters_gap = 0.0
        for x, results, iters in cases:
            a_ref, it_ref = ref(x, c["dtype"])
            if control:
                a_low, it_low = ref(x, "bfloat16")
                results = {i: a_low for i in results}
                iters = {i: it_low for i in iters}
            for a in results.values():
                if a is not None:
                    err = max(err, float(np.max(np.abs(a - a_ref))))
            for it in iters.values():
                iters_gap = max(iters_gap, abs(it - it_ref))
        emission_gap = sum(
            int(np.sum(np.bincount(emitted, minlength=handed) != 1))
            for emitted, handed in (
                ([r[0] for r in self.records], len(self.pulled)),
                ([r[0] for r in self.probe_out], len(self.probe_frames))))
        print(f"reference: {len(cases)} frames in "
              f"{time.perf_counter() - t_check:.3f} s", file=sys.stderr)
        limits = c["limits"]
        return [Check("restore_err", err, limits["restore_err"]),
                Check("iters_gap", iters_gap, limits["iters_gap"]),
                Check("emission_gap", emission_gap, limits["emission_gap"])]


def _compiles():
    """The program's count of backend compiles, where it keeps one."""
    try:
        from repro.core import spans
    except ImportError:
        return None
    return spans.counts()["compiles"]
