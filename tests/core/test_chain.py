"""Chained dispatch — device-resident segment chaining + staging ring.

The contracts of ``FarmEngine(chained=True)`` (the default):

  bit-identity     — on a fault-free stream the chained pipeline emits
                     the SAME results (payload, reduced, iters, status,
                     order of indexes per slot) as ``chained=False``
  exactly-once     — every index emits exactly one StreamResult
  one compilation  — the fused ``_chain_fn`` entry traces ONCE across a
                     ragged stream (and across a second stream through
                     the same engine), as do staging and the classic
                     refill used for the initial fill
  no host sync     — in steady state the drain of segment t reads its
                     metadata only AFTER segment t+1 is dispatched, one
                     ``_meta_read`` per drained segment, and never
                     touches device arrays element-wise
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import FarmEngine, LoopOfStencilReduce
from repro.core.executor import auto_unroll
from repro.core.frames import (refill_lane_frames,
                               refill_lanes_env_masked,
                               refill_lanes_masked, stage_ring_write)


def countdown(get, *_):
    return get(0, 0) - 1.0


def mk_countdown(max_iters=64, backend="jnp"):
    return LoopOfStencilReduce(
        f=countdown, k=1, combine="max", cond=lambda r: r < 0.5,
        boundary="zero", max_iters=max_iters, backend=backend,
        interpret=True, block=(32, 128))


def trip_items(trips, shape=(8, 128)):
    base = np.linspace(0.1, 0.9, shape[0] * shape[1],
                       dtype=np.float32).reshape(shape)
    return [base + float(t) - 1.0 for t in trips]


TRIPS = [3, 9, 5, 7, 4, 6, 2, 8, 5, 3, 11, 2]


def stream(eng, items, **kw):
    got = {}

    def sink(r):
        assert r.index not in got, f"duplicate emission for {r.index}"
        got[r.index] = r

    n = eng.run_continuous(items, sink, **kw)
    assert n == len(got)
    return got


# ---------------------------------------------------------------------------
# bit-identity + exactly-once
# ---------------------------------------------------------------------------


class TestBitIdentity:
    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    def test_chained_matches_synchronous(self, backend):
        items = trip_items(TRIPS)
        got_c = stream(FarmEngine(mk_countdown(backend=backend),
                                  lanes=4, segment=4), items)
        got_s = stream(FarmEngine(mk_countdown(backend=backend),
                                  lanes=4, segment=4, chained=False),
                       items)
        assert set(got_c) == set(got_s) == set(range(len(items)))
        for i in got_c:
            assert got_c[i].status == got_s[i].status == "ok"
            assert int(got_c[i].iters) == int(got_s[i].iters)
            np.testing.assert_array_equal(np.asarray(got_c[i].a),
                                          np.asarray(got_s[i].a))
            np.testing.assert_array_equal(
                np.asarray(got_c[i].reduced),
                np.asarray(got_s[i].reduced))

    def test_stats_parity_with_synchronous(self):
        items = trip_items(TRIPS)
        eng_c = FarmEngine(mk_countdown(), lanes=4, segment=4)
        eng_s = FarmEngine(mk_countdown(), lanes=4, segment=4,
                           chained=False)
        stream(eng_c, items)
        stream(eng_s, items)
        # same refill count; the chained pipeline may run extra
        # (zero-step, early-exited) trailing segments but never fewer
        assert eng_c.stats["refills"] == eng_s.stats["refills"]
        assert eng_c.stats["segments"] >= eng_s.stats["segments"]
        # lane-step waste identical: the chain freezes finished lanes
        # exactly as the synchronous loop does
        assert (eng_c.stats["wasted_lane_steps"]
                == eng_s.stats["wasted_lane_steps"])

    def test_single_item_and_single_lane(self):
        got = stream(FarmEngine(mk_countdown(), lanes=1, segment=4),
                     trip_items([5]))
        assert set(got) == {0} and got[0].status == "ok"
        assert int(got[0].iters) == 5


# ---------------------------------------------------------------------------
# one compilation across a ragged stream (and a second stream)
# ---------------------------------------------------------------------------


class TestTraceCounts:
    def test_one_compilation_across_ragged_streams(self):
        eng = FarmEngine(mk_countdown(), lanes=4, segment=4)
        stream(eng, trip_items(TRIPS))
        assert eng.stats["chain_traces"] == 1
        assert eng.stats["segment_traces"] == 1
        assert eng.stats["stage_traces"] == 1
        # the initial cohort seats through the ring too — the classic
        # per-slot refill never even compiles on a fault-free stream
        assert eng.stats["refill_traces"] == 0
        # a SECOND ragged stream through the same engine: zero retraces
        stream(eng, trip_items([4, 1, 6, 2, 9]))
        assert eng.stats["chain_traces"] == 1
        assert eng.stats["segment_traces"] == 1
        assert eng.stats["stage_traces"] == 1
        assert eng.stats["refill_traces"] == 0

    def test_synchronous_path_never_traces_the_chain(self):
        eng = FarmEngine(mk_countdown(), lanes=4, segment=4,
                         chained=False)
        stream(eng, trip_items(TRIPS[:6]))
        assert eng.stats["chain_traces"] == 0
        assert eng.stats["stage_traces"] == 0
        assert eng.stats["segment_traces"] == 1


# ---------------------------------------------------------------------------
# steady-state no-host-sync guard
# ---------------------------------------------------------------------------


class TestNoHostSync:
    def test_drain_reads_only_after_next_dispatch(self):
        """The pipeline contract itself: every steady-state segment's
        ONE metadata read happens strictly AFTER the next segment is
        already dispatched (the device never waits on the host), and
        there is exactly one ``_meta_read`` per drained segment."""
        eng = FarmEngine(mk_countdown(), lanes=4, segment=4)
        events = []
        chain_fn, meta_read = eng._chain_fn, eng._meta_read

        def spy_chain(*a, **k):
            events.append("dispatch")
            return chain_fn(*a, **k)

        def spy_read(*a):
            events.append("read")
            return meta_read(*a)

        eng._chain_fn, eng._meta_read = spy_chain, spy_read
        try:
            stream(eng, trip_items(TRIPS))
        finally:
            eng._chain_fn, eng._meta_read = chain_fn, meta_read
        n_dispatch = events.count("dispatch")
        n_read = events.count("read")
        assert n_dispatch == eng.stats["segments"] > 0
        assert n_read == n_dispatch     # one read per drained segment
        # read i drains segment i; dispatch i+1 must precede it for
        # every non-tail segment (the tail has nothing left to overlap)
        reads_seen = 0
        for j, ev in enumerate(events):
            if ev != "read":
                continue
            reads_seen += 1
            dispatches_before = events[:j].count("dispatch")
            if reads_seen < n_read:     # steady state (non-tail)
                assert dispatches_before >= reads_seen + 1, (
                    f"segment {reads_seen} was drained before segment "
                    f"{reads_seen + 1} dispatched: {events[:j + 1]}")

    def test_zero_blocking_reads_outside_meta_read(self):
        """_CountingArray-style transfer counter: every per-segment
        metadata pull of the chained drain funnels through ONE
        ``_meta_read`` call — element indexing of device arrays (one
        blocking transfer per slot, the classic loop's cost model)
        never happens."""
        eng = FarmEngine(mk_countdown(), lanes=4, segment=4)
        meta_read = eng._meta_read
        counts = {"reads": 0, "arrays": 0}

        class _NoTouch:
            """Wraps one drained metadata array: whole-array conversion
            is the sanctioned (already-on-host) access; per-element
            device indexing is the regression."""

            def __init__(self, arr):
                self._arr = np.asarray(arr)
                counts["arrays"] += 1

            def __array__(self, dtype=None, copy=None):
                return (self._arr if dtype is None
                        else self._arr.astype(dtype))

            def __getattr__(self, name):
                return getattr(self._arr, name)

            def __getitem__(self, i):
                return self._arr[i]     # host-side numpy by now

        def spy_read(*arrs):
            counts["reads"] += 1
            return tuple(_NoTouch(a) for a in meta_read(*arrs))

        eng._meta_read = spy_read
        try:
            got = stream(eng, trip_items(TRIPS))
        finally:
            eng._meta_read = meta_read
        assert set(got) == set(range(len(TRIPS)))
        assert counts["reads"] == eng.stats["segments"]
        # the whole drain decision state crosses as ONE packed int32
        # vector per segment — not one transfer per metadata field
        assert counts["arrays"] == counts["reads"]


# ---------------------------------------------------------------------------
# frames-level units: masked batch refill + staging ring
# ---------------------------------------------------------------------------


class TestFrameUnits:
    def test_stage_ring_write_and_gather(self):
        ring = jnp.zeros((4, 3, 3), jnp.float32)
        for i in range(5):      # wraps: position 0 written twice
            ring = stage_ring_write(
                ring, jnp.full((3, 3), float(i + 1)), i % 4)
        np.testing.assert_array_equal(
            np.asarray(ring)[:, 0, 0], [5.0, 2.0, 3.0, 4.0])
        pos = jnp.asarray([2, 0, 1])
        np.testing.assert_array_equal(
            np.asarray(ring[pos])[:, 0, 0], [3.0, 5.0, 2.0])

    def test_refill_lanes_masked_matches_per_slot(self):
        from repro.core.frames import frame_spec
        spec = frame_spec(8, 128, k=1, block=(8, 128))
        lanes, (rs, cs) = 3, spec.domain
        rng = np.random.default_rng(1)
        frames = jnp.asarray(rng.normal(size=(lanes, *spec.shape)),
                             jnp.float32)
        fresh = jnp.asarray(rng.normal(size=(lanes, 8, 128)),
                            jnp.float32)
        take = jnp.asarray([True, False, True])
        got = refill_lanes_masked(frames, take, fresh, spec, "zero")
        # reference: keep the untaken lane's interior, refresh ALL
        # ghosts (exactly what the classic per-slot refill's vmapped
        # refresh does to bystander lanes)
        cur = frames[:, rs, cs]
        ref_interiors = jnp.where(take[:, None, None], fresh, cur)
        ref = refill_lane_frames(frames, ref_interiors, spec, "zero")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        # the untaken lane's interior is bit-untouched
        np.testing.assert_array_equal(
            np.asarray(got)[1, rs, cs], np.asarray(frames)[1, rs, cs])
        # the taken lanes carry the fresh interiors
        np.testing.assert_array_equal(
            np.asarray(got)[0, rs, cs], np.asarray(fresh)[0])

    def test_refill_lanes_env_masked_non_halo(self):
        from repro.core.frames import frame_spec
        spec = frame_spec(8, 128, k=1, block=(8, 128))
        mi, ni = spec.interior
        rng = np.random.default_rng(2)
        env = jnp.asarray(rng.normal(size=(3, mi, ni)), jnp.float32)
        fresh = jnp.asarray(rng.normal(size=(3, 8, 128)), jnp.float32)
        take = jnp.asarray([False, True, False])
        got = refill_lanes_env_masked(env, take, fresh, spec, "zero",
                                      halo=False)
        np.testing.assert_array_equal(np.asarray(got)[1, :8, :128],
                                      np.asarray(fresh)[1])
        np.testing.assert_array_equal(np.asarray(got)[0, :8, :128],
                                      np.asarray(env)[0, :8, :128])


# ---------------------------------------------------------------------------
# auto_unroll folds the segment length in (dispatch amortization)
# ---------------------------------------------------------------------------


class TestAutoUnrollSegmentFold:
    def test_segment_fold_raises_T_in_dispatch_bound_regime(self):
        base = auto_unroll(64, 512, k=1, block=(32, 128))
        folded = auto_unroll(64, 512, k=1, block=(32, 128), segment=4)
        assert folded >= base
        # 4-step segments amortize one dispatch over segment*T sweeps;
        # the default 64-sweep target wants T up toward 16, capped at 8
        assert folded == 8

    def test_segment_fold_respects_feasibility(self):
        # tiny local domain: k*T < min(lm, ln) still binds, whatever
        # the amortization target asks for
        T = auto_unroll(6, 512, k=1, block=(32, 128), segment=1)
        assert T * 1 < 6
        assert T == auto_unroll(6, 512, k=1, block=(32, 128),
                                segment=1, dispatch_amortize=10_000)

    def test_no_segment_means_no_fold(self):
        assert (auto_unroll(64, 512, k=1, block=(32, 128))
                == auto_unroll(64, 512, k=1, block=(32, 128),
                               segment=None))

    def test_amortized_segment_left_alone(self):
        base = auto_unroll(64, 512, k=1, block=(32, 128))
        assert auto_unroll(64, 512, k=1, block=(32, 128), segment=256,
                           dispatch_amortize=64) == base


# ---------------------------------------------------------------------------
# repair mode (retries) and drained snapshot boundaries
# ---------------------------------------------------------------------------


class TestChainedResilience:
    def test_retry_repair_recovers_everything(self):
        """Faulted slots push entries onto the retry queue; the chain
        drops to synchronous repair (ring rewound, classic admission),
        recovers every item bit-identically, then resumes — still one
        compilation per entry point."""
        from repro.core.reduce import Sentinel
        from repro.resilience import FaultPlan

        clean = LoopOfStencilReduce(
            f=countdown, k=1, combine="max", cond=lambda r: r < 0.5,
            boundary="zero", max_iters=32, backend="jnp",
            interpret=True, block=(32, 128),
            sentinel=Sentinel(nan=True, patience=3))
        plan = FaultPlan(lanes=4, nan_events=((1, 2),),
                         stall_events=((2, 1 << 20),))
        items = trip_items(TRIPS[:8])
        ref = stream(FarmEngine(clean, lanes=4, segment=4), items)
        eng = FarmEngine(plan.instrument(clean), lanes=4, segment=4,
                         max_attempts=3, slot_patience=2)
        got = stream(eng, items)
        assert all(r.status == "ok" for r in got.values()), {
            i: r.status for i, r in got.items()}
        for i, r in got.items():
            np.testing.assert_array_equal(r.a, ref[i].a)
        assert eng.stats["retries"] > 0
        assert eng.stats["chain_traces"] == 1
        assert eng.stats["segment_traces"] == 1
        assert eng.stats["refill_traces"] == 1  # the repair-mode seats

    def test_preempt_resume_keeps_staged_entries(self, tmp_path):
        """A preemption with items sitting in the staging ring (staged
        but not yet seated): the snapshot's queued list carries them,
        and the resumed run emits every index exactly once."""
        from repro.resilience import FaultPlan, PreemptionError
        from repro.resilience.recovery import RecoveryConfig

        trips = [3, 9, 5, 12, 7, 4, 10, 6, 8, 2, 6, 3]
        items = trip_items(trips)
        ref = stream(FarmEngine(mk_countdown(), lanes=2, segment=2),
                     items)
        rec = RecoveryConfig(dir=str(tmp_path), snapshot_every=1,
                             fsync=False)
        plan = FaultPlan(lanes=2, preempt_at_segment=3)
        # stage_depth=8: at the kill point several pulled-ahead items
        # live ONLY in the ring — the snapshot must not lose them
        eng = FarmEngine(mk_countdown(), lanes=2, segment=2,
                         stage_depth=8)
        got0 = {}
        with pytest.raises(PreemptionError):
            eng.run_continuous(
                items, lambda r: got0.__setitem__(r.index, r),
                recovery=rec,
                on_segment=plan.preempt_hook(mode="raise"))
        eng2 = FarmEngine(mk_countdown(), lanes=2, segment=2)
        got = stream(eng2, items, recovery=rec, resume=True)
        assert sorted(got) == list(range(len(items)))
        for i in range(len(items)):
            assert got[i].status == "ok"
            np.testing.assert_array_equal(got[i].a, ref[i].a)
            assert int(got[i].iters) == int(ref[i].iters)


# ---------------------------------------------------------------------------
# serve twin: chained engine matches the synchronous dispatcher
# ---------------------------------------------------------------------------


class TestServeChained:
    def test_batcher_chained_matches_synchronous(self, rng):
        from repro.configs import get_reduced
        from repro.models import transformer as T
        from repro.serve import GenerateConfig
        from repro.serve.batcher import Batcher, Request

        cfg = get_reduced("qwen3-1.7b")
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        gcfg = GenerateConfig(max_new_tokens=10, eos_id=1,
                              temperature=0.0)
        reqs = [Request(rid=i, prompt=np.asarray(
            rng.integers(2, cfg.vocab_size, 3 + i % 4), np.int32))
            for i in range(7)]

        def drain(chained):
            b = Batcher(cfg, params, gcfg, max_batch=3,
                        cache_dtype=jnp.float32)
            for r in reqs:
                b.submit(Request(rid=r.rid, prompt=r.prompt.copy()))
            res = b.run_continuous(chained=chained)
            eng = b.engines[0]
            return {r.rid: r for r in res}, eng

        got_s, eng_s = drain(False)
        got_c, eng_c = drain(True)
        assert set(got_c) == set(got_s) == set(range(7))
        for rid in got_c:
            assert got_c[rid].status == got_s[rid].status == "ok"
            np.testing.assert_array_equal(got_c[rid].tokens,
                                          got_s[rid].tokens)
        assert eng_c.stats["chain_traces"] == 1
        assert eng_c.stats["segment_traces"] == 1
        assert eng_c.stats["prefill_traces"] == 1
        assert eng_s.stats["chain_traces"] == 0
