"""Chaos suite — convergence sentinels, quarantine, retry, dead-letter.

Every fault here comes from a seeded :class:`repro.resilience.faults.
FaultPlan` (same schedule every run): a NaN-ed lane, a stalled lane and
corrupted stream items, driven through the SAME engines the happy-path
tests use.  The contracts under fault:

  exactly-once     — every stream index emits exactly one StreamResult,
                     whatever slots/retries it passed through
  containment      — healthy items finish ``status="ok"`` BIT-IDENTICAL
                     to a fault-free run (a fault never leaks across
                     lanes)
  loud failure     — every faulty item surfaces a non-ok status (and
                     the dead-letter list); nothing hangs, nothing
                     silently returns NaN
  waste dominance  — under faults, the farm's ``wasted + quarantined``
                     lane steps stay strictly below a barrier farm's on
                     the same trip counts (the barrier burns the
                     fault's straggler shadow on every lane)
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import FarmEngine, LoopOfStencilReduce
from repro.core.reduce import (HEALTH_CONVERGED, HEALTH_DIVERGED,
                               HEALTH_POISONED, Sentinel, health_status,
                               health_update)
from repro.core.streaming import item_status
from repro.resilience import FaultPlan


def countdown(get, *_):
    """max decrements by 1 per sweep — an item whose max is v converges
    in EXACTLY v sweeps (cond: max < 0.5): programmable trip counts."""
    return get(0, 0) - 1.0


def mk_countdown(max_iters=64, sentinel=None, backend="jnp"):
    return LoopOfStencilReduce(
        f=countdown, k=1, combine="max", cond=lambda r: r < 0.5,
        boundary="zero", max_iters=max_iters, backend=backend,
        interpret=True, block=(32, 128), sentinel=sentinel)


def trip_items(trips, shape=(8, 128)):
    base = np.linspace(0.1, 0.9, shape[0] * shape[1],
                       dtype=np.float32).reshape(shape)
    return [base + float(t) - 1.0 for t in trips]


def stream(eng, items, **kw):
    got = {}

    def sink(r):
        assert r.index not in got, f"duplicate emission for {r.index}"
        got[r.index] = r
    n = eng.run(items, sink, **kw)
    assert n == len(got)
    return got


# ---------------------------------------------------------------------------
# Sentinel unit level
# ---------------------------------------------------------------------------


class TestSentinel:
    def test_health_word_bits_and_status(self):
        hw0 = jnp.zeros((4,), jnp.int32)
        live = jnp.ones((4,), bool)
        r_prev = jnp.asarray([1.0, 1.0, 1.0, 1.0], jnp.float32)
        r_new = jnp.asarray([0.1, jnp.nan, 2.0, 0.2], jnp.float32)
        conv = jnp.asarray([True, False, False, False])
        s = Sentinel(nan=True, patience=1)
        hw, quar = health_update(hw0, r_new, r_prev, live, conv,
                                 jnp.full((4,), 3, jnp.int32), s)
        hw = np.asarray(hw)
        assert hw[0] & HEALTH_CONVERGED
        assert hw[1] & HEALTH_POISONED
        assert hw[2] & HEALTH_DIVERGED       # 2.0 >= 1.0, patience 1
        assert not hw[3] & (HEALTH_POISONED | HEALTH_DIVERGED)
        assert list(np.asarray(quar)) == [False, True, True, False]
        assert health_status(hw[0]) == "ok"
        assert health_status(hw[1]) == "poisoned"
        assert health_status(hw[2]) == "nonconverged"
        assert health_status(hw[3]) == "nonconverged"
        # poison outranks a converged bit: a NaN result is never ok
        assert health_status(HEALTH_CONVERGED | HEALTH_POISONED) \
            == "poisoned"

    def test_item_status_taxonomy(self):
        assert item_status(HEALTH_CONVERGED, 7, 64) == "ok"
        assert item_status(HEALTH_POISONED, 7, 64) == "poisoned"
        assert item_status(HEALTH_DIVERGED, 7, 64) == "nonconverged"
        assert item_status(0, 64, 64) == "timed_out"
        assert item_status(0, 7, 64) == "nonconverged"

    def test_dead_lanes_frozen(self):
        """A retired lane's word never changes, whatever its reduce
        value reads (the frozen carry may hold stale garbage)."""
        hw0 = jnp.asarray([HEALTH_CONVERGED, 0], jnp.int32)
        live = jnp.asarray([False, True])
        r = jnp.asarray([jnp.nan, 0.3], jnp.float32)
        hw, quar = health_update(hw0, r, r, live,
                                 jnp.asarray([False, True]),
                                 jnp.full((2,), 5, jnp.int32),
                                 Sentinel(nan=True, patience=2))
        assert int(np.asarray(hw)[0]) == HEALTH_CONVERGED
        assert not bool(np.asarray(quar)[0])

    def test_patience_bounds_validated(self):
        with pytest.raises(ValueError, match="patience"):
            mk_countdown(sentinel=Sentinel(patience=-1))
        with pytest.raises(ValueError, match="patience"):
            mk_countdown(sentinel=Sentinel(patience=1 << 17))

    def test_sentinel_off_still_reports_converged(self):
        """health rides every run (sentinel or not): a plain loop's
        results decode 'ok' for free."""
        eng = FarmEngine(mk_countdown(), lanes=2, segment=4)
        got = stream(eng, trip_items([3, 5]))
        assert all(r.status == "ok" for r in got.values())


class TestFaultPlan:
    def test_seeded_is_deterministic_and_bounded(self):
        a = FaultPlan.seeded(7, lanes=4, n_nan=1, n_stall=1,
                             n_corrupt=2, n_items=10)
        b = FaultPlan.seeded(7, lanes=4, n_nan=1, n_stall=1,
                             n_corrupt=2, n_items=10)
        assert a == b
        lanes = [l for l, _ in (*a.nan_events, *a.stall_events)]
        assert len(set(lanes)) == len(lanes)          # distinct victims
        assert len(lanes) <= 3                        # >=1 healthy lane
        assert FaultPlan.seeded(8, lanes=4).nan_events != a.nan_events \
            or FaultPlan.seeded(8, lanes=4).stall_events \
            != a.stall_events

    def test_lane_bounds_validated(self):
        with pytest.raises(ValueError, match="fault lane"):
            FaultPlan(lanes=2, nan_events=((2, 1),))

    def test_corrupt_stream_plants_nan_in_planned_items_only(self):
        plan = FaultPlan(lanes=2, corrupt_indices=(1,))
        items = trip_items([3, 4, 5])
        out = list(plan.corrupt_stream(items))
        assert not np.isfinite(out[1]).all()
        assert np.isfinite(out[0]).all() and np.isfinite(out[2]).all()
        assert np.isfinite(items[1]).all()            # original untouched


# ---------------------------------------------------------------------------
# Farm chaos — the acceptance fault plan through both dispatchers
# ---------------------------------------------------------------------------

PLAN = FaultPlan(lanes=4, nan_events=((1, 2),), stall_events=((2, 1 << 20),))
TRIPS = [3, 9, 5, 7, 4, 6, 2, 8]


class TestFarmChaos:
    def _loops(self, max_iters=32):
        clean = mk_countdown(max_iters=max_iters,
                             sentinel=Sentinel(nan=True, patience=3))
        return clean, PLAN.instrument(clean)

    def test_exactly_once_and_statuses_no_retry(self):
        """max_attempts=1: the occupant of a faulted slot surfaces its
        non-ok status (poisoned / nonconverged) and lands on the
        dead-letter list; healthy-slot items are ok and bit-identical
        to the fault-free run; nothing hangs, nothing emits twice."""
        clean, faulty = self._loops()
        items = trip_items(TRIPS)
        ref = stream(FarmEngine(clean, lanes=4, segment=4), items)
        eng = FarmEngine(faulty, lanes=4, segment=4)
        got = stream(eng, items)
        assert sorted(got) == list(range(len(items)))
        statuses = {i: got[i].status for i in got}
        assert "poisoned" in statuses.values()
        # the stalled lane diverges (patience) or exhausts its budget
        assert set(statuses.values()) <= {"ok", "poisoned",
                                          "nonconverged", "timed_out"}
        n_bad = sum(1 for s in statuses.values() if s != "ok")
        assert n_bad >= 2
        for i, r in got.items():
            if r.status == "ok":
                np.testing.assert_array_equal(r.a, ref[i].a)
                assert int(r.iters) == int(ref[i].iters)
                assert np.isfinite(r.a).all()
        assert sorted(d.index for d in eng.dead_letter) == sorted(
            i for i, s in statuses.items() if s != "ok")

    def test_retry_into_fresh_slot_recovers_everything(self):
        """The faults ride the SLOTS, so a retried item escapes into a
        fresh slot and converges — with enough attempts EVERY item ends
        ok and bit-identical, the failing slots rack up consecutive
        failures and are quarantined out of the rotation."""
        clean, faulty = self._loops()
        items = trip_items(TRIPS)
        ref = stream(FarmEngine(clean, lanes=4, segment=4), items)
        eng = FarmEngine(faulty, lanes=4, segment=4, max_attempts=3,
                         slot_patience=2)
        got = stream(eng, items)
        assert all(r.status == "ok" for r in got.values()), {
            i: r.status for i, r in got.items()}
        for i, r in got.items():
            np.testing.assert_array_equal(r.a, ref[i].a)
        assert any(r.attempts > 1 for r in got.values())
        assert eng.stats["retries"] > 0
        assert 1 <= eng.stats["quarantined_slots"] <= 2   # both faulted
        assert eng.stats["quarantined_lane_steps"] > 0
        assert eng.dead_letter == []
        # one compilation still serves the whole faulted stream
        assert eng.stats["segment_traces"] == 1
        assert eng.stats["refill_traces"] == 1

    def test_round_mode_surfaces_statuses_too(self):
        """The synchronous dispatcher (``chained=False``) seats item i in
        slot i and surfaces the same per-slot statuses: the NaN-ed slot
        poisons its item, the stalled slot's item fails, the rest are
        ok and finite."""
        _, faulty = self._loops()
        eng = FarmEngine(faulty, lanes=4, segment=4, chained=False)
        got = stream(eng, trip_items([3, 5, 4, 6]))
        statuses = [got[i].status for i in range(4)]
        assert statuses[1] == "poisoned"
        assert statuses[0] == "ok" and np.isfinite(got[0].a).all()
        assert statuses[2] != "ok"                    # stalled lane
        assert eng.quarantined_lane_steps > 0

    def test_waste_dominance_under_faults(self):
        """The acceptance inequality: under the fault plan, the farm's
        wasted+quarantined lane steps stay strictly below those of a
        barrier farm on the same emitted trip counts — batches of
        ``lanes`` items in stream order, each run to its slowest, burn
        ``lanes·max(it) − Σit``: the stalled lane becomes a straggler
        whose shadow the barrier burns on every healthy lane."""
        _, faulty = self._loops()
        items = trip_items(TRIPS)
        eng = FarmEngine(faulty, lanes=4, segment=4)
        got = stream(eng, items)
        its = [int(got[i].iters) for i in range(len(items))]
        barrier = sum(4 * max(its[i:i + 4]) - sum(its[i:i + 4])
                      for i in range(0, len(its), 4))
        quarantined = sum(int(r.iters) for r in got.values()
                          if r.status != "ok")
        assert eng.quarantined_lane_steps == quarantined
        cost = eng.wasted_lane_steps + eng.quarantined_lane_steps
        assert cost < barrier + quarantined, (eng.stats, its)

    def test_quarantine_never_eats_the_last_slot(self):
        """lanes=1 degenerate: the only slot fails every occupant, yet
        is never retired — the stream still drains (non-ok, bounded
        attempts, no deadlock)."""
        plan = FaultPlan(lanes=1, stall_events=((0, 1 << 20),))
        loop = plan.instrument(mk_countdown(max_iters=8))
        eng = FarmEngine(loop, lanes=1, segment=4, max_attempts=2,
                         slot_patience=1)
        got = stream(eng, trip_items([3, 4]))
        assert all(r.status != "ok" for r in got.values())
        assert all(r.attempts == 2 for r in got.values())
        assert eng.stats["quarantined_slots"] == 0
        assert len(eng.dead_letter) == 2


# ---------------------------------------------------------------------------
# Prep-boundary corruption — the admission finite check
# ---------------------------------------------------------------------------


class TestAdmissionCheck:
    def test_round_mode_rejects_nonfinite_batch_loudly(self):
        """A NaN item after the slots are bound is rejected at the prep
        boundary — ``status="rejected"``, dead-lettered — and the rest
        of the stream is untouched."""
        eng = FarmEngine(mk_countdown(), lanes=2, segment=4)
        eng.run(trip_items([2, 3]), lambda r: None)   # binds
        bad = trip_items([2, 3])
        bad[1][4, 7] = np.nan
        got = stream(eng, bad)
        assert {i: r.status for i, r in got.items()} == {
            0: "ok", 1: "rejected"}
        assert got[1].a is None
        assert [d.index for d in eng.dead_letter] == [1]

    def test_continuous_mode_rejects_and_keeps_streaming(self):
        """A corrupted item is shed at the door — status='rejected',
        dead-lettered, slot never dirtied — and the stream continues;
        clean items are unaffected."""
        plan = FaultPlan(lanes=2, corrupt_indices=(1, 4))
        items = trip_items([3, 5, 4, 6, 2])
        eng = FarmEngine(mk_countdown(), lanes=2, segment=4)
        got = stream(eng, plan.corrupt_stream(items))
        assert {i: r.status for i, r in got.items()} == {
            0: "ok", 1: "rejected", 2: "ok", 3: "ok", 4: "rejected"}
        assert all(got[i].a is None for i in (1, 4))
        assert eng.stats["rejected"] == 2
        assert sorted(d.index for d in eng.dead_letter) == [1, 4]

    def test_env_leaves_checked_too(self):
        from repro.kernels import ref as R
        loop = LoopOfStencilReduce(
            f=R.restore_taps(2.0), k=1, combine="max",
            cond=lambda r: r < 1e-3, delta=R.abs_delta,
            boundary="reflect", max_iters=16, backend="jnp",
            interpret=True)
        a = trip_items([3])[0]
        mask = (a > 0.5).astype(np.float32)
        eng = FarmEngine(loop, lanes=2, segment=4)
        assert stream(eng, [(a, a.copy(), mask)])[0].status != "rejected"
        bad_mask = mask.copy()
        bad_mask[3, 9] = np.inf
        # only the env leaf is non-finite: the check reads every leaf
        got = stream(eng, [(a, a.copy(), mask), (a, a.copy(), bad_mask)])
        assert got[0].status != "rejected" and got[0].a is not None
        assert got[1].status == "rejected" and got[1].a is None
        assert eng.stats["rejected"] == 1

    def test_check_finite_off_defers_to_the_sentinel(self):
        """check_finite=False admits the poisoned item; the sentinel
        catches the NaN on device and quarantines the lane instead of
        spinning it to the iteration cap."""
        plan = FaultPlan(lanes=2, corrupt_indices=(1,))
        items = trip_items([3, 5, 4])
        eng = FarmEngine(
            mk_countdown(max_iters=32, sentinel=Sentinel(nan=True)),
            lanes=2, segment=4, check_finite=False)
        got = stream(eng, plan.corrupt_stream(items))
        assert got[1].status == "poisoned"
        assert int(got[1].iters) < 32                 # no spin to cap
        assert got[0].status == "ok" and got[2].status == "ok"
        assert np.isfinite(got[0].a).all()
        assert np.isfinite(got[2].a).all()


# ---------------------------------------------------------------------------
# Sharded NaN containment — 8 virtual devices, subprocess
# ---------------------------------------------------------------------------

SRC = os.path.join(os.path.dirname(__file__), "..", "..", "src")


def run_multidevice(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
class TestShardedNaNContainment:
    def test_nan_frame_contained_to_its_lane(self):
        """Composed farm (2 lanes × 4 spatial shards): a NaN planted in
        ONE lane's frame spreads through THAT lane's ghost exchange
        only — the NaN-safe pmax re-propagation makes every spatial
        shard of the poisoned lane agree on the NaN reduce (uniform
        quarantine, no hang), while the neighbour lane's reductions
        stay finite and its results land bit-identical to a fault-free
        run."""
        out = run_multidevice("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import FarmEngine, GridPartition, LoopOfStencilReduce
from repro.core.reduce import Sentinel

def countdown(get, *_):
    return get(0, 0) - 1.0

def mk(part):
    return LoopOfStencilReduce(
        f=countdown, k=1, combine="max", cond=lambda r: r < 0.5,
        boundary="zero", max_iters=32, backend="pallas-sharded",
        partition=part, interpret=True, block=(16, 128),
        sentinel=Sentinel(nan=True))

def trip_items(trips, shape=(32, 64)):
    base = np.linspace(0.1, 0.9, shape[0] * shape[1],
                       dtype=np.float32).reshape(shape)
    return [base + float(t) - 1.0 for t in trips]

from repro.sharding.specs import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
part = GridPartition(mesh=mesh, axis_names=("model",), array_axes=(0,))

items = trip_items([3, 9, 5, 7, 4, 6])
bad = [it.copy() for it in items]
bad[1][20, 33] = np.nan          # one cell of one item's frame

def drive(items):
    eng = FarmEngine(mk(part), lanes=2, mesh=mesh, segment=4,
                     check_finite=False)
    got = {}
    n = eng.run(items, lambda r: got.setdefault(r.index, r))
    assert n == len(items) == len(got), (n, len(got))
    return got

ref = drive(items)
got = drive(bad)
assert got[1].status == "poisoned", got[1].status
assert int(got[1].iters) < 32     # quarantined, not spun to the cap
for i in got:
    if i == 1:
        continue
    assert got[i].status == "ok", (i, got[i].status)
    assert np.isfinite(np.asarray(got[i].a)).all(), i
    assert np.isfinite(np.asarray(got[i].reduced)).all(), i
    np.testing.assert_array_equal(np.asarray(got[i].a),
                                  np.asarray(ref[i].a))

# NaN-safe pmin: min-monoid convergence is untouched by the
# re-propagation guard when nothing is NaN
mn = LoopOfStencilReduce(
    f=lambda get, *_: get(0, 0) - 1.0, k=1, combine="min",
    cond=lambda r: r < -40.0, boundary="zero", max_iters=64,
    backend="pallas-sharded", partition=part, interpret=True,
    block=(16, 128))
res = mn.run(jnp.asarray(trip_items([5])[0]))
assert np.isfinite(float(res.reduced))
assert int(res.iters) < 64
print("OKCONTAIN")
""")
        assert "OKCONTAIN" in out
