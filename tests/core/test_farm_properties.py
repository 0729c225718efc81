"""Property tests for continuous-refill invariants (hypothesis).

The dispatcher contract, over random lane counts / item counts / segment
lengths S:

* every stream item is processed EXACTLY once and lands at its own
  index — no drops, no duplicates, regardless of how refills interleave;
* a refilled slot carries NOTHING over from its previous occupant —
  values, trip counts and ghost rings all match the item's solo run
  (stale ghosts would corrupt boundary-reading workers on the persistent
  backends);
* ragged tails (items < lanes, including the empty stream) stay
  done-masked: unoccupied slots never emit.
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis "
                    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core import FarmEngine, LoopOfStencilReduce
from repro.kernels import ref as R


def countdown(get, *_):
    return get(0, 0) - 1.0


def mk_countdown(backend="jnp", max_iters=64):
    return LoopOfStencilReduce(
        f=countdown, k=1, combine="max", cond=lambda r: r < 0.5,
        boundary="zero", max_iters=max_iters, backend=backend,
        interpret=True, block=(32, 128))


def trip_items(trips, shape=(6, 10)):
    base = np.linspace(0.1, 0.9, shape[0] * shape[1],
                       dtype=np.float32).reshape(shape)
    return [base + float(t) - 1.0 for t in trips]


class TestRefillInvariants:
    @settings(deadline=None, max_examples=25)
    @given(lanes=st.integers(1, 5),
           trips=st.lists(st.integers(1, 9), min_size=0, max_size=12),
           segment=st.integers(1, 7))
    def test_every_item_exactly_once(self, lanes, trips, segment):
        """Random lane/item/segment geometry: each index emitted once,
        each result equal to its solo run (trip count AND values)."""
        eng = FarmEngine(mk_countdown(), lanes=lanes, segment=segment)
        outs = []
        n = eng.run(trip_items(trips), outs.append)
        assert n == len(trips)
        assert sorted(r.index for r in outs) == list(range(len(trips)))
        outs.sort(key=lambda r: r.index)
        for t, res in zip(trips, outs):
            assert int(res.iters) == t
            np.testing.assert_array_equal(
                res.a, trip_items([t])[0] - float(t))

    @settings(deadline=None, max_examples=25)
    @given(lanes=st.integers(1, 4),
           trips=st.lists(st.integers(1, 9), min_size=1, max_size=10),
           segment=st.integers(1, 7))
    def test_accounting_invariants(self, lanes, trips, segment):
        """lane_steps = useful + wasted, with useful = Σ trip counts —
        the waste metric never undercounts (and never goes negative)."""
        eng = FarmEngine(mk_countdown(), lanes=lanes, segment=segment)
        n = eng.run(trip_items(trips), lambda r: None)
        assert n == len(trips)
        useful = sum(trips)
        assert eng.stats["wasted_lane_steps"] >= 0
        assert eng.lane_steps == useful + eng.stats["wasted_lane_steps"]
        assert eng.stats["refills"] == len(trips)

    @settings(deadline=None, max_examples=8)
    @given(scales=st.lists(
               st.floats(0.2, 6.0, allow_nan=False), min_size=1,
               max_size=6),
           segment=st.integers(1, 6),
           lanes=st.integers(1, 3))
    def test_no_stale_ghost_after_refill(self, scales, segment, lanes):
        """Persistent-frame backend, boundary-READING worker (reflect):
        if a refill left the previous occupant's ghost ring in place,
        the first sweep after the refill would read it and the result
        would diverge from the item's solo run."""
        loop = LoopOfStencilReduce(
            f=R.heat_taps(0.1), k=1, combine="max",
            cond=lambda r: r < 2e-3, delta=R.abs_delta,
            boundary="reflect", max_iters=40, backend="pallas",
            interpret=True, block=(32, 128))
        rng = np.random.default_rng(7)
        base = np.asarray(rng.normal(size=(12, 130)), np.float32)
        items = [base * s for s in scales]
        eng = FarmEngine(loop, lanes=lanes, segment=segment)
        outs = []
        assert eng.run(items, outs.append) == len(items)
        outs.sort(key=lambda r: r.index)
        for it, res in zip(items, outs):
            ref = loop.run(jnp.asarray(it))
            assert int(res.iters) == int(ref.iters)
            np.testing.assert_allclose(res.a, np.asarray(ref.a),
                                       atol=1e-5)

    @settings(deadline=None, max_examples=15)
    @given(lanes=st.integers(2, 6),
           n_items=st.integers(0, 5),
           segment=st.integers(1, 5))
    def test_ragged_tail_done_masked(self, lanes, n_items, segment):
        """items <= lanes: the unoccupied slots must neither emit nor
        stall the stream (they enter every segment done-masked)."""
        n_items = min(n_items, lanes)
        trips = list(range(1, n_items + 1))
        eng = FarmEngine(mk_countdown(), lanes=lanes, segment=segment)
        outs = []
        n = eng.run(trip_items(trips), outs.append)
        assert n == n_items == len(outs)
        assert sorted(r.index for r in outs) == list(range(n_items))
