"""Lane-resident streaming engine: farm_run parity + lane-slot reuse.

Parity: ``farm_run`` (ONE done-masked while_loop over a stacked
(lanes, frame) carry) must match ``farm(run)`` (vmap of the scalar loop)
lane for lane — values, reduces, per-lane trip counts — on mixed
convergence speeds, across the jnp / pallas / pallas-multistep backends.

Slot reuse: processing stream item i+1 in an existing lane slot performs
no ``jnp.pad``, no full-frame copy, and no re-framing — only the
O(interior) refill plus the ghost-ring refresh.  Verified by jaxpr
inspection of the FarmEngine round, by trace counting across a whole
stream (ONE compilation, ragged final round included), and by the
engine's own host-transfer accounting (interiors cross the boundary,
frames never do).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import FarmEngine, LoopOfStencilReduce, farm
from repro.core.executor import auto_unroll, check_unroll_feasible
from repro.core.introspect import flatten_eqns, while_body_eqns
from repro.kernels import ref as R

BACKENDS = ["jnp", "pallas", "pallas-multistep"]


def heat(get, *_):
    lap = (get(-1, 0) + get(1, 0) + get(0, -1) + get(0, 1)
           - 4.0 * get(0, 0))
    return get(0, 0) + 0.1 * lap


def mkloop(backend, unroll=1, boundary="reflect", max_iters=60):
    return LoopOfStencilReduce(
        f=heat, k=1, combine="max", cond=lambda r: r < 2e-3,
        delta=R.abs_delta, boundary=boundary, max_iters=max_iters,
        unroll=unroll, backend=backend, interpret=True, block=(32, 128))


def mixed_batch(rng, n=4, shape=(40, 136)):
    """Stacked items with deliberately different convergence speeds."""
    u0 = jnp.asarray(rng.normal(size=shape), jnp.float32)
    scales = (1.0, 5.0, 0.1, 2.0, 0.5, 3.0)
    return jnp.stack([u0 * scales[i % len(scales)] for i in range(n)])


class TestFarmRunParity:
    @pytest.mark.parametrize("backend,unroll", [
        ("jnp", 1), ("pallas", 1), ("pallas", 2),
        ("pallas-multistep", 3)])
    def test_matches_vmapped_run_mixed_trip_counts(self, backend, unroll,
                                                   rng):
        loop = mkloop(backend, unroll)
        batch = mixed_batch(rng)
        want = farm(loop.run)(batch)
        got = loop.farm_run(batch)
        iters = np.asarray(got.iters)
        assert len(set(iters.tolist())) > 1, "want MIXED trip counts"
        np.testing.assert_array_equal(iters, np.asarray(want.iters))
        np.testing.assert_allclose(np.asarray(got.a),
                                   np.asarray(want.a), atol=1e-5)
        np.testing.assert_allclose(np.asarray(got.reduced),
                                   np.asarray(want.reduced), atol=1e-6)

    def test_done0_premasks_lanes(self, rng):
        loop = mkloop("pallas")
        batch = mixed_batch(rng)
        done0 = jnp.asarray([False, True, False, False])
        res = loop.farm_run(batch, done0=done0)
        assert int(res.iters[1]) == 0
        np.testing.assert_allclose(np.asarray(res.a[1]),
                                   np.asarray(batch[1]), atol=0)

    def test_env_fields_per_lane(self, rng):
        loop = LoopOfStencilReduce(
            f=R.restore_taps(2.0), k=1, combine="max",
            cond=lambda r: r < 1e-3, delta=R.abs_delta,
            boundary="reflect", max_iters=24, backend="pallas",
            interpret=True, block=(32, 128))
        batch = mixed_batch(rng, n=3)
        masks = (batch > 1.0).astype(jnp.float32)
        got = loop.farm_run(batch, env=(batch, masks))
        for i in range(3):
            ref = loop.run(batch[i], env=(batch[i], masks[i]))
            assert int(got.iters[i]) == int(ref.iters)
            np.testing.assert_allclose(np.asarray(got.a[i]),
                                       np.asarray(ref.a), atol=1e-5)

    def test_s_variant_and_sharded_rejected(self):
        loop = LoopOfStencilReduce(
            f=heat, cond=lambda r, s: True,
            state_init=lambda: jnp.zeros(()),
            state_update=lambda s, a, it: s)
        with pytest.raises(ValueError, match="-s variant"):
            loop.farm_run(jnp.zeros((2, 8, 128)))
        sharded = LoopOfStencilReduce(
            f=heat, cond=lambda r: True, backend="pallas-sharded",
            partition=object())
        with pytest.raises(ValueError, match="FarmEngine"):
            sharded.farm_run(jnp.zeros((2, 8, 128)))


class TestFarmEngineStream:
    @pytest.mark.parametrize("backend", BACKENDS)
    def test_stream_parity_with_per_item_runs(self, backend, rng):
        """5 items through 2 lane slots (2 full rounds + a ragged one):
        every item must match its solo run exactly — the refilled slot
        carries nothing over from the previous occupant."""
        loop = mkloop(backend, unroll=3 if "multistep" in backend else 1)
        items = [np.asarray(x) for x in mixed_batch(rng, n=5)]
        eng = FarmEngine(loop, lanes=2)
        outs = []
        n = eng.run(items, outs.append)
        assert n == 5 and eng.stats["rounds"] == 3
        for it, res in zip(items, outs):
            ref = loop.run(jnp.asarray(it))
            assert int(res.iters) == int(ref.iters)
            np.testing.assert_allclose(np.asarray(res.a),
                                       np.asarray(ref.a), atol=1e-5)

    def test_empty_source_and_oversize_batch(self):
        eng = FarmEngine(mkloop("pallas"), lanes=2)
        assert eng.run(lambda: iter([]), lambda r: None) == 0
        with pytest.raises(ValueError, match="exceeds"):
            eng.round(np.zeros((3, 8, 128), np.float32))

    def test_one_compilation_across_the_stream(self, rng):
        """The whole stream — ragged final round included — must hit ONE
        compilation of the round function: the host pads short batches
        to the lane count, so shapes never change."""
        traces = {"n": 0}

        def counted_heat(get, *_):
            traces["n"] += 1
            return heat(get)

        loop = LoopOfStencilReduce(
            f=counted_heat, k=1, combine="max", cond=lambda r: r < 2e-3,
            delta=R.abs_delta, boundary="zero", max_iters=12,
            backend="pallas", interpret=True, block=(32, 128))
        items = [np.asarray(x) for x in mixed_batch(rng, n=7)]
        eng = FarmEngine(loop, lanes=3)
        n = eng.run(items[:3], lambda r: None)
        assert n == 3
        after_first = traces["n"]
        assert after_first > 0
        n = eng.run(items[3:], lambda r: None)      # incl. ragged round
        assert n == 4
        assert traces["n"] == after_first, \
            f"worker retraced: {traces['n']} != {after_first}"

    def test_host_transfer_is_interior_sized(self, rng):
        """Per item, exactly the (m, n) interior crosses the host
        boundary in each direction (plus the scalar reduce/iters) — the
        (m+2p, n+2p) frames never do."""
        m, n_ = 40, 136
        loop = mkloop("pallas", max_iters=8)
        items = [np.asarray(x) for x in mixed_batch(rng, n=4,
                                                    shape=(m, n_))]
        eng = FarmEngine(loop, lanes=2)
        count = eng.run(items, lambda r: None)
        cell = 4                                   # f32
        want_h2d = eng.stats["rounds"] * 2 * m * n_ * cell
        want_d2h = eng.stats["rounds"] * 2 * (m * n_ * cell + cell + 4)
        assert eng.stats["h2d_bytes"] == want_h2d
        assert eng.stats["d2h_bytes"] == want_d2h
        frame_bytes = (m + 2) * (n_ + 2) * cell
        assert eng.stats["h2d_bytes"] / count < frame_bytes


def _round_jaxpr(backend, rng, unroll=1):
    """Trace one FarmEngine round (slots already bound — this is the
    steady-state 'process item i+1 in an existing slot' program)."""
    loop = mkloop(backend, unroll=unroll, max_iters=8)
    eng = FarmEngine(loop, lanes=2)
    items = np.stack([np.asarray(x) for x in mixed_batch(rng, n=2)])
    eng.round(items)                     # binds + fills the slots
    active = jnp.ones((2,), bool)
    return jax.make_jaxpr(eng._round_impl)(
        eng._frames, eng._env_frames, jnp.asarray(items), active)


class TestLaneSlotReuse:
    """The acceptance criterion, by jaxpr inspection: stream item i+1
    lands in an existing lane slot with no pad, no full-frame copy and
    no re-framing — only the O(interior) refill + ghost refresh."""

    @pytest.mark.parametrize("backend,unroll",
                             [("pallas", 1), ("pallas-multistep", 3)])
    def test_no_pad_no_reframe_in_round(self, backend, unroll, rng):
        jaxpr = _round_jaxpr(backend, rng, unroll)
        eqns = flatten_eqns(jaxpr.jaxpr, [])
        names = [e.primitive.name for e in eqns]
        assert "pad" not in names, "re-framing pad in the streaming round"

        # no re-allocation of the frame stack: nothing materialises a
        # fresh full-frame-sized float array (the bool done-mask select
        # is the only frame-sized broadcast allowed)
        lanes, fh, fw = 2, 42, 138                 # (40,136) + 2*pad
        frame_elems = lanes * fh * fw
        for e in eqns:
            if e.primitive.name in ("broadcast_in_dim", "iota"):
                for v in e.outvars:
                    if (np.issubdtype(v.aval.dtype, np.floating)
                            and int(np.prod(v.aval.shape)) >= frame_elems):
                        raise AssertionError(
                            f"full-frame allocation in round: {e}")

        # every dynamic_update_slice writes at most the interior stack
        # (the refill) — a full-frame copy would exceed it
        interior_elems = lanes * 40 * 136
        for e in eqns:
            if e.primitive.name == "dynamic_update_slice":
                upd = e.invars[1].aval
                assert int(np.prod(upd.shape)) <= interior_elems, \
                    f"super-interior DUS in round: {upd.shape}"

    @pytest.mark.parametrize("backend", ["pallas", "pallas-multistep"])
    def test_while_body_is_the_persistent_kernel(self, backend, rng):
        """Inside the shared while body: the vmapped fused kernel and
        the edge-sized ghost refresh — no pad, no interior-sized copies
        beyond the kernel's own frame round-trip."""
        loop = mkloop(backend, unroll=3 if "multistep" in backend else 1,
                      max_iters=8)
        eng = FarmEngine(loop, lanes=2)
        items = np.stack([np.asarray(x) for x in mixed_batch(rng, n=2)])
        eng.round(items)
        active = jnp.ones((2,), bool)
        eqns = while_body_eqns(
            lambda fr, it, act: eng._round_impl(fr, (), it, act)[2],
            eng._frames, jnp.asarray(items), active)
        names = [e.primitive.name for e in eqns]
        assert "pallas_call" in names
        assert "pad" not in names


def countdown(get, *_):
    """Every cell decrements by 1 per sweep — an item whose max value is
    v converges in EXACTLY v sweeps (cond: max < 0.5), so trip-count
    spreads are programmable per item."""
    return get(0, 0) - 1.0


def mk_countdown(backend, max_iters=256, unroll=1):
    return LoopOfStencilReduce(
        f=countdown, k=1, combine="max", cond=lambda r: r < 0.5,
        boundary="zero", max_iters=max_iters, unroll=unroll,
        backend=backend, interpret=True, block=(32, 128))


def trip_items(trips, shape=(8, 128)):
    """Stream items with the given per-item trip counts."""
    base = np.linspace(0.1, 0.9, shape[0] * shape[1], dtype=np.float32)
    base = base.reshape(shape)
    return [base + float(t) - 1.0 for t in trips]


SPREADS = {
    "uniform": [6, 6, 6, 6, 6, 6],
    "bimodal": [1, 200, 1, 200, 1, 1],
    "straggler": [2, 2, 2, 200, 2, 2],
}


class TestContinuousFarm:
    """The tentpole acceptance: continuous refill matches the round farm
    and the sequential reference item for item, while strictly cutting
    the done-masked lane sweeps the straggler barrier burns."""

    @pytest.mark.parametrize("spread", list(SPREADS))
    def test_parity_and_waste_drop_jnp(self, spread):
        trips = SPREADS[spread]
        items = trip_items(trips)
        loop = mk_countdown("jnp")

        # sequential reference: farm(run) over the stacked batch
        want = farm(loop.run)(jnp.stack(items))
        np.testing.assert_array_equal(np.asarray(want.iters), trips)

        eng_round = FarmEngine(loop, lanes=2)
        round_outs = []
        assert eng_round.run(items, round_outs.append) == len(items)

        eng_cont = FarmEngine(loop, lanes=2, segment=8)
        cont_outs = []
        assert eng_cont.run(items, cont_outs.append,
                            continuous=True) == len(items)
        cont_outs.sort(key=lambda r: r.index)

        for i, (ro, co) in enumerate(zip(round_outs, cont_outs)):
            assert co.index == i
            assert int(ro.iters) == int(co.iters) == trips[i]
            np.testing.assert_array_equal(np.asarray(ro.a), co.a)
            np.testing.assert_array_equal(np.asarray(want.a[i]), co.a)

        # the metric: total lane sweeps strictly drop whenever the
        # spread gives the barrier something to waste
        assert eng_cont.lane_steps <= eng_round.lane_steps
        if spread != "uniform":
            assert eng_cont.lane_steps < eng_round.lane_steps
            assert (eng_cont.stats["wasted_lane_steps"]
                    < eng_round.stats["wasted_lane_steps"])

    @pytest.mark.parametrize("backend,unroll",
                             [("pallas", 1), ("pallas-multistep", 3)])
    def test_parity_and_waste_drop_pallas(self, backend, unroll):
        trips = [3, 42, 3, 3, 42, 3]
        items = trip_items(trips)
        loop = mk_countdown(backend, max_iters=60, unroll=unroll)

        eng_round = FarmEngine(loop, lanes=2)
        round_outs = []
        assert eng_round.run(items, round_outs.append) == len(items)

        eng_cont = FarmEngine(loop, lanes=2, segment=6)
        cont_outs = []
        assert eng_cont.run(items, cont_outs.append,
                            continuous=True) == len(items)
        cont_outs.sort(key=lambda r: r.index)
        for i, (ro, co) in enumerate(zip(round_outs, cont_outs)):
            assert int(ro.iters) == int(co.iters)
            np.testing.assert_allclose(np.asarray(ro.a), co.a, atol=1e-5)
        assert eng_cont.wasted_lane_steps < eng_round.wasted_lane_steps

    def test_completion_order_beats_the_barrier(self):
        """A 1-sweep item sharing a cohort with a 200-sweep straggler is
        emitted FIRST in continuous mode — the round barrier would hold
        it until the straggler converged."""
        items = trip_items([200, 1, 1, 1])
        eng = FarmEngine(mk_countdown("jnp"), lanes=2, segment=8)
        trips = []
        eng.run(items, lambda r: trips.append(int(r.iters)))
        assert trips == [200, 1, 1, 1]          # barrier: item 0 first
        eng = FarmEngine(mk_countdown("jnp"), lanes=2, segment=8)
        order = []
        eng.run(items, lambda r: order.append(r.index), continuous=True)
        assert order[0] == 1 and order[-1] == 0, order

    def test_one_compilation_across_segments_and_refills(self):
        """The whole continuous stream — every segment, every refill,
        the ragged tail included — hits ONE compilation of each entry
        point (the carry shapes round-trip unchanged)."""
        traces = {"n": 0}

        def counted(get, *_):
            traces["n"] += 1
            return countdown(get)

        loop = LoopOfStencilReduce(
            f=counted, k=1, combine="max", cond=lambda r: r < 0.5,
            boundary="zero", max_iters=64, backend="pallas",
            interpret=True, block=(32, 128))
        eng = FarmEngine(loop, lanes=3, segment=5)
        n = eng.run(trip_items([2, 9, 4, 17, 3, 5, 2]),
                    lambda r: None, continuous=True)
        assert n == 7
        assert eng.stats["segment_traces"] == 1
        # chained + ring-seeded initial cohort: the classic per-slot
        # refill never compiles on a fault-free stream
        assert eng.stats["refill_traces"] == 0
        assert eng.stats["refills"] == 7
        after_first = traces["n"]
        assert after_first > 0
        # a second stream through the same engine state must not retrace
        eng.run(trip_items([4, 2]), lambda r: None, continuous=True)
        assert traces["n"] == after_first, "continuous worker retraced"
        assert eng.stats["segment_traces"] == 1

    def test_ragged_tail_and_empty_source(self):
        eng = FarmEngine(mk_countdown("jnp"), lanes=4, segment=4)
        assert eng.run(lambda: iter([]), lambda r: None,
                       continuous=True) == 0
        outs = []
        assert eng.run(trip_items([5, 2]), outs.append,
                       continuous=True) == 2    # items < lanes
        outs.sort(key=lambda r: r.index)
        assert [int(o.iters) for o in outs] == [5, 2]

    def test_mode_mixing_rejected(self):
        eng = FarmEngine(mk_countdown("jnp"), lanes=2)
        eng.run(trip_items([2, 3]), lambda r: None)
        with pytest.raises(ValueError, match="round mode"):
            eng.run(trip_items([2]), lambda r: None, continuous=True)
        eng = FarmEngine(mk_countdown("jnp"), lanes=2, segment=3)
        eng.run(trip_items([2]), lambda r: None, continuous=True)
        with pytest.raises(ValueError, match="continuous mode"):
            eng.round(np.stack(trip_items([2, 3])))
        with pytest.raises(ValueError, match="segment"):
            FarmEngine(mk_countdown("jnp"), lanes=2, segment=0)

    def test_composed_sharded_continuous_accepted(self):
        """The PR-4 rejection is GONE: a composed (lanes × spatial)
        engine streams continuously — parity and waste are pinned by
        the multi-device matrix in TestComposedContinuous; here the
        1×1-mesh degenerate case runs in process."""
        from repro.core import GridPartition
        from repro.sharding.specs import make_mesh
        mesh = make_mesh((1, 1), ("lanes", "model"))
        part = GridPartition(mesh=mesh, axis_names=("model",),
                             array_axes=(0,))
        loop = LoopOfStencilReduce(
            f=countdown, cond=lambda r: r < 0.5, combine="max",
            backend="pallas-sharded", partition=part, interpret=True,
            block=(32, 128))
        eng = FarmEngine(loop, lanes=1, mesh=mesh, lane_axis="lanes",
                         segment=4)
        outs = []
        assert eng.run(trip_items([3, 5]), outs.append,
                       continuous=True) == 2
        outs.sort(key=lambda r: r.index)
        assert [int(o.iters) for o in outs] == [3, 5]

    def test_sink_exception_does_not_corrupt_the_engine(self):
        """A raising sink degrades each affected item to a failed
        StreamResult on ``dead_letter`` instead of killing the stream
        (the other in-flight slots' items survive), and leaves the
        engine on LIVE buffers — a second run must work (regression:
        a second run crashed on deleted buffers)."""
        eng = FarmEngine(mk_countdown("jnp"), lanes=2, segment=4)

        def boom(r):
            raise RuntimeError("sink failed")
        assert eng.run(trip_items([2, 3, 4]), boom, continuous=True) == 3
        assert eng.stats["sink_errors"] == 3
        failed = [r for r in eng.dead_letter
                  if r.error and "sink failed" in r.error]
        assert sorted(r.index for r in failed) == [0, 1, 2]
        assert all(r.status == "failed" for r in failed)
        outs = []
        assert eng.run(trip_items([2, 3, 4]), outs.append,
                       continuous=True) == 3
        assert sorted(r.index for r in outs) == [0, 1, 2]

    def test_env_fields_survive_refill(self, rng):
        """Per-item env fields ride the continuous refill: every item's
        result must match its solo run with ITS OWN env — a slot that
        kept the previous occupant's env would diverge."""
        loop = LoopOfStencilReduce(
            f=R.restore_taps(2.0), k=1, combine="max",
            cond=lambda r: r < 1e-3, delta=R.abs_delta,
            boundary="reflect", max_iters=24, backend="pallas",
            interpret=True, block=(32, 128))
        items = [np.asarray(x) for x in mixed_batch(rng, n=5)]

        def prep(item):
            return item, (item, (item > 1.0).astype(jnp.float32))

        eng = FarmEngine(loop, lanes=2, prep=prep, segment=6)
        outs = []
        assert eng.run(items, outs.append, continuous=True) == 5
        outs.sort(key=lambda r: r.index)
        for it, res in zip(items, outs):
            a0, envs = prep(jnp.asarray(it))
            ref = loop.run(a0, env=envs)
            assert int(res.iters) == int(ref.iters)
            np.testing.assert_allclose(res.a, np.asarray(ref.a),
                                       atol=1e-5)


def _segment_jaxpr(backend, unroll=1):
    """Trace one steady-state continuous segment (slots bound and the
    carry mid-stream — the program every segment of the stream reuses)."""
    loop = mk_countdown(backend, max_iters=32, unroll=unroll)
    eng = FarmEngine(loop, lanes=2, segment=4)
    eng.run(trip_items([3, 5, 4]), lambda r: None, continuous=True)
    r, it, done, hw = eng._cont_carry
    return eng, jax.make_jaxpr(eng._segment_entry)(
        eng._frames, eng._env_frames, r, it, done, hw)


class TestContinuousJaxpr:
    """The zero-copy claim for the segmented loop, structurally: the
    steady-state segment and the per-slot refill contain no pad, no
    full-frame allocation and no super-interior copies."""

    @pytest.mark.parametrize("backend,unroll",
                             [("pallas", 1), ("pallas-multistep", 3)])
    def test_segment_has_no_pad_or_reframe(self, backend, unroll):
        eng, jaxpr = _segment_jaxpr(backend, unroll)
        eqns = flatten_eqns(jaxpr.jaxpr, [])
        names = [e.primitive.name for e in eqns]
        assert "pad" not in names, "re-framing pad in the segment"
        lanes, (fh, fw) = 2, eng._lspec.frame.shape
        frame_elems = lanes * fh * fw
        for e in eqns:
            if e.primitive.name in ("broadcast_in_dim", "iota"):
                for v in e.outvars:
                    if (np.issubdtype(v.aval.dtype, np.floating)
                            and int(np.prod(v.aval.shape)) >= frame_elems):
                        raise AssertionError(
                            f"full-frame allocation in segment: {e}")

    @pytest.mark.parametrize("backend,unroll",
                             [("pallas", 1), ("pallas-multistep", 3)])
    def test_segment_while_body_is_the_persistent_kernel(self, backend,
                                                         unroll):
        eng, _ = _segment_jaxpr(backend, unroll)
        r, it, done, hw = eng._cont_carry
        eqns = while_body_eqns(
            lambda fr, rr, ii, dd, hh: eng._segment_entry(fr, (), rr, ii,
                                                          dd, hh)[0],
            eng._frames, r, it, done, hw)
        names = [e.primitive.name for e in eqns]
        assert "pallas_call" in names
        assert "pad" not in names

    @pytest.mark.parametrize("backend,unroll",
                             [("pallas", 1), ("pallas-multistep", 3)])
    def test_refill_writes_at_most_one_interior(self, backend, unroll):
        """The per-slot refill: ONE (1, m, n) interior write plus edge-
        strip ghost refreshes — nothing frame-stack-sized materialises,
        no pad, no re-framing."""
        eng, _ = _segment_jaxpr(backend, unroll)
        r, it, done, hw = eng._cont_carry
        item = jnp.asarray(trip_items([3])[0])
        jaxpr = jax.make_jaxpr(eng._refill_impl)(
            eng._frames, eng._env_frames, r, it, done, hw,
            jnp.asarray(0, jnp.int32), item)
        eqns = flatten_eqns(jaxpr.jaxpr, [])
        names = [e.primitive.name for e in eqns]
        assert "pad" not in names, "re-framing pad in the refill"
        spec = eng._lspec.frame
        interior_elems = spec.m * spec.n
        for e in eqns:
            if e.primitive.name == "dynamic_update_slice":
                upd = e.invars[1].aval
                assert int(np.prod(upd.shape)) <= interior_elems, \
                    f"super-interior DUS in refill: {upd.shape}"
            if e.primitive.name in ("broadcast_in_dim", "iota"):
                for v in e.outvars:
                    if (np.issubdtype(v.aval.dtype, np.floating)
                            and int(np.prod(v.aval.shape))
                            >= 2 * np.prod(spec.shape)):
                        raise AssertionError(
                            f"frame-stack allocation in refill: {e}")


class TestEnvStreamItems:
    """Tuple stream items ``(a, *env)`` carry externally produced env
    fields through both modes, and EVERY leaf — env included — is
    guarded against mid-stream shape/dtype drift (regression: only the
    main leaf was checked, so a drifted env leaf reached the jitted
    refill and died as an opaque XLA shape error)."""

    @staticmethod
    def _mkloop():
        return LoopOfStencilReduce(
            f=R.restore_taps(2.0), k=1, combine="max",
            cond=lambda r: r < 1e-3, delta=R.abs_delta,
            boundary="reflect", max_iters=24, backend="pallas",
            interpret=True, block=(32, 128))

    @staticmethod
    def _items(rng, n=5):
        base = [np.asarray(x) for x in mixed_batch(rng, n=n)]
        return [(b, b, (b > 1.0).astype(np.float32)) for b in base]

    @pytest.mark.parametrize("continuous", [False, True])
    def test_tuple_items_match_solo_runs(self, continuous, rng):
        loop = self._mkloop()
        items = self._items(rng)
        eng = FarmEngine(loop, lanes=2, segment=6)
        outs = []
        assert eng.run(items, outs.append, continuous=continuous) == 5
        if continuous:
            outs.sort(key=lambda r: r.index)
        for it, res in zip(items, outs):
            ref = loop.run(jnp.asarray(it[0]),
                           env=(jnp.asarray(it[1]), jnp.asarray(it[2])))
            assert int(res.iters) == int(ref.iters)
            np.testing.assert_allclose(np.asarray(res.a),
                                       np.asarray(ref.a), atol=1e-5)

    @pytest.mark.parametrize("continuous", [False, True])
    def test_env_item_drift_is_guarded(self, continuous, rng):
        """A drifted ENV leaf mid-stream must raise the same loud
        build-a-fresh-FarmEngine error the main leaf gets — not an XLA
        shape error from inside the jitted refill."""
        items = self._items(rng, n=4)
        a2 = items[2]
        bad = items[:2] + [(a2[0], a2[1],
                            np.zeros((8, 8), np.float32))]
        eng = FarmEngine(self._mkloop(), lanes=2, segment=6)
        with pytest.raises(ValueError, match="env stream item.*fresh "
                                             "FarmEngine"):
            eng.run(bad, lambda r: None, continuous=continuous)

    def test_env_item_arity_drift_is_guarded(self, rng):
        items = self._items(rng, n=3)
        bad = items[:2] + [(items[2][0], items[2][1])]   # env leaf lost
        eng = FarmEngine(self._mkloop(), lanes=2, segment=6)
        with pytest.raises(ValueError, match="arity changed"):
            eng.run(bad, lambda r: None, continuous=True)


SRC = os.path.join(os.path.dirname(__file__), "..", "..", "src")


def run_multidevice(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


SHARDED_PRELUDE = """
import os, sys
import numpy as np, jax, jax.numpy as jnp
from repro.core import FarmEngine, GridPartition, LoopOfStencilReduce
from repro.kernels import ref as R
from repro.sharding.specs import make_mesh
rng = np.random.default_rng(0)
items = [np.asarray(rng.normal(size=(64, 64)), np.float32) * s
         for s in (1.0, 5.0, 0.1, 2.0, 3.0, 0.5, 4.0)]

def heat(get, *_):
    lap = get(-1,0)+get(1,0)+get(0,-1)+get(0,1)-4.0*get(0,0)
    return get(0,0)+0.1*lap

def mkloop(backend, part=None, unroll=1):
    return LoopOfStencilReduce(
        f=heat, k=1, combine="max", cond=lambda r: r < 2e-3,
        delta=R.abs_delta, boundary="zero", max_iters=40, unroll=unroll,
        backend=backend, partition=part, interpret=True, block=(16, 128))

refs = [mkloop("jnp").run(jnp.asarray(it)) for it in items]

def check(eng):
    outs = []
    n = eng.run(items, outs.append)
    assert n == len(items), n
    for res, ref in zip(outs, refs):
        assert int(res.iters) == int(ref.iters), (res.iters, ref.iters)
        np.testing.assert_allclose(np.asarray(res.a), np.asarray(ref.a),
                                   atol=1e-5)
"""


@pytest.mark.slow
class TestFarmEngineSharded:
    """The 1:1×1:n compositions, in an 8-virtual-device subprocess."""

    def test_lanes_over_data_axis(self):
        out = run_multidevice(SHARDED_PRELUDE + """
mesh = make_mesh((4,), ("data",))
check(FarmEngine(mkloop("pallas"), lanes=4, mesh=mesh))
check(FarmEngine(mkloop("jnp"), lanes=4, mesh=mesh))
print("OKLANES")
""")
        assert "OKLANES" in out

    def test_continuous_lanes_over_data_axis(self):
        """Continuous refill with lanes spread over the mesh: each lane
        shard runs its own segments (no collectives cross the lane
        axis); parity vs the solo runs, every item exactly once."""
        out = run_multidevice(SHARDED_PRELUDE + """
mesh = make_mesh((4,), ("data",))
for backend in ("pallas", "jnp"):
    eng = FarmEngine(mkloop(backend), lanes=4, mesh=mesh, segment=6)
    outs = []
    n = eng.run(items, outs.append, continuous=True)
    assert n == len(items), n
    assert sorted(r.index for r in outs) == list(range(len(items)))
    outs.sort(key=lambda r: r.index)
    for res, ref in zip(outs, refs):
        assert int(res.iters) == int(ref.iters), (res.index, res.iters)
        np.testing.assert_allclose(res.a, np.asarray(ref.a), atol=1e-5)
    assert eng.stats["segment_traces"] == 1
    assert eng.stats["refill_traces"] == 0   # seated through the ring
print("OKCONT")
""")
        assert "OKCONT" in out

    def test_composed_prep_is_halo_aware(self):
        """The lifted composed-mode prep: a stencil-shaped prep (reads
        neighbours across what will become shard boundaries) runs on the
        WHOLE item before the spatial split, so its results match the
        single-device reference exactly."""
        out = run_multidevice(SHARDED_PRELUDE + """
mesh = make_mesh((2, 4), ("data", "model"))
part = GridPartition(mesh=mesh, axis_names=("model",), array_axes=(0,))

def prep(item):
    blur = (jnp.roll(item, 1, 0) + jnp.roll(item, -1, 0)
            + jnp.roll(item, 1, 1) + jnp.roll(item, -1, 1) + item) / 5.0
    return blur, (jnp.abs(item) > 1.0,)

def restore(get, mask):
    lap = get(-1,0)+get(1,0)+get(0,-1)+get(0,1)-4.0*get(0,0)
    return get(0,0) + 0.1*lap

def mkrestore(backend, part=None):
    return LoopOfStencilReduce(
        f=restore, k=1, combine="max", cond=lambda r: r < 2e-3,
        delta=R.abs_delta, boundary="zero", max_iters=40,
        backend=backend, partition=part, interpret=True, block=(16, 128))

eng = FarmEngine(mkrestore("pallas-sharded", part), lanes=4, mesh=mesh,
                 prep=prep)
outs = []
n = eng.run(items, outs.append)
assert n == len(items), n
jref = mkrestore("jnp")
for it, res in zip(items, outs):
    a0, envs = prep(jnp.asarray(it))
    ref = jref.run(a0, env=envs)
    assert int(res.iters) == int(ref.iters), (res.iters, ref.iters)
    np.testing.assert_allclose(np.asarray(res.a), np.asarray(ref.a),
                               atol=1e-5)
print("OKPREP")
""")
        assert "OKPREP" in out

    def test_composed_lanes_times_spatial(self):
        """Lanes over 'data' x each lane's frame ppermute-decomposed
        over 'model' — the full two-tier composition, unroll 1 and
        auto."""
        out = run_multidevice(SHARDED_PRELUDE + """
from repro.core.executor import auto_unroll
mesh = make_mesh((2, 4), ("data", "model"))
part = GridPartition(mesh=mesh, axis_names=("model",), array_axes=(0,))
check(FarmEngine(mkloop("pallas-sharded", part), lanes=4, mesh=mesh))
# unroll='auto' checks the condition every T sweeps: parity against the
# jnp path at the SAME resolved T (iters overshoot by < T vs unroll=1)
T = auto_unroll(64, 64, k=1, block=(16, 128), part=part)
assert T > 1, T
refs = [mkloop("jnp", unroll=T).run(jnp.asarray(it)) for it in items]
check(FarmEngine(mkloop("pallas-sharded", part, unroll="auto"),
                 lanes=4, mesh=mesh))
print("OKCOMPOSED")
""")
        assert "OKCOMPOSED" in out

    @pytest.mark.parametrize("continuous", [False, True])
    def test_default_mesh_accepted(self, continuous, rng):
        """``jax.make_mesh``'s default mesh (Explicit axes) is normalised
        to Auto by FarmEngine and GridPartition, so lanes over it stream
        and match the solo runs."""
        from jax.sharding import AxisType
        from repro.core import GridPartition
        mesh = jax.make_mesh((1,), ("data",))
        assert AxisType.Explicit in mesh.axis_types
        part = GridPartition(mesh=mesh, axis_names=("data",),
                             array_axes=(0,))
        assert all(t == AxisType.Auto for t in part.mesh.axis_types)
        loop = mkloop("pallas")
        eng = FarmEngine(loop, lanes=2, mesh=mesh, segment=6)
        assert all(t == AxisType.Auto for t in eng.mesh.axis_types)
        items = list(np.asarray(mixed_batch(rng, n=3)))
        outs = []
        assert eng.run(items, outs.append, continuous=continuous) == 3
        outs.sort(key=lambda r: getattr(r, "index", 0))
        for it, res in zip(items, outs):
            ref = loop.run(jnp.asarray(it))
            assert int(res.iters) == int(ref.iters)
            np.testing.assert_allclose(np.asarray(res.a),
                                       np.asarray(ref.a), atol=1e-5)

    def test_validation(self):
        from repro.core import GridPartition
        from repro.sharding.specs import make_mesh
        mesh = make_mesh((1,), ("data",))
        part = GridPartition(mesh=mesh, axis_names=("data",),
                             array_axes=(0,))
        loop = LoopOfStencilReduce(
            f=heat, cond=lambda r: True, backend="pallas-sharded",
            partition=part)
        with pytest.raises(ValueError, match="mesh="):
            FarmEngine(loop, lanes=2)
        with pytest.raises(ValueError, match="collides"):
            FarmEngine(loop, lanes=1, mesh=mesh, lane_axis="data")
        from types import SimpleNamespace
        fake2 = SimpleNamespace(axis_names=("data",), shape={"data": 2})
        with pytest.raises(ValueError, match="divide"):
            FarmEngine(mkloop("pallas"), lanes=3, mesh=fake2)


COMPOSED_PRELUDE = """
import os, sys
import numpy as np, jax, jax.numpy as jnp
from repro.core import FarmEngine, GridPartition, LoopOfStencilReduce
from repro.sharding.specs import make_mesh

def countdown(get, *_):
    return get(0, 0) - 1.0

def mk(part, max_iters=256):
    return LoopOfStencilReduce(
        f=countdown, k=1, combine="max", cond=lambda r: r < 0.5,
        boundary="zero", max_iters=max_iters, backend="pallas-sharded",
        partition=part, interpret=True, block=(16, 128))

def trip_items(trips, shape=(32, 64)):
    base = np.linspace(0.1, 0.9, shape[0] * shape[1],
                       dtype=np.float32).reshape(shape)
    return [base + float(t) - 1.0 for t in trips]

mesh = make_mesh((2, 4), ("data", "model"))
part = GridPartition(mesh=mesh, axis_names=("model",), array_axes=(0,))
"""


@pytest.mark.slow
class TestComposedContinuous:
    """The tentpole acceptance on the composed (lanes × spatial)
    deployment, in an 8-virtual-device subprocess: continuous refill
    matches round mode item for item on adversarial trip-count spreads,
    strictly cuts the barrier's wasted lane sweeps on non-uniform
    spreads, compiles once per entry point, and is structurally clean
    (no pad, owner-masked interior-sized refill writes, collectives
    along the SPATIAL axes only — nothing crosses the lane axis)."""

    def test_parity_matrix_and_waste_drop(self):
        out = run_multidevice(COMPOSED_PRELUDE + """
SPREADS = {
    "uniform": [6] * 8,
    "bimodal": [1, 200, 1, 1, 200, 1, 1, 1, 1, 1, 1, 1],
    "straggler": [2, 2, 2, 200, 2, 2, 2, 2],
}
for name, trips in SPREADS.items():
    items = trip_items(trips)
    eng_r = FarmEngine(mk(part), lanes=4, mesh=mesh)
    r_outs = []
    assert eng_r.run(items, r_outs.append) == len(trips)
    eng_c = FarmEngine(mk(part), lanes=4, mesh=mesh, segment=8)
    c_outs = []
    assert eng_c.run(items, c_outs.append, continuous=True) == len(trips)
    assert sorted(r.index for r in c_outs) == list(range(len(trips)))
    c_outs.sort(key=lambda r: r.index)
    for i, (ro, co) in enumerate(zip(r_outs, c_outs)):
        assert int(ro.iters) == int(co.iters) == trips[i], (
            name, i, ro.iters, co.iters)
        np.testing.assert_array_equal(np.asarray(ro.a), co.a)
    assert eng_c.stats["segment_traces"] == 1
    assert eng_c.stats["refill_traces"] == 1
    if name != "uniform":
        assert eng_c.wasted_lane_steps < eng_r.wasted_lane_steps, (
            name, eng_c.wasted_lane_steps, eng_r.wasted_lane_steps)
print("OKMATRIX")
""")
        assert "OKMATRIX" in out

    def test_one_compilation_and_completion_order(self):
        """A straggler sharing the pool with 1-sweep items must NOT gate
        their emission, and a second stream through the same engine must
        not retrace."""
        out = run_multidevice(COMPOSED_PRELUDE + """
eng = FarmEngine(mk(part), lanes=4, mesh=mesh, segment=4)
order = []
n = eng.run(trip_items([200, 1, 1, 1, 1, 1]),
            lambda r: order.append(r.index), continuous=True)
assert n == 6, n
assert order[-1] == 0, order       # the straggler emits LAST
assert eng.stats["segment_traces"] == 1
assert eng.stats["refill_traces"] == 1
eng.run(trip_items([2, 3]), lambda r: None, continuous=True)
assert eng.stats["segment_traces"] == 1    # no retrace across streams
assert eng.stats["refill_traces"] == 1
print("OKORDER")
""")
        assert "OKORDER" in out

    def test_steady_state_jaxpr_is_pad_free_and_lane_local(self):
        out = run_multidevice(COMPOSED_PRELUDE + """
from repro.core.introspect import flatten_eqns
eng = FarmEngine(mk(part), lanes=4, mesh=mesh, segment=4)
eng.run(trip_items([3, 5, 4, 2, 6]), lambda r: None, continuous=True)
r, it, done, hw = eng._cont_carry

def collective_axes(eqns):
    axes = set()
    for e in eqns:
        if e.primitive.name in ("ppermute", "psum", "pmax", "pmin",
                                "all_gather", "all_to_all",
                                "reduce_scatter"):
            ax = e.params.get("axis_name", e.params.get("axes", ()))
            if not isinstance(ax, (tuple, list)):
                ax = (ax,)
            axes.update(a for a in ax if isinstance(a, str))
    return axes

# the steady-state SEGMENT: no pad, ghost exchange along the spatial
# axis only, nothing along the lane axis
jaxpr = jax.make_jaxpr(eng._segment_entry)(
    eng._frames, eng._env_frames, r, it, done, hw)
seg = flatten_eqns(jaxpr.jaxpr, [])
names = [e.primitive.name for e in seg]
assert "pad" not in names, "re-framing pad in the composed segment"
axes = collective_axes(seg)
assert "model" in axes, axes
assert "data" not in axes, ("cross-lane collective in segment", axes)

# the per-slot REFILL: no pad, owner-masked writes at most one LOCAL
# interior each, and again no lane-axis collective
item = jnp.asarray(trip_items([3])[0])
jaxpr = jax.make_jaxpr(eng._refill_impl)(
    eng._frames, eng._env_frames, r, it, done, hw,
    jnp.asarray(0, jnp.int32), item)
ref = flatten_eqns(jaxpr.jaxpr, [])
names = [e.primitive.name for e in ref]
assert "pad" not in names, "re-framing pad in the composed refill"
axes = collective_axes(ref)
assert "data" not in axes, ("cross-lane collective in refill", axes)
spec = eng._lspec.local
interior = spec.m * spec.n
for e in ref:
    if e.primitive.name == "dynamic_update_slice":
        upd = e.invars[1].aval
        assert int(np.prod(upd.shape)) <= interior, upd.shape
print("OKJAXPR")
""")
        assert "OKJAXPR" in out

    def test_continuous_prep_and_env_refill(self):
        """Halo-aware prep + per-item env slots ride the composed
        continuous refill: every item must match its solo run with ITS
        OWN env (a slot keeping the previous occupant's env — or a
        non-owner shard clobbering a live slot — would diverge)."""
        out = run_multidevice(SHARDED_PRELUDE + """
mesh = make_mesh((2, 4), ("data", "model"))
part = GridPartition(mesh=mesh, axis_names=("model",), array_axes=(0,))

def prep(item):
    blur = (jnp.roll(item, 1, 0) + jnp.roll(item, -1, 0)
            + jnp.roll(item, 1, 1) + jnp.roll(item, -1, 1) + item) / 5.0
    return blur, (jnp.abs(item) > 1.0,)

def restore(get, mask):
    lap = get(-1,0)+get(1,0)+get(0,-1)+get(0,1)-4.0*get(0,0)
    return get(0,0) + 0.1*lap

def mkrestore(backend, part=None):
    return LoopOfStencilReduce(
        f=restore, k=1, combine="max", cond=lambda r: r < 2e-3,
        delta=R.abs_delta, boundary="zero", max_iters=40,
        backend=backend, partition=part, interpret=True, block=(16, 128))

eng = FarmEngine(mkrestore("pallas-sharded", part), lanes=4, mesh=mesh,
                 prep=prep, segment=6)
outs = []
n = eng.run(items, outs.append, continuous=True)
assert n == len(items), n
outs.sort(key=lambda r: r.index)
jref = mkrestore("jnp")
for it, res in zip(items, outs):
    a0, envs = prep(jnp.asarray(it))
    ref = jref.run(a0, env=envs)
    assert int(res.iters) == int(ref.iters), (res.iters, ref.iters)
    np.testing.assert_allclose(res.a, np.asarray(ref.a), atol=1e-5)
print("OKPREPCONT")
""")
        assert "OKPREPCONT" in out


class TestAutoUnroll:
    def test_respects_local_feasibility_ceiling(self):
        class FakeMesh:
            shape = {"data": 8}

        class FakePart:
            mesh = FakeMesh()
            axis_names = ("data",)
            array_axes = (0,)
            shards = (8,)

        # 8 shards of a 64-row grid: local m = 8, so k·T < 8
        T = auto_unroll(64, 64, k=1, part=FakePart())
        assert 1 <= T < 8
        # single device, roomy grid: deeper blocking is allowed
        assert auto_unroll(512, 512, k=1) >= T

    def test_infeasible_explicit_T_raises_with_context(self):
        class FakeMesh:
            shape = {"data": 8}

        class FakePart:
            mesh = FakeMesh()
            axis_names = ("data",)
            array_axes = (0,)
            shards = (8,)

        with pytest.raises(ValueError, match="T <= 7"):
            check_unroll_feasible(64, 64, 8, k=1, part=FakePart())
        check_unroll_feasible(64, 64, 4, k=1, part=FakePart())  # fine

    def test_auto_resolves_on_run(self, rng):
        loop = mkloop("pallas-multistep", unroll="auto", max_iters=12)
        a = jnp.asarray(rng.normal(size=(40, 136)), jnp.float32)
        res = loop.run(a)
        T = auto_unroll(40, 136, k=1, block=(32, 128))
        assert T > 1
        ref = mkloop("jnp", unroll=T, max_iters=12).run(a)
        assert int(res.iters) == int(ref.iters)
        np.testing.assert_allclose(np.asarray(res.a), np.asarray(ref.a),
                                   atol=1e-4)

    def test_bad_unroll_rejected(self):
        with pytest.raises(ValueError, match="unroll"):
            mkloop("pallas", unroll=0)
        with pytest.raises(ValueError, match="unroll"):
            mkloop("pallas", unroll="deep")
