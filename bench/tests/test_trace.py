"""The trace reduction, on a recorded chip trace and on hand-made ones."""
import os

import pytest

from bench import trace as T

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "solve_1024.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    """Two 8-sweep 1024² pallas solves with a 20 ms host sleep between
    them, on one v5e chip (``record_trace.py``)."""
    return T.load(RECORDED)


def test_recorded_planes(recorded):
    assert list(recorded.devices) == ["/device:TPU:0"]
    names = {s[0] for s in recorded.spans}
    assert {"bench.window", "bench.solve", "bench.host"} <= names
    assert recorded.window_s > 0.02


def test_recorded_kernel_calls(recorded):
    calls, seconds = T.kernel_time(recorded, "stencil2d_fused_framed")
    assert calls == 16                   # 2 solves x 8 sweeps
    assert 0 < seconds < T.busy_s(recorded)
    assert T.kernel_time(recorded, "stencil2d_multistep_framed") == (0, 0)


def test_recorded_busy_union(recorded):
    evs = recorded.devices["/device:TPU:0"]
    merged = T.union((s, e) for _, s, e in evs)
    # by hand: disjoint, sorted, and no longer than the window
    assert all(a[1] < b[0] for a, b in zip(merged, merged[1:]))
    busy = T.busy_s(recorded)
    assert busy == pytest.approx(sum(e - s for s, e in merged) * 1e-9)
    assert 0 < busy < recorded.window_s - 0.02


def test_recorded_idle_gap_named(recorded):
    gaps = T.idle_gaps(recorded)
    assert gaps[0][0] == "bench.host"
    assert 0.02 <= gaps[0][1] < 0.05
    assert all(a[1] >= b[1] for a, b in zip(gaps, gaps[1:]))


def test_recorded_no_collectives(recorded):
    assert T.exposed_collective_s(recorded) == 0.0
    top = dict(T.top_ops(recorded))
    assert "stencil2d_fused_framed" in top


def _trace(events, spans=(("bench.window", 0, 100),)):
    return T.from_events({"/device:TPU:0": list(events)}, list(spans))


def test_leaves_drop_enclosing_ops():
    tr = _trace([("while.1", 10, 90), ("fusion.2", 10, 30),
                 ("stencil2d_fused_framed.3", 30, 80)])
    assert [n for n, _, _ in tr.devices["/device:TPU:0"]] == [
        "fusion.2", "stencil2d_fused_framed.3"]
    assert T.busy_s(tr) == pytest.approx(70e-9)
    assert T.kernel_time(tr, "stencil2d_fused_framed") == \
        (1, pytest.approx(50e-9))
    assert T.busy_outside_s(tr, ["stencil2d_fused_framed"]) == \
        pytest.approx(20e-9)


def test_busy_union_clips_to_window():
    tr = _trace([("a.1", -20, 10), ("b.2", 5, 20), ("c.3", 50, 60),
                 ("d.4", 95, 130)])
    # [0,20] + [50,60] + [95,100] by hand
    assert T.busy_s(tr) == pytest.approx(35e-9)


def test_exposed_collectives_by_hand():
    """An async permute in flight over [10,40] beside a fusion [20,30];
    its done op [40,45] waits alone; a synchronous all-reduce [60,70]."""
    tr = T.from_events(
        {"/device:TPU:0": [("fusion.2", 20, 30),
                           ("collective-permute-done.1", 40, 45),
                           ("all-reduce.3", 60, 70),
                           ("stencil2d_fused_framed.4", 70, 90)]},
        [("bench.window", 0, 100)],
        async_ops={"/device:TPU:0": [
            ("collective-permute-start.1", 10, 40),
            ("copy-start.5", 0, 90)]})
    # [10,45] minus [20,30] = 25, plus [60,70] = 10; the copy is no
    # collective
    assert T.exposed_collective_s(tr) == pytest.approx(35e-9)
    assert T.busy_s(tr) == pytest.approx(45e-9)


def test_exposed_collectives_mean_over_devices():
    tr = T.from_events(
        {"/device:TPU:0": [("collective-permute.1", 0, 40)],
         "/device:TPU:1": [("collective-permute.1", 0, 40),
                           ("fusion.1", 0, 40)]},
        [("bench.window", 0, 100)])
    assert T.exposed_collective_s(tr) == pytest.approx(20e-9)
    assert T.busy_s(tr) == pytest.approx(40e-9)


def test_idle_gaps_named_by_innermost_span():
    spans = [("bench.window", 0, 100), ("bench.stream", 0, 100),
             ("bench.sink", 42, 58), ("bench.source", 71, 73)]
    tr = _trace([("a.1", 0, 40), ("b.2", 60, 70), ("c.3", 80, 100)], spans)
    assert T.idle_gaps(tr) == [["bench.sink", pytest.approx(20e-9)],
                               ["bench.stream", pytest.approx(10e-9)]]


def test_idle_gaps_named_by_program_span_first():
    """A hole under a program span takes its name, whatever benchmark
    span is shorter; a hole under none takes the benchmark's."""
    spans = [("bench.window", 0, 100), ("bench.stream", 0, 100),
             ("repro.farm.dispatch", 38, 45), ("repro.farm.drain", 40, 62),
             ("bench.sink", 42, 58), ("repro.farm.stage", 70, 72)]
    tr = _trace([("a.1", 0, 40), ("b.2", 60, 70), ("c.3", 80, 100)], spans)
    # [40,60] lies in the drain, not (mostly) in the dispatch; [70,80]
    # is 20% under the stage span, so it falls back to the benchmark's
    assert T.idle_gaps(tr) == [["repro.farm.drain", pytest.approx(20e-9)],
                               ["bench.stream", pytest.approx(10e-9)]]


def test_program_spans_and_modules_from_a_profile():
    """The reader keeps ``repro.*`` host spans beside ``bench.*`` ones,
    and each device's executable runs by executable name."""
    from jax.profiler import ProfileData

    text = """
    planes { id: 1 name: "/device:TPU:0"
      lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
        events { metadata_id: 1 offset_ps: 1000 duration_ps: 2000 } }
      lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
        events { metadata_id: 2 offset_ps: 0 duration_ps: 4000 } }
      event_metadata { key: 1 value { id: 1 name: "%sort.1 = f32[8] sort()" } }
      event_metadata { key: 2 value { id: 2 name: "jit__stage_impl(42)" } } }
    planes { id: 3 name: "/host:CPU"
      lines { id: 7 name: "python" timestamp_ns: 1000
        events { metadata_id: 1 offset_ps: 0 duration_ps: 10000 }
        events { metadata_id: 2 offset_ps: 3000 duration_ps: 5000 }
        events { metadata_id: 3 offset_ps: 3000 duration_ps: 1000 } }
      event_metadata { key: 1 value { id: 1 name: "bench.window" } }
      event_metadata { key: 2 value { id: 2 name: "repro.farm.drain" } }
      event_metadata { key: 3 value { id: 3 name: "other" } } }
    """
    tr = T.from_profile(ProfileData.from_text_proto(text))
    assert [s[0] for s in tr.spans] == ["bench.window", "repro.farm.drain"]
    assert tr.modules == {"/device:TPU:0": [("jit__stage_impl", 1000,
                                             1004)]}
    assert T.module_ops_s(tr, "jit__stage_impl") == pytest.approx(2e-9)
    # the hole [1003, 1010] ns lies 5/7 under the drain
    assert T.idle_gaps(tr)[0] == ["repro.farm.drain", pytest.approx(7e-9)]


def test_text_proto_round_trip():
    """The reader of the profiler's own format: planes, the ops line,
    host spans, and a collective that nothing hides."""
    from jax.profiler import ProfileData

    text = """
    planes { id: 1 name: "/device:TPU:0"
      lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
        events { metadata_id: 1 offset_ps: 0 duration_ps: 6000 }
        events { metadata_id: 2 offset_ps: 1000 duration_ps: 2000 }
        events { metadata_id: 3 offset_ps: 4000 duration_ps: 2000 } }
      event_metadata { key: 1 value { id: 1 name: "%while.1 = f32[] while()" } }
      event_metadata { key: 2 value { id: 2
        name: "%collective-permute.3 = f32[8] collective-permute()" } }
      event_metadata { key: 3 value { id: 3 name: "%fusion.4 = f32[8] fusion()" } } }
    planes { id: 2 name: "/device:TPU:0 SparseCore 0" }
    planes { id: 3 name: "/host:CPU"
      lines { id: 7 name: "python" timestamp_ns: 1000
        events { metadata_id: 1 offset_ps: 0 duration_ps: 10000 } }
      event_metadata { key: 1 value { id: 1 name: "bench.window" } } }
    """
    tr = T.from_profile(ProfileData.from_text_proto(text))
    assert list(tr.devices) == ["/device:TPU:0"]
    assert tr.window_s == pytest.approx(10e-9)
    assert T.exposed_collective_s(tr) == pytest.approx(2e-9)
    assert T.busy_s(tr) == pytest.approx(4e-9)


def test_no_device_plane_is_an_error():
    from jax.profiler import ProfileData

    data = ProfileData.from_text_proto('planes { id: 1 name: "/host:CPU" }')
    with pytest.raises(ValueError, match="device"):
        T.from_profile(data)
