"""The ``stream`` driver at its rehearsal size (Pallas in interpret mode):
a whole run, the symmetries of the seed, the control and planted faults,
and the stream's readers on hand-made traces."""
import importlib

import numpy as np
import pytest

from bench import generators, run, scopes
from bench import trace as T
from bench.common import Context
from bench.tests.conftest import run_small, small_spec
from repro.core import executor, streaming

CELL = "restore-720p.noise70"
STREAM_METRICS = ("prep_share.stream", "lane_roofline.stream",
                  "lane_select_share.stream", "lane_waste.stream",
                  "segments_per_frame.stream", "idle_share.stream")


def _cell(seed):
    import jax

    spec = small_spec(CELL)
    driver = importlib.import_module(
        "bench.drivers." + spec["config"]["driver"])
    return driver.Cell(spec["config"], spec["traffic"], seed,
                       jax.devices()[:1])


def test_stream_rehearsal(capsys):
    """A whole run: correct, every end-to-end metric, the per-layer
    metrics that read counters, and the checks printed last."""
    cell = _cell(2**31 + 43)
    win = cell.window(0.5)
    err = capsys.readouterr().err
    assert "compiles in the window: 0" in err
    assert win.attempted == win.counters["frames"] == 8 and not win.failed
    assert win.e2e["frames_per_s"] > 0 and win.e2e["frame_p95_ms"] > 0
    ctx = Context(trace=None, counters=win.counters, config=cell.config,
                  peaks=None)
    read = {m: run.read_metric(m, ctx) for m in STREAM_METRICS}
    print(win.e2e, read)
    assert 0 <= read["lane_waste.stream"] < 100
    assert read["segments_per_frame.stream"] >= 1 / 4
    assert all(read[m] is None for m in STREAM_METRICS
               if m not in ("lane_waste.stream",
                            "segments_per_frame.stream"))
    cell.probe()
    cell.release()
    checks = cell.check()
    assert all(c.ok for c in checks), checks
    assert {c.name for c in checks} == {"restore_err", "iters_gap",
                                        "emission_gap"}

    res = run_small(CELL, seed=2**31 + 44, seconds=0.5)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"frames_per_s", "frame_p95_ms",
                                   "setup_s"}
    assert list(res)[-1] == "checks"


def test_pool_frames_are_row_flips_of_one_draw():
    shape, count = (12, 20), 4
    bases, seen = [], set()
    for seed in range(2**31, 2**31 + 8):
        frames = generators.restoration_pool(seed, shape, count, 7, 0.7)
        flips = generators._row_flips(seed, count)
        seen.add(tuple(flips))
        bases.append([f[::-1] if fl else f for f, fl in zip(frames, flips)])
    assert len(seen) > 1
    assert all(np.array_equal(b[i], bases[0][i])
               for b in bases for i in range(count))
    noisy = np.isin(bases[0][0], (0.0, 1.0)).mean()
    assert 0.6 < noisy < 0.8


def test_symmetric_frames_do_the_same_work():
    """The program's farm takes a frame and its row flip through the
    same sweeps, and the flip's result is the flipped result, bitwise."""
    cell = _cell(2**31 + 45)
    pool = cell.pool
    out = {}
    cell.engine.run(pool + [np.ascontiguousarray(f[::-1]) for f in pool],
                    lambda r: out.__setitem__(r.index, r), continuous=True)
    n = len(pool)
    for i in range(n):
        a, b = out[i], out[n + i]
        assert a.status == b.status == "ok"
        assert int(a.iters) == int(b.iters)
        assert np.array_equal(np.asarray(a.a)[::-1], np.asarray(b.a))
    assert len({int(out[i].iters) for i in range(n)}) > 1


def test_control_is_not_correct():
    res = run_small(CELL, seed=2**31 + 46, seconds=0.5, control=True)
    assert not res["correct"], res["checks"]


def _unchanged_step(monkeypatch):
    """Every lane's sweep hands its input frame back (its reduce still
    computed)."""
    sweeps = executor.StencilEngine.sweeps

    def stuck(self, frame, env_frames, spec):
        return frame, sweeps(self, frame, env_frames, spec)[1]
    monkeypatch.setattr(executor.StencilEngine, "sweeps", stuck)


def _altered_answer(monkeypatch):
    """One cell of every emitted result is changed where the farm slices
    the lanes' domains out of their frames."""
    unframe = streaming.FarmEngine._unframe_all
    monkeypatch.setattr(streaming.FarmEngine, "_unframe_all",
                        lambda self, fr: unframe(self, fr)
                        .at[:, 0, 0].add(0.01))


def _prep_skipped(monkeypatch):
    """The farm's prep hands the raw noisy frame on as the loop's start
    (its read-only inputs still detected)."""
    from bench.drivers import stream

    detect = stream.detect
    monkeypatch.setattr(stream, "detect",
                        lambda frame, kmax: (frame, detect(frame, kmax)[1]))


FAULTS = {"unchanged_step": _unchanged_step,
          "altered_answer": _altered_answer,
          "prep_skipped": _prep_skipped}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(fault, monkeypatch, fresh_jit):
    FAULTS[fault](monkeypatch)
    res = run_small(CELL, seed=2**31 + 47, seconds=0.3)
    assert not res["correct"], (fault, res["checks"])


# -- the stream's readers on hand-made traces -------------------------------

def _trace(events, modules, spans=(("bench.window", 0, 1000),)):
    dev = "/device:TPU:0"
    return T.from_events({dev: list(events)}, list(spans),
                         modules={dev: list(modules)})


CONFIG = {"frame": [720, 1280], "kernel_fields": {"read": 3, "written": 1}}
EVENTS = [("sort.1", 0, 200), ("fusion.2", 200, 250),
          ("stencil2d_fused_framed.3", 300, 500), ("select_n.4", 500, 560),
          ("fusion.2", 560, 600), ("stencil2d_fused_framed.3", 600, 800),
          ("select_n.4", 800, 850)]
MODULES = [("jit__stage_impl(17)", 0, 250),
           ("jit__chain_entry(23)", 290, 600),
           ("jit__chain_entry(23)", 600, 900)]


def _ctx(tr, **counters):
    return Context(trace=tr, counters=counters, config=CONFIG,
                   peaks={"hbm_bytes_per_s": 819e9})


def test_module_ops_by_hand():
    tr = _trace(EVENTS, MODULES)
    # stage: [0,250]; chain: [300,600] and [600,850] of ops
    assert T.module_ops_s(tr, "jit__stage_impl") == pytest.approx(250e-9)
    assert T.module_ops_s(tr, "jit__chain_entry") == pytest.approx(550e-9)
    # fusion.2 runs in both executables: only its chain run counts
    assert T.module_ops_s(tr, "jit__chain_entry",
                          {"fusion.2", "select_n.4"}) == \
        pytest.approx(150e-9)
    assert T.module_ops_s(tr, "jit__refill_impl") == 0.0


def test_stream_trace_readers_by_hand(monkeypatch):
    tr = _trace(EVENTS, MODULES)
    monkeypatch.setattr(scopes, "entry_op_scopes",
                        lambda entry: {"select_n.4": "repro.done_mask",
                                       "fusion.2": "repro.ghost_refresh"})
    ctx = _ctx(tr, lanes=8, chain_entry=object())
    read = {m: run.read_metric(m, ctx) for m in STREAM_METRICS}
    assert read["prep_share.stream"] == pytest.approx(25.0)
    assert read["lane_select_share.stream"] == pytest.approx(11.0)
    assert read["idle_share.stream"] == pytest.approx(100 - 80.0)
    # 8 lanes x 4 fields x 720 x 1280 x 4 B a call, two calls in 400 ns
    least = 2 * 8 * 4 * 720 * 1280 * 4 / 819e9
    assert read["lane_roofline.stream"] == pytest.approx(
        100 * least / 400e-9)
    assert read["lane_waste.stream"] is None
    assert read["segments_per_frame.stream"] is None


def test_stream_readers_find_nothing_to_read():
    tr = _trace([("fusion.1", 0, 10)], [("jit_other(1)", 0, 10)])
    ctx = _ctx(tr)
    for name in ("prep_share.stream", "lane_roofline.stream",
                 "lane_select_share.stream"):
        assert run.read_metric(name, ctx) is None, name
    ctx = Context(trace=None, config=CONFIG, peaks=None,
                  counters={"frames": 10, "segments": 17,
                            "lane_steps": 400, "wasted_lane_steps": 12})
    assert run.read_metric("segments_per_frame.stream", ctx) == 1.7
    assert run.read_metric("lane_waste.stream", ctx) == 3.0
    assert run.read_metric("idle_share.stream", ctx) is None
