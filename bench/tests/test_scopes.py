"""The program's own names in a traced run (``bench/scopes.py``) and the
readers built on them, on hand-made traces and a recorded chip trace."""
import json
import os

import numpy as np
import pytest

from bench import run, scopes
from bench import trace as T
from bench.common import Context

DATA = os.path.join(os.path.dirname(__file__), "data")
SCOPED = os.path.join(DATA, "solve_1024_scoped.xplane.pb")
SCOPED_MAP = os.path.join(DATA, "solve_1024_scoped.scopes.json")
SCOPED_HLO = os.path.join(DATA, "solve_1024_scoped.hlo.txt")
KERNELS = ("stencil2d_fused_framed", "stencil2d_multistep_framed")


def _trace(events, spans=(("bench.window", 0, 100),)):
    return T.from_events({"/device:TPU:0": list(events)}, list(spans))


def _ctx(tr):
    return Context(trace=tr, counters={"iters": [3, 3]}, config={},
                   peaks=None)


# -- scope_s ---------------------------------------------------------------

def test_scope_s_unions_overlapping_ops():
    tr = _trace([("a.1", 10, 30), ("b.2", 20, 40), ("c.3", 50, 60),
                 ("d.4", 55, 70)])
    m = {"a.1": "repro.x", "b.2": "repro.x", "c.3": "repro.y"}
    # [10,40] for x by hand; y is c alone; d has no scope
    assert scopes.scope_s(tr, m, "repro.x") == pytest.approx(30e-9)
    assert scopes.scope_s(tr, m, "repro.y") == pytest.approx(10e-9)
    assert scopes.scope_s(tr, m, "repro.z") == 0.0


def test_scope_s_clips_to_window_and_averages_devices():
    tr = T.from_events(
        {"/device:TPU:0": [("a.1", -20, 10), ("b.2", 90, 130)],
         "/device:TPU:1": [("a.1", 0, 40)]},
        [("bench.window", 0, 100)])
    m = {"a.1": "repro.x", "b.2": "repro.x"}
    # device 0: [0,10] + [90,100] = 20; device 1: 40; mean 30
    assert scopes.scope_s(tr, m, "repro.x") == pytest.approx(30e-9)


def test_scope_s_counts_leaves_not_the_enclosing_while():
    tr = _trace([("while.1", 0, 90), ("fusion.2", 10, 30),
                 ("stencil2d_fused_framed.3", 30, 80)])
    m = {"while.1": "repro.x", "fusion.2": "repro.x"}
    assert scopes.scope_s(tr, m, "repro.x") == pytest.approx(20e-9)


# -- readers -----------------------------------------------------------------

def _read(name, ctx):
    return run.read_metric(name, ctx)


NEW = ("done_mask_share.solve", "ghost_refresh_share.solve",
       "recompiles.solve")


SHARES = ("done_mask_share.solve", "ghost_refresh_share.solve")


@pytest.mark.parametrize("name", SHARES)
def test_readers_none_when_not_traced(name, monkeypatch):
    monkeypatch.setattr(scopes, "solve_op_scopes",
                        lambda: {"a.1": "repro.done_mask"})
    ctx = Context(trace=None, counters={"iters": [3]}, config={},
                  peaks=None)
    assert _read(name, ctx) is None


def test_share_readers_by_hand(monkeypatch):
    tr = _trace([("broadcast_select_fusion.2", 0, 30),
                 ("select_n.4", 25, 35),
                 ("dynamic-update-slice.7", 40, 42),
                 ("stencil2d_fused_framed.1", 50, 90)])
    m = {"broadcast_select_fusion.2": "repro.done_mask",
         "select_n.4": "repro.done_mask",
         "dynamic-update-slice.7": "repro.ghost_refresh"}
    monkeypatch.setattr(scopes, "solve_op_scopes", lambda: m)
    ctx = _ctx(tr)
    assert _read("done_mask_share.solve", ctx) == pytest.approx(35.0)
    assert _read("ghost_refresh_share.solve", ctx) == pytest.approx(2.0)
    assert _read("outside_kernel_share.solve", ctx) == pytest.approx(37.0)


class _Entry:
    """A stand-in for ``spans.Entry``: its calls and their counts."""

    def __init__(self, calls, late):
        self.calls, self.after_first = calls, late

    def op_scopes(self):
        return None


def test_recompiles_reader_sums_the_calls_after_the_first(monkeypatch):
    entry = _Entry(7, {"traces": 1, "compiles": 0, "cache_loads": 1})
    monkeypatch.setattr(scopes, "_solve_entry", lambda: entry)
    assert _read("recompiles.solve", _ctx(_trace([("a.1", 0, 9)]))) == 2
    entry.calls = 1          # only the warm call: nothing to count yet
    assert _read("recompiles.solve", _ctx(_trace([("a.1", 0, 9)]))) is None


def test_recompiles_reader_reports_untraced_runs(monkeypatch):
    entry = _Entry(5, {"traces": 0, "compiles": 0, "cache_loads": 0})
    monkeypatch.setattr(scopes, "_solve_entry", lambda: entry)
    ctx = Context(trace=None, counters={"iters": [3]}, config={},
                  peaks=None)
    assert _read("recompiles.solve", ctx) == 0


@pytest.mark.parametrize("name", NEW)
def test_readers_none_on_a_program_without_spans(monkeypatch, name):
    """The parent of this change: a plain jitted ``jacobi_solve``."""
    import jax

    from repro.kernels import ops

    monkeypatch.setattr(ops, "jacobi_solve", jax.jit(lambda u, f: u))
    ctx = _ctx(_trace([("broadcast_select_fusion.2", 0, 30)]))
    assert _read(name, ctx) is None


def _fresh_solve_entry(monkeypatch):
    """A solve entry of the program's own with no calls yet (the
    process-wide one has seen every other test's solves)."""
    from repro.core import spans
    from repro.kernels import ops

    entry = spans.Entry("solve", ops.jacobi_solve.__wrapped__,
                        static_argnames=ops.jacobi_solve.static)
    monkeypatch.setattr(ops, "jacobi_solve", entry)
    return entry


def test_readers_on_the_program_after_a_window(monkeypatch):
    """The program's solve entry at 64² on CPU: the map names both
    scopes, and a repeat of the warm call's signature is no recompile."""
    import jax.numpy as jnp

    entry = _fresh_solve_entry(monkeypatch)
    u0 = jnp.zeros((64, 64), jnp.float32)
    f = jnp.ones((64, 64), jnp.float32)
    kw = dict(alpha=0.5, dx=1.0, tol=np.float32(1e-4), max_iters=37,
              backend="pallas")
    entry(u0, f, **kw)
    entry(u0, f, **kw)
    assert scopes.solve_recompiles() == 0
    m = scopes.solve_op_scopes()
    assert set(m.values()) == {"repro.done_mask", "repro.ghost_refresh"}


@pytest.mark.parametrize("cell", ["helmholtz-16384.pallas",
                                  "helmholtz-16384.multistep-t4"])
def test_cell_runs_one_executable(monkeypatch, cell):
    """A rehearsal of the cell (warm call, window, probe) calls the
    solve entry with one signature, shardings included, so the readers
    rebuild the executable the window ran, and count no recompile."""
    from bench.tests.conftest import run_small

    entry = _fresh_solve_entry(monkeypatch)
    res = run_small(cell, seed=2**31 + 17, seconds=0.3)
    assert res["correct"], res["checks"]
    assert entry.calls == res["attempted"] + 2 and not entry.mixed
    assert scopes.solve_recompiles() == 0
    m = scopes.solve_op_scopes()
    assert set(m.values()) == {"repro.done_mask", "repro.ghost_refresh"}


# -- the recorded chip trace with the program's names ----------------------

@pytest.fixture(scope="module")
def scoped():
    """Two 8-sweep 1024² pallas solves with a 20 ms host sleep between
    them, on one v5e chip, with the program's spans and scopes
    (``record_scoped_trace.py``), and the executable's scope map."""
    with open(SCOPED_MAP) as fh:
        return T.load(SCOPED), json.load(fh)


def _host_spans(path, prefix):
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == T.HOST_PLANE:
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.end_ns)
                           for e in line.events
                           if e.name.startswith(prefix))
    return sorted(out, key=lambda sp: sp[1])


def test_scoped_trace_has_the_program_spans(scoped):
    program = [sp for sp in _host_spans(SCOPED, "repro.")
               if sp[0] == "repro.solve"]
    bench = [sp for sp in _host_spans(SCOPED, "bench.")
             if sp[0] == "bench.solve"]
    assert len(program) == 2 and len(bench) == 2
    for (_, s0, e0), (_, s1, e1) in zip(program, bench):
        assert s1 <= s0 < e0 <= e1      # the program's span is inside


def test_scoped_map_is_op_scopes_of_the_recorded_executable(scoped):
    """The committed map is what ``spans.op_scopes`` makes of the text of
    the executable that ran in the recorded trace."""
    from repro.core import spans

    _, m = scoped
    with open(SCOPED_HLO) as fh:
        assert spans.op_scopes(fh.read()) == m


def test_scoped_trace_names_the_select_and_the_refresh(scoped):
    tr, m = scoped
    ops = {n for evs in tr.devices.values() for n, _, _ in evs}
    selects = [n for n in ops if T.op_kind(n) == "broadcast_select_fusion"]
    assert selects and all(m.get(n) == "repro.done_mask" for n in selects)
    dus = [n for n in ops if T.op_kind(n) == "dynamic-update-slice"]
    assert dus and all(m.get(n) == "repro.ghost_refresh" for n in dus)
    assert not any(m.get(n) for n in ops
                   if any(k in n for k in KERNELS))


def test_scoped_trace_scopes_inside_the_outside_kernel_time(scoped):
    tr, m = scoped
    done = scopes.scope_s(tr, m, "repro.done_mask")
    ghost = scopes.scope_s(tr, m, "repro.ghost_refresh")
    assert done > 0 and ghost > 0
    assert done + ghost < T.busy_outside_s(tr, KERNELS)


def test_scoped_trace_program_spans_leave_the_benchmark_reduction(scoped):
    """The benchmark's own reduction of the same file keeps its
    ``bench.*`` spans and the program's ``repro.solve``, and its longest
    gap is still the host sleep, which no program span covers."""
    tr, _ = scoped
    assert {s[0] for s in tr.spans} == {"bench.window", "bench.solve",
                                        "bench.host", "repro.solve"}
    assert sorted(s[1:] for s in tr.spans if s[0] == "repro.solve") == \
        [s[1:] for s in _host_spans(SCOPED, "repro.")
         if s[0] == "repro.solve"]
    gaps = T.idle_gaps(tr)
    assert gaps[0][0] == "bench.host"
    assert 0.02 <= gaps[0][1] < 0.05
