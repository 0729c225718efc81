"""Preemption-recovery suite: snapshots, WAL journal, elastic resume.

The contracts under kill (DESIGN.md §Recovery):

  exactly-once     — across any number of kills and restarts, every
                     stream item / request emits exactly one result
                     (the WAL journal suppresses re-emission; replay
                     re-delivers what the dead process already sank)
  bit-identity     — a preempted-and-resumed run's outputs equal an
                     uninterrupted run's, bit for bit, even at
                     temperature > 0 (PRNG keys ride in the snapshot)
  elasticity       — snapshots are logical (unsharded): a run killed at
                     lanes/slots = N resumes at any other N or mesh
  crash-atomicity  — a kill at ANY point leaves a loadable snapshot
                     and a replayable journal (rename-aside publish;
                     CRC-framed, torn-tail-tolerant journal lines)

Kill-at-random-segment subprocess tests use ``os._exit(PREEMPTED_EXIT)``
— no finally blocks, no flushing: the portable stand-in for a spot
reclaim.  The preempt hook is armed ONLY on the first launch (a resumed
process re-counts segments from its own start and would re-kill
forever otherwise).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import FarmEngine, LoopOfStencilReduce
from repro.resilience import (FaultPlan, Journal, PreemptionError,
                              RecoveryConfig, load_snapshot,
                              run_to_completion, save_snapshot)
from repro.resilience.recovery import (fresh_tmp_dir, list_steps,
                                       publish_dir, sweep_strays)

SRC = os.path.join(os.path.dirname(__file__), "..", "..", "src")


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


def collect():
    got = {}

    def sink(r):
        assert r.index not in got, f"duplicate emission for {r.index}"
        got[r.index] = r
    return got, sink


# ---------------------------------------------------------------------------
# atomic publish + checkpoint crash window
# ---------------------------------------------------------------------------


class TestAtomicPublish:
    def test_rename_aside_never_leaves_nothing(self, tmp_path):
        parent = str(tmp_path)
        final = os.path.join(parent, "step_1")
        for gen in ("first", "second"):
            tmp = fresh_tmp_dir(parent, "1")
            with open(os.path.join(tmp, "payload"), "w") as f:
                f.write(gen)
            publish_dir(tmp, final)
            with open(os.path.join(final, "payload")) as f:
                assert f.read() == gen
        assert not [d for d in os.listdir(parent) if d.startswith(".")]

    def test_orphaned_old_is_promoted(self, tmp_path):
        """Crash after rename-aside, before publish: the .old copy is
        the sole survivor and sweep promotes it back to final."""
        parent = str(tmp_path)
        os.makedirs(os.path.join(parent, ".old-step_7"))
        with open(os.path.join(parent, ".old-step_7", "payload"),
                  "w") as f:
            f.write("survivor")
        os.makedirs(os.path.join(parent, ".tmp-9"))
        sweep_strays(parent)
        assert os.path.exists(os.path.join(parent, "step_7", "payload"))
        assert not os.path.exists(os.path.join(parent, ".tmp-9"))
        assert list_steps(parent) == [7]

    def test_checkpoint_same_step_resave_crash_window(self, tmp_path,
                                                      monkeypatch):
        """Re-saving an existing checkpoint step must never pass through
        a state with no copy on disk: crash the publish at the moment
        the new dir would swap in and assert the OLD copy restores."""
        from repro.train import checkpoint

        ckpt = str(tmp_path / "ckpt")
        tree = {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)}
        checkpoint.save(ckpt, 3, tree)

        real_replace = os.replace

        def exploding_replace(src, dst):
            if os.path.basename(src).startswith(".tmp-"):
                raise OSError("simulated crash mid-publish")
            return real_replace(src, dst)

        monkeypatch.setattr(os, "replace", exploding_replace)
        tree2 = {"w": tree["w"] + 100.0}
        with pytest.raises(OSError, match="simulated crash"):
            checkpoint.save(ckpt, 3, tree2)
        monkeypatch.setattr(os, "replace", real_replace)

        # the step dir was renamed aside, not destroyed: restore finds it
        restored, step, _ = checkpoint.restore(ckpt, tree)
        assert step == 3
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(tree["w"]))
        assert checkpoint.latest_step(ckpt) == 3

    def test_checkpoint_tolerates_stray_tmp(self, tmp_path):
        from repro.train import checkpoint

        ckpt = str(tmp_path / "ckpt")
        tree = {"w": jnp.ones((2,), jnp.bfloat16)}
        checkpoint.save(ckpt, 1, tree)
        os.makedirs(os.path.join(ckpt, ".tmp-999"))
        assert checkpoint.latest_step(ckpt) == 1
        restored, _, _ = checkpoint.restore(ckpt, tree)
        assert restored["w"].dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# journal + snapshot units
# ---------------------------------------------------------------------------


class TestJournal:
    def test_round_trip_with_arrays(self, tmp_path):
        path = str(tmp_path / "j.jsonl")
        j = Journal(path, fsync=False)
        a = np.arange(12, dtype=np.float32).reshape(3, 4)
        j.append({"index": 0, "a": a, "status": "ok", "err": None})
        j.append({"index": 1, "a": a.astype(jnp.bfloat16), "nested":
                  {"x": [1, 2.5, True]}})
        j.close()
        recs = list(Journal.replay(path))
        assert len(recs) == 2
        np.testing.assert_array_equal(recs[0]["a"], a)
        assert recs[1]["a"].dtype == jnp.bfloat16
        assert recs[1]["nested"]["x"] == [1, 2.5, True]

    def test_torn_tail_stops_replay(self, tmp_path):
        path = str(tmp_path / "j.jsonl")
        j = Journal(path, fsync=False)
        for i in range(3):
            j.append({"index": i})
        j.close()
        with open(path, "rb") as f:
            data = f.read()
        # crash mid-append: the last line loses its tail
        with open(path, "wb") as f:
            f.write(data[:-7])
        assert [r["index"] for r in Journal.replay(path)] == [0, 1]

    def test_corrupt_crc_stops_replay(self, tmp_path):
        path = str(tmp_path / "j.jsonl")
        j = Journal(path, fsync=False)
        for i in range(3):
            j.append({"index": i})
        j.close()
        lines = open(path, "rb").read().splitlines(keepends=True)
        lines[1] = b"deadbeef" + lines[1][8:]
        open(path, "wb").write(b"".join(lines))
        assert [r["index"] for r in Journal.replay(path)] == [0]

    def test_append_after_replay_extends(self, tmp_path):
        """The resume pattern: replay, then open in append mode — old
        records survive, new ones land after them."""
        path = str(tmp_path / "j.jsonl")
        Journal(path, fsync=False).append({"index": 0})
        assert len(list(Journal.replay(path))) == 1
        j = Journal(path, fsync=False)
        j.append({"index": 1})
        j.close()
        assert [r["index"] for r in Journal.replay(path)] == [0, 1]


class TestSnapshotTree:
    def test_dynamic_structure_round_trip(self, tmp_path):
        snap = str(tmp_path / "snaps")
        tree = {"kind": "farm", "version": 1, "complete": False,
                "occupants": [
                    {"index": 4, "item": np.ones((3, 5), np.float32),
                     "carry": (np.zeros((2,), jnp.bfloat16), 0.5, 7)},
                ],
                "retry": [], "none": None}
        save_snapshot(snap, 11, tree)
        out = load_snapshot(snap)
        assert out["kind"] == "farm" and out["none"] is None
        assert isinstance(out["occupants"][0]["carry"], tuple)
        assert out["occupants"][0]["carry"][0].dtype == jnp.bfloat16
        assert out["occupants"][0]["carry"][1:] == (0.5, 7)
        np.testing.assert_array_equal(out["occupants"][0]["item"],
                                      np.ones((3, 5), np.float32))
        assert out["retry"] == [] and out["complete"] is False

    def test_keep_prunes_and_latest_wins(self, tmp_path):
        snap = str(tmp_path / "snaps")
        for step in (1, 2, 3, 4):
            save_snapshot(snap, step, {"step": step}, keep=2)
        assert list_steps(snap) == [3, 4]
        assert load_snapshot(snap)["step"] == 4
        assert load_snapshot(snap, step=3)["step"] == 3

    def test_empty_dir_is_fresh_run(self, tmp_path):
        assert load_snapshot(str(tmp_path / "nothing")) is None


class TestSeededPreemptPlans:
    def test_seeded_draws_preempt_point(self):
        p1 = FaultPlan.seeded(5, lanes=4, preempt_within=6)
        p2 = FaultPlan.seeded(5, lanes=4, preempt_within=6)
        assert p1 == p2
        assert 1 <= p1.preempt_at_segment <= 6
        assert FaultPlan.seeded(5, lanes=4).preempt_at_segment is None

    def test_preempt_hook_fires_once(self):
        plan = FaultPlan(lanes=2, preempt_at_segment=3)
        hook = plan.preempt_hook(mode="raise")
        hook(1)
        hook(2)
        with pytest.raises(PreemptionError):
            hook(3)
        hook(4)        # already fired: a resumed in-process run survives
        assert FaultPlan(lanes=2).preempt_hook() is None


# ---------------------------------------------------------------------------
# farm: in-process elastic resume (raise-mode preemption)
# ---------------------------------------------------------------------------


def run_reference(items, lanes=4):
    eng = FarmEngine(loop=mk_countdown(), lanes=lanes, segment=2)
    got, sink = collect()
    eng.run(items, sink)
    return got


class TestFarmElasticResume:
    TRIPS = [3, 9, 5, 12, 7, 4, 10, 6]

    def _preempt_then_resume(self, tmp_path, lanes0, lanes1,
                             preempt_at=3):
        items = trip_items(self.TRIPS)
        ref = run_reference(items)
        rec = RecoveryConfig(dir=str(tmp_path), snapshot_every=1,
                             fsync=False)
        plan = FaultPlan(lanes=lanes0, preempt_at_segment=preempt_at)
        eng = FarmEngine(loop=mk_countdown(), lanes=lanes0, segment=2)
        got0, sink0 = collect()
        with pytest.raises(PreemptionError):
            eng.run(items, sink0, recovery=rec,
                    on_segment=plan.preempt_hook(mode="raise"))
        # resumed process: FRESH consumer, different lane count, hook
        # disarmed (first-launch-only arming)
        eng2 = FarmEngine(loop=mk_countdown(), lanes=lanes1, segment=2)
        got, sink = collect()
        n = eng2.run(items, sink, recovery=rec, resume=True)
        assert n == len(items) and sorted(got) == list(range(len(items)))
        for i in range(len(items)):
            assert got[i].status == ref[i].status == "ok"
            np.testing.assert_array_equal(got[i].a, ref[i].a)
            assert got[i].iters == ref[i].iters
            assert got[i].reduced == ref[i].reduced
        assert eng2.stats["replayed_items"] == len(got0)
        return eng2

    def test_resume_fewer_lanes(self, tmp_path):
        eng2 = self._preempt_then_resume(tmp_path, lanes0=4, lanes1=2)
        assert eng2.stats["recovered_occupants"] > 0
        assert eng2.stats["recovery_seconds"] > 0

    def test_resume_more_lanes(self, tmp_path):
        self._preempt_then_resume(tmp_path, lanes0=2, lanes1=4)

    def test_second_resume_replays_complete_state(self, tmp_path):
        self._preempt_then_resume(tmp_path, lanes0=4, lanes1=2)
        items = trip_items(self.TRIPS)
        rec = RecoveryConfig(dir=str(tmp_path), snapshot_every=1,
                             fsync=False)
        eng3 = FarmEngine(loop=mk_countdown(), lanes=3, segment=2)
        got, sink = collect()
        n = eng3.run(items, sink, recovery=rec,
                     resume=True)
        assert n == len(items)
        assert eng3.stats["replayed_items"] == len(items)
        assert eng3.stats["segments"] > 0     # restored counter, no work
        ref = run_reference(items)
        for i in range(len(items)):
            np.testing.assert_array_equal(got[i].a, ref[i].a)

    def test_pallas_backend_resume(self, tmp_path):
        items = trip_items([3, 8, 5, 11], shape=(8, 128))
        ref_eng = FarmEngine(loop=mk_countdown(backend="pallas"),
                             lanes=2, segment=2)
        ref, ref_sink = collect()
        ref_eng.run(items, ref_sink)
        rec = RecoveryConfig(dir=str(tmp_path), snapshot_every=1,
                             fsync=False)
        plan = FaultPlan(lanes=2, preempt_at_segment=2)
        eng = FarmEngine(loop=mk_countdown(backend="pallas"), lanes=2,
                         segment=2)
        with pytest.raises(PreemptionError):
            eng.run(items, collect()[1], recovery=rec,
                    on_segment=plan.preempt_hook(mode="raise"))
        eng2 = FarmEngine(loop=mk_countdown(backend="pallas"), lanes=3,
                         segment=2)
        got, sink = collect()
        n = eng2.run(items, sink, recovery=rec, resume=True)
        assert n == 4
        for i in range(4):
            np.testing.assert_array_equal(got[i].a, ref[i].a)

    def test_sink_exception_degrades_not_kills(self, tmp_path):
        """Satellite contract: a raising sink mid-stream degrades that
        ONE result to a failed StreamResult on dead_letter — the other
        in-flight items still emit ok."""
        items = trip_items([3, 6, 4, 8, 5, 7])
        eng = FarmEngine(loop=mk_countdown(), lanes=2, segment=2)
        got = {}

        def sink(r):
            if r.index == 1:
                raise IOError("disk full")
            got[r.index] = r
        n = eng.run(items, sink)
        assert n == 6
        assert eng.stats["sink_errors"] == 1
        assert sorted(got) == [0, 2, 3, 4, 5]
        assert all(r.status == "ok" for r in got.values())
        dead = {r.index: r for r in eng.dead_letter}
        assert dead[1].status == "failed"
        assert "disk full" in dead[1].error


# ---------------------------------------------------------------------------
# serve twin: in-process resume (raise-mode preemption)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    from repro.configs import get_reduced
    from repro.models import transformer as T

    cfg = get_reduced("qwen3-1.7b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def serve_collect():
    got = {}

    def sink(rid, toks, status):
        assert rid not in got, f"duplicate emission for {rid}"
        got[rid] = (np.asarray(toks).copy(), status)
    return got, sink


class TestServeResume:
    def _requests(self, cfg, n=7):
        from repro.serve.batcher import Request

        rng = np.random.default_rng(0)
        return [Request(rid=i, prompt=np.asarray(
            rng.integers(2, cfg.vocab_size, 4 + (i % 3)), np.int32),
            max_new_tokens=4 + 2 * (i % 4)) for i in range(n)]

    def test_mid_generation_resume_elastic_sampled(self, served,
                                                   tmp_path):
        """Kill mid-decode at temperature > 0, resume on a SMALLER slot
        pool with an empty submitted queue: every request emits exactly
        once, token-identical to an uninterrupted run — the per-slot
        PRNG keys and the admission-key cursor both ride the snapshot."""
        from repro.serve import GenerateConfig
        from repro.serve.engine import ContinuousEngine

        cfg, params = served
        gcfg = GenerateConfig(max_new_tokens=10, eos_id=cfg.vocab_size,
                              temperature=0.7, seed=3)
        reqs = self._requests(cfg)
        ref_eng = ContinuousEngine(cfg, params, gcfg, slots=3,
                                   cache_dtype=jnp.float32, segment=2)
        ref, ref_sink = serve_collect()
        assert ref_eng.run(list(reqs), ref_sink) == 7

        rec = RecoveryConfig(dir=str(tmp_path), snapshot_every=1,
                             fsync=False)
        plan = FaultPlan(lanes=3, preempt_at_segment=3)
        eng = ContinuousEngine(cfg, params, gcfg, slots=3,
                               cache_dtype=jnp.float32, segment=2)
        got0, sink0 = serve_collect()
        with pytest.raises(PreemptionError):
            eng.run(list(reqs), sink0, recovery=rec,
                    on_segment=plan.preempt_hook(mode="raise"))

        eng2 = ContinuousEngine(cfg, params, gcfg, slots=2,
                                cache_dtype=jnp.float32, segment=2)
        got, sink = serve_collect()
        n = eng2.run([], sink, recovery=rec, resume=True)
        assert n == 7 and sorted(got) == list(range(7))
        assert eng2.stats["replayed_items"] == len(got0)
        assert eng2.stats["recovered_occupants"] > 0
        assert eng2.stats["recovery_seconds"] > 0
        for rid in range(7):
            assert got[rid][1] == ref[rid][1] == "ok"
            np.testing.assert_array_equal(got[rid][0], ref[rid][0])

    def test_deadline_reanchors_to_resumed_clock(self, served, tmp_path):
        """A deadline is stored as REMAINING time: a request with lots
        of slack survives a restart whose clock starts from zero, and
        one with no slack times out in the resumed process."""
        from repro.serve import GenerateConfig
        from repro.serve.batcher import Request
        from repro.serve.engine import ContinuousEngine

        cfg, params = served
        gcfg = GenerateConfig(max_new_tokens=8, eos_id=cfg.vocab_size,
                              temperature=0.0)
        rng = np.random.default_rng(2)
        prompt = np.asarray(rng.integers(2, cfg.vocab_size, 5), np.int32)
        # clock ticks once per read; deadline 1000 ticks out = never hit
        reqs = [Request(rid=0, prompt=prompt, deadline=1000.0),
                Request(rid=1, prompt=prompt),
                Request(rid=2, prompt=prompt)]

        def ticking(start=0.0):
            ticks = [start]

            def clock():
                ticks[0] += 1.0
                return ticks[0]
            return clock

        rec = RecoveryConfig(dir=str(tmp_path), snapshot_every=1,
                             fsync=False)
        plan = FaultPlan(lanes=2, preempt_at_segment=2)
        eng = ContinuousEngine(cfg, params, gcfg, slots=2,
                               cache_dtype=jnp.float32, segment=2)
        with pytest.raises(PreemptionError):
            eng.run(reqs, serve_collect()[1], recovery=rec,
                    clock=ticking(),
                    on_segment=plan.preempt_hook(mode="raise"))
        snap = load_snapshot(rec.snap_dir)
        occ = {e["rid"]: e for e in snap["occupants"]}
        assert occ[0]["deadline_remaining"] is not None
        assert occ[0]["deadline_remaining"] < 1000.0
        assert occ[1]["deadline_remaining"] is None

        # resumed process: its clock restarts near zero — the stored
        # remaining slack re-anchors, so rid 0 still finishes ok
        eng2 = ContinuousEngine(cfg, params, gcfg, slots=2,
                                cache_dtype=jnp.float32, segment=2)
        got, sink = serve_collect()
        n = eng2.run([], sink, recovery=rec, resume=True,
                     clock=ticking())
        assert n >= 3 and sorted(got) == [0, 1, 2]
        assert got[0][1] == "ok"
        assert got[1][1] == "ok" and got[2][1] == "ok"


# ---------------------------------------------------------------------------
# kill-at-random-segment chaos (subprocess, os._exit — the real thing)
# ---------------------------------------------------------------------------

_FARM_CHILD = """
import json, os, sys
import numpy as np
sys.path.insert(0, {src!r})
from repro.core import FarmEngine, LoopOfStencilReduce
from repro.resilience import FaultPlan, RecoveryConfig

def countdown(get, *_):
    return get(0, 0) - 1.0

loop = LoopOfStencilReduce(
    f=countdown, k=1, combine="max", cond=lambda r: r < 0.5,
    boundary="zero", max_iters=64, backend="jnp", interpret=True)
base = np.linspace(0.1, 0.9, 8 * 128, dtype=np.float32).reshape(8, 128)
items = [base + float(t) - 1.0 for t in {trips}]
rec = RecoveryConfig(dir={recdir!r}, snapshot_every=1)
resume = os.path.exists(rec.journal_path) or \
    os.path.isdir(rec.snap_dir)
# the seeded kill arms ONLY on first launch — a resumed process counts
# segments from its own start and would re-kill forever
hook = None if resume else FaultPlan.seeded(
    {seed}, lanes={lanes}, n_nan=0, n_stall=0,
    preempt_within={within}).preempt_hook()
eng = FarmEngine(loop=loop, lanes={lanes}, segment=2)
out = open({outpath!r}, "a")
def sink(r):
    out.write(json.dumps({{"index": int(r.index), "status": r.status,
                           "iters": int(r.iters),
                           "reduced": float(r.reduced),
                           "sum": float(np.asarray(r.a).sum()),
                           "a00": float(np.asarray(r.a)[0, 0])}}) + "\\n")
    out.flush()
n = eng.run(items, sink, recovery=rec, resume=resume,
            on_segment=hook)
out.close()
"""

_SERVE_CHILD = """
import json, os, sys
import numpy as np
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
from repro.configs import get_reduced
from repro.models import transformer as T
from repro.serve import GenerateConfig
from repro.serve.batcher import Batcher, Request
from repro.resilience import FaultPlan, RecoveryConfig

cfg = get_reduced("qwen3-1.7b")
params = T.init_params(cfg, jax.random.PRNGKey(0))
gcfg = GenerateConfig(max_new_tokens=8, eos_id=cfg.vocab_size,
                      temperature=0.6, seed=2)
rng = np.random.default_rng(1)
rec = RecoveryConfig(dir={recdir!r}, snapshot_every=1)
resume = os.path.exists(rec.journal_path) or \
    os.path.isdir(rec.snap_dir)
hook = None if resume else FaultPlan.seeded(
    {seed}, lanes={slots}, n_nan=0, n_stall=0,
    preempt_within={within}).preempt_hook()
b = Batcher(cfg, params, gcfg, max_batch={slots},
            cache_dtype=jnp.float32)
if not resume:
    for i in range(6):
        b.submit(Request(rid=i, prompt=np.asarray(
            rng.integers(2, cfg.vocab_size, 4 + (i % 3)), np.int32),
            max_new_tokens=3 + (i % 5)))
res = b.run_continuous(recovery=rec, resume=resume, on_segment=hook)
with open({outpath!r}, "a") as out:
    for r in res:
        out.write(json.dumps({{"rid": int(r.rid), "status": r.status,
                   "tokens": [int(x) for x in np.asarray(r.tokens)]}})
                  + "\\n")
"""


def _spawn_until_done(code, devices=1):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return run_to_completion([sys.executable, "-c", code], env=env,
                             max_restarts=10, timeout=600)


def _read_emissions(path):
    with open(path) as f:
        return [json.loads(line) for line in f]


@pytest.mark.slow
class TestKillAndRespawnFarm:
    TRIPS = [3, 9, 5, 12, 7, 4, 10, 6, 8, 11]

    @pytest.mark.parametrize("devices,lanes", [(1, 4), (8, 8)])
    def test_exactly_once_bit_identical(self, tmp_path, devices, lanes):
        ref = run_reference(trip_items(self.TRIPS), lanes=4)
        outpath = str(tmp_path / "emitted.jsonl")
        code = _FARM_CHILD.format(
            src=os.path.abspath(SRC), trips=self.TRIPS,
            recdir=str(tmp_path / "rec"), seed=3 + devices,
            lanes=lanes, within=6, outpath=outpath)
        restarts = _spawn_until_done(code, devices=devices)
        assert restarts >= 1, "the seeded kill never fired"
        recs = _read_emissions(outpath)
        # pre-kill emissions appear once live + once replayed; the
        # exactly-once contract is per process lifetime of the consumer
        final = {r["index"]: r for r in recs}
        assert sorted(final) == list(range(len(self.TRIPS)))
        for i, r in final.items():
            assert r["status"] == "ok"
            assert r["iters"] == int(ref[i].iters)
            assert r["reduced"] == float(ref[i].reduced)
            assert r["sum"] == float(np.asarray(ref[i].a).sum())
            assert r["a00"] == float(np.asarray(ref[i].a)[0, 0])
        # replays are verbatim journal copies of the live record
        for r in recs:
            assert r == final[r["index"]]


_COMPOSED_CHILD = """
import json, os, sys
import numpy as np
sys.path.insert(0, {src!r})
import jax
from repro.core import FarmEngine, GridPartition, LoopOfStencilReduce
from repro.resilience import FaultPlan, RecoveryConfig

def countdown(get, *_):
    return get(0, 0) - 1.0

from repro.sharding.specs import make_mesh
mesh = make_mesh(({lanes}, {shards}), ("data", "model"))
part = GridPartition(mesh=mesh, axis_names=("model",), array_axes=(0,))
loop = LoopOfStencilReduce(
    f=countdown, k=1, combine="max", cond=lambda r: r < 0.5,
    boundary="zero", max_iters=32, backend="pallas-sharded",
    partition=part, interpret=True, block=(16, 128))
base = np.linspace(0.1, 0.9, 32 * 64, dtype=np.float32).reshape(32, 64)
items = [base + float(t) - 1.0 for t in {trips}]
rec = RecoveryConfig(dir={recdir!r}, snapshot_every=1)
resume = os.path.exists(rec.journal_path) or \
    os.path.isdir(rec.snap_dir)
hook = None if resume else FaultPlan(
    lanes={lanes}, preempt_at_segment={at}).preempt_hook()
eng = FarmEngine(loop=loop, lanes={lanes}, mesh=mesh, segment=2)
out = open({outpath!r}, "a")
def sink(r):
    out.write(json.dumps({{"index": int(r.index), "status": r.status,
                           "iters": int(r.iters),
                           "sum": float(np.asarray(r.a).sum())}}) + "\\n")
    out.flush()
eng.run(items, sink, recovery=rec, resume=resume,
        on_segment=hook)
out.close()
"""


@pytest.mark.slow
class TestKillAndRespawnComposed:
    TRIPS = [3, 9, 5, 7, 4, 6]

    def test_sharded_lanes_by_spatial_resume(self, tmp_path):
        """Composed farm (2 lanes × 4 spatial shards) killed mid-stream
        resumes onto the SAME mesh shape from a logical snapshot: the
        snapshotted interiors are unsharded, so the restore path is the
        ordinary sharded refill — exactly-once, bit-identical."""
        ref_eng = FarmEngine(
            loop=mk_countdown(max_iters=32),
            lanes=2, segment=2)
        ref, ref_sink = collect()
        ref_eng.run(trip_items(self.TRIPS, shape=(32, 64)), ref_sink)

        outpath = str(tmp_path / "emitted.jsonl")
        code = _COMPOSED_CHILD.format(
            src=os.path.abspath(SRC), trips=self.TRIPS,
            recdir=str(tmp_path / "rec"), lanes=2, shards=4, at=2,
            outpath=outpath)
        restarts = _spawn_until_done(code, devices=8)
        assert restarts >= 1, "the seeded kill never fired"
        final = {r["index"]: r for r in _read_emissions(outpath)}
        assert sorted(final) == list(range(len(self.TRIPS)))
        for i, r in final.items():
            assert r["status"] == "ok"
            assert r["iters"] == int(ref[i].iters)
            assert r["sum"] == float(np.asarray(ref[i].a).sum())


@pytest.mark.slow
class TestKillAndRespawnServe:
    @pytest.mark.parametrize("devices", [1, 8])
    def test_batcher_drain_survives_kill(self, tmp_path, devices):
        from repro.configs import get_reduced
        from repro.models import transformer as T
        from repro.serve import GenerateConfig
        from repro.serve.batcher import Batcher, Request

        cfg = get_reduced("qwen3-1.7b")
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        gcfg = GenerateConfig(max_new_tokens=8, eos_id=cfg.vocab_size,
                              temperature=0.6, seed=2)
        rng = np.random.default_rng(1)
        b = Batcher(cfg, params, gcfg, max_batch=3,
                    cache_dtype=jnp.float32)
        for i in range(6):
            b.submit(Request(rid=i, prompt=np.asarray(
                rng.integers(2, cfg.vocab_size, 4 + (i % 3)), np.int32),
                max_new_tokens=3 + (i % 5)))
        ref = {r.rid: r for r in b.run_continuous()}

        outpath = str(tmp_path / "emitted.jsonl")
        code = _SERVE_CHILD.format(
            src=os.path.abspath(SRC), recdir=str(tmp_path / "rec"),
            seed=11, slots=3, within=5, outpath=outpath)
        restarts = _spawn_until_done(code, devices=devices)
        assert restarts >= 1, "the seeded kill never fired"
        final = {r["rid"]: r for r in _read_emissions(outpath)}
        assert sorted(final) == list(range(6))
        for rid, r in final.items():
            assert r["status"] == "ok"
            assert r["tokens"] == [int(x) for x in
                                   np.asarray(ref[rid].tokens)]
