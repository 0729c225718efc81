"""CPU rehearsals of the mesh-sharded solve cell at its rehearsal size
(128² over a 2×2 mesh of placeholder CPU devices, Pallas in interpret
mode) through ``run.run``, and what decides its ``correct`` failing: the
control and two planted faults.  Each case runs in a process of its own
with four devices, so this process keeps its one-device view; no fault
makes the shards' trip counts differ, since mismatched collectives can
hang."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench import run

CELL = "helmholtz-32768-2x2.sharded"

PRELUDE = """
import json, sys
sys.path[:0] = [ROOT, ROOT + "/src"]
from bench.tests.conftest import run_small
"""


def run4(code: str, timeout: float = 300) -> dict:
    """Run ``code`` with four CPU devices; it prints a JSON object last."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    src = f"ROOT = {run.ROOT!r}\nCELL = {CELL!r}\n" + textwrap.dedent(
        PRELUDE) + textwrap.dedent(code)
    out = subprocess.run([sys.executable, "-c", src], env=env,
                         cwd=run.ROOT, capture_output=True, text=True,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_rehearsal_is_correct():
    res = run4("""
        res = run_small(CELL, seed=2**31 + 11)
        print(json.dumps(res))
    """)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"solve_s", "setup_s"}
    assert res["device"]["count"] == 4
    assert list(res)[-1] == "checks"


def test_probe_is_drawn_from_the_seed():
    """The probe's forcing is no symmetry of the window's, and the check
    holds it, solved over the mesh, to the reference."""
    res = run4("""
        import importlib
        import jax
        import numpy as np
        from bench.tests.conftest import small_spec
        spec = small_spec(CELL)
        driver = importlib.import_module(
            "bench.drivers." + spec["config"]["driver"])
        cell = driver.Cell(spec["config"], spec["traffic"], 2**31 + 13,
                           jax.devices()[:4])
        win = cell.window(0.2)
        cell.probe()
        f0 = np.asarray(cell.forcings[0])
        fp = np.asarray(cell.forcings[-1])
        sym = any(np.array_equal(f0, g)
                  for g in (fp, fp[::-1], -fp, -fp[::-1]))
        shards = len(cell.forcings[-1].sharding.device_set)
        cell.release()
        print(json.dumps({
            "count": len(cell.forcings), "sym": sym, "shards": shards,
            "records": len(cell.records), "attempted": win.attempted,
            "ok": all(c.ok for c in cell.check())}))
    """)
    assert res["count"] == 2 and not res["sym"] and res["shards"] == 4
    assert res["records"] == res["attempted"] + 1
    assert res["ok"]


def test_control_is_not_correct():
    res = run4("""
        res = run_small(CELL, seed=2**31 + 21, control=True)
        print(json.dumps(res))
    """)
    assert not res["correct"], res["checks"]


FAULTS = {
    # the ghost ring is never refreshed from the neighbours
    "exchange_left_out": """
        from repro.core import frames
        frames._refresh_axis_sharded = (
            lambda frame, sspec, axis, boundary, olo, ohi: frame)
    """,
    # one cell of each shard's answer changes where it leaves its frame
    "altered_answer": """
        from repro.core import executor
        unframe = executor.ShardedStencilEngine.unframe
        executor.ShardedStencilEngine.unframe = (
            lambda self, fr, sspec:
            unframe(self, fr, sspec).at[0, 0].add(0.01))
    """,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(fault):
    res = run4(FAULTS[fault] + """
        res = run_small(CELL, seed=2**31 + 31)
        print(json.dumps(res))
    """)
    assert not res["correct"], (fault, res["checks"])
