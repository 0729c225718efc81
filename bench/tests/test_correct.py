"""What decides ``correct`` has to fail: the control (the reference one
precision down in the program's place) and faults planted under the
timed path, each through a whole tiny run with the chip check skipped."""

import pytest

from bench.tests.conftest import run_small
from repro.core import executor

CELLS = ["helmholtz-16384.pallas", "helmholtz-16384.multistep-t4"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    res = run_small(cell, seed=2**31 + 21, control=True)
    assert not res["correct"], res["checks"]


def _unchanged_step(monkeypatch):
    """The sweep hands its input state back (its reduce still computed)."""
    sweeps = executor.StencilEngine.sweeps

    def stuck(self, frame, env_frames, spec):
        return frame, sweeps(self, frame, env_frames, spec)[1]
    monkeypatch.setattr(executor.StencilEngine, "sweeps", stuck)


def _altered_answer(monkeypatch):
    """One cell of every result is changed where the domain is sliced
    out of its frame."""
    unframe = executor.StencilEngine.unframe
    monkeypatch.setattr(executor.StencilEngine, "unframe",
                        lambda self, fr, spec:
                        unframe(self, fr, spec).at[0, 0].add(0.01))


FAULTS = {"unchanged_step": _unchanged_step,
          "altered_answer": _altered_answer}


@pytest.mark.parametrize("cell,fault", [
    ("helmholtz-16384.pallas", "unchanged_step"),
    ("helmholtz-16384.pallas", "altered_answer"),
    ("helmholtz-16384.multistep-t4", "unchanged_step"),
    ("helmholtz-16384.multistep-t4", "altered_answer"),
])
def test_planted_fault_is_not_correct(cell, fault, monkeypatch, fresh_jit):
    FAULTS[fault](monkeypatch)
    res = run_small(cell, seed=2**31 + 31)
    assert not res["correct"], (fault, res["checks"])
