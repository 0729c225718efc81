"""Tiny copies of the benchmark's cells for CPU rehearsals: the same
drivers, entries and checks, with Pallas in interpret mode, at the size
each configuration file gives under its ``rehearsal`` key."""
import copy

import jax
import pytest

from bench import run


def small_spec(cell: str) -> dict:
    """The cell at the size its configuration's ``rehearsal`` key gives:
    numbers that replace the configuration's, and under ``traffic``
    those that replace the traffic's."""
    spec = copy.deepcopy(run.load_cell(cell))
    small = dict(spec["config"]["rehearsal"])
    spec["traffic"].update(small.pop("traffic", {}))
    spec["config"].update(small)
    return spec


def run_small(cell: str, seed: int, seconds: float = 1.0, **kw) -> dict:
    import time

    spec = small_spec(cell)
    return run.run(spec, seed, seconds, False,
                   jax.devices()[:spec["config"]["chips"]],
                   time.perf_counter(), **kw)


@pytest.fixture
def fresh_jit():
    """Faults are planted in code that jit traces: drop every trace
    before and after, so no test sees another's program."""
    jax.clear_caches()
    yield
    jax.clear_caches()
