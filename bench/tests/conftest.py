"""Tiny copies of the benchmark's cells for CPU rehearsals: the same
drivers, entries and checks, with Pallas in interpret mode."""
import copy

import jax
import pytest

from bench import run

SMALL = {
    "helmholtz-16384": {"grid": [64, 64], "alpha": 1.0, "max_iters": 400},
}


def small_spec(cell: str) -> dict:
    spec = copy.deepcopy(run.load_cell(cell))
    spec["config"].update(SMALL[spec["cell"]["config"]])
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
