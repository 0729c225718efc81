"""CPU rehearsals of each driver at a tiny size (Pallas in interpret mode),
and the symmetries of the seed."""
import numpy as np
import pytest

from bench import generators
from bench.tests.conftest import run_small, small_spec

SOLVE_CELLS = ["helmholtz-16384.pallas", "helmholtz-16384.multistep-t4"]


@pytest.mark.parametrize("cell", SOLVE_CELLS)
def test_solve_rehearsal(cell):
    res = run_small(cell, seed=2**31 + 11)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"solve_s", "setup_s"}
    assert res["metrics"]["solve_s"]["value"] > 0
    assert list(res)[-1] == "checks"


def _cell(name, seed):
    import importlib

    import jax

    spec = small_spec(name)
    driver = importlib.import_module(
        "bench.drivers." + spec["config"]["driver"])
    return driver.Cell(spec["config"], spec["traffic"], seed,
                       jax.devices()[:1])


def test_solve_probe_is_drawn_from_the_seed():
    """The probe's forcing is no symmetry of the window's: the check
    sees an input of the seed's own, solved by the window's program."""
    cell = _cell("helmholtz-16384.pallas", 2**31 + 13)
    win = cell.window(0.2)
    cell.probe()
    f0, fp = np.asarray(cell.forcings[0]), np.asarray(cell.forcings[-1])
    assert len(cell.forcings) == 2 and len(cell.records) == win.attempted + 1
    for g in (fp, fp[::-1], -fp, -fp[::-1]):
        assert not np.array_equal(f0, g)
    cell.release()
    assert all(c.ok for c in cell.check()), cell.check()


def test_seed_draw_differs_by_seed():
    draws = {generators.seed_draw(s) for s in range(2**31, 2**31 + 8)}
    assert len(draws) == 8
    assert generators.seed_draw(2**40 + 3) == generators.seed_draw(2**40 + 3)


def test_forcing_fields_are_symmetries_of_one_draw():
    two = generators.forcing_fields(5, (8, 8), 2, 11)
    assert not np.array_equal(two[0], two[1])
    bases, seen = [], set()
    for seed in range(2**31, 2**31 + 16):
        f = np.asarray(generators.forcing_fields(seed, (8, 8), 1, 11)[0])
        flip, sign = generators._symmetry(seed)
        seen.add((flip, sign))
        bases.append(sign * (f[::-1] if flip else f))
    assert len(seen) == 4
    assert all(np.array_equal(b, bases[0]) for b in bases)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_symmetric_forcings_do_the_same_work(backend):
    """The program's solve of a flipped or negated forcing takes the same
    iterations and reaches the same change, bitwise; its answer is the
    flipped or negated answer."""
    import jax.numpy as jnp
    from repro.kernels import ops

    f = np.asarray(generators.forcing_fields(0, (64, 64), 1, 3)[0])
    u0 = jnp.zeros((64, 64), jnp.float32)

    def solve(x):
        u, d, it = ops.jacobi_solve(u0, jnp.asarray(x), alpha=1.0, dx=1.0,
                                    tol=np.float32(1e-5), max_iters=400,
                                    backend=backend)
        return np.asarray(u), float(d), int(it)

    u, d, it = solve(f)
    for g, back in ((f[::-1], lambda v: v[::-1]), (-f, lambda v: -v),
                    (-f[::-1], lambda v: -v[::-1])):
        ug, dg, itg = solve(np.ascontiguousarray(g))
        assert (itg, dg) == (it, d)
        assert np.array_equal(back(ug), u)
