"""pallas-sharded backend: distributed ≡ single-device, zero-copy body.

Parity: the 1:n persistent deployment (per-shard halo frames inside
shard_map, ppermute ghost exchange, monoid collectives) must match the
single-device "jnp" and "pallas" backends — values, reduce, iteration
counts — across 1-D and 2-D decompositions, all four ⊥ models,
sum/max/any monoids, and unroll ∈ {1, 4} (deep-halo temporal blocking);
its two-frame loop must equal the done-masked ``_drive`` loop bitwise.

Zero-copy/communication-avoiding: jaxpr inspection of the sharded
while_loop body shows no ``pad``, no array-sized ``concatenate``, no
full-block ``dynamic_slice`` — only edge-strip traffic — and unroll=4
issues the same ppermute rounds per *body* as unroll=1 while advancing
4 sweeps: 1/4 the exchanges per sweep.

Multi-device tests run in a SUBPROCESS with 8 placeholder host devices so
the main test process keeps the single-device view.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "..", "src")
ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def run_multidevice(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


PRELUDE = """
import numpy as np, jax, jax.numpy as jnp
from repro.core import LoopOfStencilReduce, GridPartition
from repro.kernels import ref as R
rng = np.random.default_rng(0)
a = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)

def heat(get, *_):
    lap = get(-1,0)+get(1,0)+get(0,-1)+get(0,1)-4.0*get(0,0)
    return get(0,0)+0.1*lap

def loop(backend, boundary="zero", unroll=1, part=None, combine="max",
         cond=None, delta=R.abs_delta, max_iters=12):
    cond = cond or (lambda r: r < 2e-3)
    return LoopOfStencilReduce(
        f=heat, k=1, combine=combine, cond=cond, delta=delta,
        boundary=boundary, max_iters=max_iters, unroll=unroll,
        backend=backend, partition=part, interpret=True, block=(16, 128))

def check(want, got, boundary):
    assert int(want.iters) == int(got.iters), (want.iters, got.iters)
    wa, ga = np.asarray(want.a), np.asarray(got.a)
    if boundary == "nan":
        # NaN ⊥ poisons a k-per-sweep deep border: the poisoned REGION
        # must match cell-for-cell, and the surviving interior must agree
        np.testing.assert_array_equal(np.isnan(ga), np.isnan(wa))
        np.testing.assert_allclose(ga[~np.isnan(ga)], wa[~np.isnan(wa)],
                                   atol=1e-5)
        return
    np.testing.assert_allclose(ga, wa, atol=1e-5)
    np.testing.assert_allclose(float(got.reduced), float(want.reduced),
                               atol=1e-5)

# pallas-sharded driven by the done-masked _drive: the loop the two-frame
# driver replaced, kept as its oracle
def drive_ref(lp, x):
    from jax.sharding import PartitionSpec as P
    from repro.core.executor import ShardedStencilEngine
    from repro.sharding.specs import shard_map
    part = lp.partition
    eng = ShardedStencilEngine(
        f=lp.f, part=part, k=lp.k, boundary=lp.boundary,
        combine=lp.combine, identity=lp.identity, delta=lp.delta,
        measure=lp.measure, block=lp.block, unroll=lp.unroll,
        interpret=lp.interpret)

    def local_run(block):
        fr, efs, sspec = eng.prepare(block, ())
        view = lambda f: eng.unframe(f, sspec)
        res = lp._drive(fr, None, step=lambda f: eng.sweeps(f, efs, sspec),
                        state_view=view, finalize=view)
        return res.a, res.reduced, res.iters, res.health

    return shard_map(local_run, mesh=part.mesh, in_specs=(part.pspec,),
                     out_specs=(part.pspec, P(), P(), P()))(x)

def check_bitwise(got, want):
    for g, w in zip((got.a, got.reduced, got.iters, got.health), want):
        assert np.asarray(g).tobytes() == np.asarray(w).tobytes()

from repro.sharding.specs import make_mesh
part1d = lambda: GridPartition(mesh=make_mesh((8,), ("data",)),
                               axis_names=("data",), array_axes=(0,))
part2d = lambda: GridPartition(mesh=make_mesh((4, 2), ("data", "model")),
                               axis_names=("data", "model"),
                               array_axes=(0, 1))
"""


@pytest.mark.slow
class TestShardedParity:
    def test_1d_all_boundaries_both_unrolls(self):
        out = run_multidevice(PRELUDE + textwrap.dedent("""
            part = part1d()
            for boundary in ("zero", "nan", "reflect", "wrap"):
                for unroll in (1, 4):
                    want = loop("pallas", boundary, unroll).run(a)
                    got = loop("pallas-sharded", boundary, unroll,
                               part).run(a)
                    check(want, got, boundary)
            # termination parity: a tolerance the loop actually reaches
            w = loop("jnp", "reflect", 1, max_iters=400,
                     cond=lambda r: r < 2e-2).run(a)
            g = loop("pallas-sharded", "reflect", 1, part, max_iters=400,
                     cond=lambda r: r < 2e-2).run(a)
            assert int(w.iters) < 400, int(w.iters)
            check(w, g, "reflect")
            print("OK1D")
        """))
        assert "OK1D" in out

    def test_2d_decomposition_and_monoids(self):
        out = run_multidevice(PRELUDE + textwrap.dedent("""
            part = part2d()
            for boundary in ("zero", "nan", "reflect", "wrap"):
                for unroll in (1, 4):
                    want = loop("pallas", boundary, unroll).run(a)
                    got = loop("pallas-sharded", boundary, unroll,
                               part).run(a)
                    check(want, got, boundary)
            # sum / any monoids against BOTH single-device backends
            for comb, cond, delta in (
                ("sum", lambda r: r < 1.0, R.abs_delta),
                ("any", lambda r: ~r,
                 lambda n, o: jnp.abs(n - o) > 1e-3),
            ):
                for unroll in (1, 4):
                    wj = loop("jnp", "zero", unroll, combine=comb,
                              cond=cond, delta=delta).run(a)
                    wp = loop("pallas", "zero", unroll, combine=comb,
                              cond=cond, delta=delta).run(a)
                    g = loop("pallas-sharded", "zero", unroll, part,
                             combine=comb, cond=cond, delta=delta).run(a)
                    check(wj, g, "zero")
                    check(wp, g, "zero")
            print("OK2D")
        """))
        assert "OK2D" in out

    def test_env_fields_and_apps(self):
        out = run_multidevice(PRELUDE + textwrap.dedent("""
            from repro.kernels import ops
            fxy = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
            u0 = jnp.zeros((64, 64), jnp.float32)
            kw = dict(alpha=2.0, dx=0.2, tol=1e-4, max_iters=200)
            ur, dr, ir = ops.jacobi_solve(u0, fxy, backend="jnp", **kw)
            us, ds, is_ = ops.jacobi_solve(u0, fxy, part=part1d(), **kw)
            u4, d4, i4 = ops.jacobi_solve(u0, fxy, part=part2d(),
                                          unroll=4, **kw)
            assert int(ir) == int(is_), (ir, is_)
            assert int(ir) <= int(i4) < int(ir) + 4    # unroll overshoot
            np.testing.assert_allclose(np.asarray(us), np.asarray(ur),
                                       atol=1e-5)
            np.testing.assert_allclose(np.asarray(u4), np.asarray(ur),
                                       atol=1e-4)
            print("OKENV")
        """))
        assert "OKENV" in out

    def test_two_frame_driver_equals_drive(self):
        """The two-frame loop equals the done-masked ``_drive`` loop
        bitwise on both decompositions: tolerance exits after an odd and
        an even number of checks, and the cap."""
        out = run_multidevice(PRELUDE + textwrap.dedent("""
            never = lambda r: False
            for part, unroll, tol, max_iters, checks in (
                (part1d(), 1, 2e-2, 400, 19), (part2d(), 1, 1.5e-2, 400, 24),
                (part2d(), 1, None, 7, 7), (part2d(), 4, 2e-2, 400, 5),
                (part1d(), 4, 1.5e-2, 400, 6), (part1d(), 4, None, 9, 3),
            ):
                cond = never if tol is None else (lambda r, t=tol: r < t)
                lp = loop("pallas-sharded", "zero", unroll, part,
                          cond=cond, max_iters=max_iters)
                got = lp.run(a)
                assert int(got.iters) == checks * unroll, int(got.iters)
                check_bitwise(got, drive_ref(lp, a))
            print("OKDRIVE")
        """))
        assert "OKDRIVE" in out

    def test_distributed_front_end_sharded_backend(self):
        """distributed_loop_of_stencil_reduce(backend='pallas-sharded')
        delegates to the engine and matches its own jnp path."""
        out = run_multidevice(PRELUDE + textwrap.dedent("""
            from repro.core import distributed_loop_of_stencil_reduce
            part = part1d()
            kw = dict(k=1, part=part, delta=R.abs_delta, max_iters=12,
                      boundary="reflect")
            dj = distributed_loop_of_stencil_reduce(
                heat, "max", lambda r: r < 2e-3, a, **kw)
            dp = distributed_loop_of_stencil_reduce(
                heat, "max", lambda r: r < 2e-3, a,
                backend="pallas-sharded", block=(16, 128),
                interpret=True, **kw)
            assert int(dj.iters) == int(dp.iters)
            np.testing.assert_allclose(np.asarray(dp.a), np.asarray(dj.a),
                                       atol=1e-5)
            print("OKFRONT")
        """))
        assert "OKFRONT" in out


JAXPR_HELPERS = """
from repro.core.introspect import while_body_eqns, max_outsize as outsize
"""


@pytest.mark.slow
class TestShardedZeroCopy:
    def test_no_staging_ops_and_ppermute_rounds(self):
        """The acceptance criterion, by jaxpr inspection: the sharded
        while body holds no pad, no array-sized concatenate, no
        full-block dynamic_slice; unroll=4 issues <= the ppermute
        rounds of unroll=1 per body while advancing 4 sweeps (=> 1/4
        the ICI messages per sweep)."""
        out = run_multidevice(PRELUDE + JAXPR_HELPERS + textwrap.dedent("""
            part = part1d()
            BLOCK = (64 // 8) * 64          # one shard's domain cells

            def counts(unroll, boundary):
                fn = lambda x: loop("pallas-sharded", boundary, unroll,
                                    part).run(x).a
                eqns = while_body_eqns(fn, a)
                names = [e.primitive.name for e in eqns]
                assert "pallas_call" in names
                assert "pad" not in names, f"pad in body ({boundary})"
                big_cat = [e for e in eqns
                           if e.primitive.name == "concatenate"
                           and outsize(e) >= BLOCK]
                assert not big_cat, "array-sized concatenate in body"
                big_ds = [e for e in eqns
                          if e.primitive.name == "dynamic_slice"
                          and outsize(e) >= BLOCK]
                assert not big_ds, "full-block dynamic_slice in body"
                return names.count("ppermute")

            for boundary in ("zero", "reflect", "wrap"):
                c1 = counts(1, boundary)
                c4 = counts(4, boundary)
                assert c1 > 0
                # same rounds per body, 4 sweeps per body => 1/4 per sweep
                assert c4 <= c1, (c4, c1)
                assert c4 / 4 <= c1 / 4
            print("OKZC")
        """))
        assert "OKZC" in out


# the converged 1:n solve at 128² over a 2×2 mesh against the plain
# reference of the benchmark (bench/reference.py, jax.numpy only); FAULT
# swaps each exchanged axis's ppermute for the local zero fill
AGAINST_REFERENCE = """
import sys
import numpy as np, jax.numpy as jnp
sys.path.insert(0, ROOT)
from bench.reference import helmholtz_solve
from repro.core import GridPartition, frames
from repro.core.semantics import Boundary
from repro.kernels import ops
from repro.sharding.specs import make_mesh

if FAULT:
    def local_fill(frame, sspec, axis, boundary, olo, ohi):
        return frames._refresh_axis_local(frame, sspec.local, axis,
                                          Boundary.ZERO, olo, ohi)
    frames._refresh_axis_sharded = local_fill

part = GridPartition(mesh=make_mesh((2, 2), ("rows", "cols")),
                     axis_names=("rows", "cols"), array_axes=(0, 1))
kw = dict(alpha=0.1, dx=1.0, max_iters=2000)
tol = np.float32(1e-5)
for seed in (0, 1):
    f = jnp.asarray(np.random.default_rng(seed).normal(size=(128, 128)),
                    jnp.float32)
    u, d, it = ops.jacobi_solve(jnp.zeros_like(f), f, tol=tol, part=part,
                                backend="pallas-sharded", unroll=1, **kw)
    ur, dr, ir = helmholtz_solve(f, tol, **kw)
    err = float(jnp.max(jnp.abs(u - ur)) / jnp.max(jnp.abs(ur)))
    agree = (err <= 1e-4 and abs(int(it) - int(ir)) <= 1
             and abs(float(d) - float(dr)) <= tol)
    print("AGREE" if agree else "DISAGREE", seed, err, int(it), int(ir),
          float(d), float(dr))
"""


@pytest.mark.parametrize("fault", [False, True], ids=["exchange",
                                                      "local_fill"])
def test_converged_2x2_solve_against_the_reference(fault):
    """A converged ``jacobi_solve(part=2×2)`` on seeded N(0, 1) forcings
    agrees with the plain reference: u within 1e-4 of max|u_ref|, the
    iteration counts within one check, the last change within one tol.
    With the exchange replaced by the local zero fill it must not."""
    code = (f"ROOT = {ROOT!r}\nFAULT = {fault}\n"
            + textwrap.dedent(AGAINST_REFERENCE))
    lines = [ln for ln in run_multidevice(code, devices=4).splitlines()
             if ln.startswith(("AGREE", "DISAGREE"))]
    assert len(lines) == 2, lines
    want = "DISAGREE" if fault else "AGREE"
    assert all(ln.split()[0] == want for ln in lines), lines


class TestShardedValidation:
    def test_partition_required(self):
        import jax.numpy as jnp
        from repro.core import LoopOfStencilReduce
        with pytest.raises(ValueError, match="partition"):
            LoopOfStencilReduce(f=lambda g: g.center,
                                cond=lambda r: True,
                                backend="pallas-sharded")

    def test_uneven_decomposition_rejected(self):
        import jax.numpy as jnp
        from types import SimpleNamespace
        from repro.core import LoopOfStencilReduce
        # duck-typed partition: the divisibility check runs before any
        # mesh machinery, so a stub with a 3-way axis suffices
        part = SimpleNamespace(
            mesh=SimpleNamespace(shape={"data": 3}),
            axis_names=("data",), array_axes=(0,))
        loop = LoopOfStencilReduce(
            f=lambda g: g.center, cond=lambda r: True,
            backend="pallas-sharded", partition=part)
        with pytest.raises(ValueError, match="divide"):
            loop.run(jnp.zeros((8, 128), jnp.float32))

    def test_state_variant_rejected(self):
        import jax
        import jax.numpy as jnp
        from repro.core import GridPartition, LoopOfStencilReduce
        from repro.sharding.specs import make_mesh
        mesh = make_mesh((1,), ("data",))
        part = GridPartition(mesh=mesh, axis_names=("data",),
                             array_axes=(0,))
        loop = LoopOfStencilReduce(
            f=lambda g: g.center, cond=lambda r, s: True,
            state_init=lambda: jnp.zeros(()),
            state_update=lambda s, a, it: s,
            backend="pallas-sharded", partition=part)
        with pytest.raises(ValueError, match="-s variant"):
            loop.run(jnp.zeros((8, 128), jnp.float32))


class TestBoundaryPadDedup:
    """halo's per-axis ⊥ padding now routes through Boundary.pad(axes=)
    — one helper, three call sites (semantics, TapAccessor, halo)."""

    @pytest.mark.parametrize("boundary", ["zero", "nan", "reflect", "wrap"])
    def test_axes_subset_matches_full_pad(self, boundary, rng):
        import jax.numpy as jnp
        from repro.core.semantics import Boundary
        a = jnp.asarray(rng.normal(size=(6, 7)), jnp.float32)
        b = Boundary(boundary)
        full = np.asarray(b.pad(a, 2))
        only0 = np.asarray(b.pad(a, 2, axes=(0,)))
        assert only0.shape == (10, 7)
        np.testing.assert_array_equal(only0, full[:, 2:-2])
        both = np.asarray(b.pad(a, 2, axes=(0, 1)))
        np.testing.assert_array_equal(both, full)

    def test_no_axes_is_identity(self, rng):
        import jax.numpy as jnp
        from repro.core.semantics import Boundary
        a = jnp.asarray(rng.normal(size=(4, 5)), jnp.float32)
        out = Boundary("reflect").pad(a, 3, axes=())
        np.testing.assert_array_equal(np.asarray(out), np.asarray(a))
