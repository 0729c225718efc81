"""Persistent-halo engine: backend parity + the zero-copy guarantee.

Parity: the pattern must produce identical results (values, reduce,
iteration counts) whichever backend realises the loop body — "jnp"
(shift algebra, pad per application), "pallas" (persistent halo frame),
"pallas-multistep" (temporal blocking) — on the -d Jacobi loop for all
four ⊥ models, in interpret mode.

Zero-copy: no ``pad`` primitive (nor any other full-grid staging op) may
appear inside the ``while_loop`` body of the Pallas-backed solver — the
frame is padded once, outside.  Verified by jaxpr inspection, plus a
strict full-grid-ops-per-sweep comparison against the seed's
pad-per-iteration style loop.

Two frames: the frame engines' loop (``_drive_frames``) swaps two frames
with no whole-frame select, and must equal the done-masked ``_drive``
loop bitwise — frame, reduce, iterations, health word and state — at
odd and even exits, at the cap, under the sentinel and under ``vmap``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import frames
from repro.core.executor import StencilEngine
from repro.core.pattern import LoopOfStencilReduce
from repro.core.reduce import Sentinel, health_status
from repro.core.semantics import Boundary
from repro.kernels import ops, ref as R

BOUNDARIES = ["zero", "nan", "reflect", "wrap"]


def heat(get, *_):
    lap = (get(-1, 0) + get(1, 0) + get(0, -1) + get(0, 1)
           - 4.0 * get(0, 0))
    return get(0, 0) + 0.1 * lap


def _loop(backend, boundary, unroll=1, tol=2e-3, **kw):
    kw = {"cond": lambda r: r < tol, "max_iters": 60, **kw}
    return LoopOfStencilReduce(
        f=heat, k=1, combine="max", delta=R.abs_delta, boundary=boundary,
        unroll=unroll, backend=backend, interpret=True, block=(32, 128),
        **kw)


class TestBackendParity:
    @pytest.mark.parametrize("boundary", BOUNDARIES)
    def test_pallas_matches_jnp_d_loop(self, boundary, rng):
        a = jnp.asarray(rng.normal(size=(40, 136)), jnp.float32)
        want = _loop("jnp", boundary).run(a)
        got = _loop("pallas", boundary).run(a)
        assert int(got.iters) == int(want.iters)
        if boundary == "nan":        # NaN ⊥ poisons edges in both paths
            assert np.isnan(np.asarray(got.a)).all() \
                == np.isnan(np.asarray(want.a)).all()
            inner = (slice(2, -2), slice(2, -2))
        else:
            inner = (slice(None), slice(None))
            np.testing.assert_allclose(float(got.reduced),
                                       float(want.reduced), atol=1e-6)
        np.testing.assert_allclose(np.asarray(got.a)[inner],
                                   np.asarray(want.a)[inner], atol=1e-5)

    @pytest.mark.parametrize("boundary", BOUNDARIES)
    @pytest.mark.parametrize("T", [2, 3])
    def test_multistep_T_equals_T_single_steps(self, boundary, T, rng):
        a = jnp.asarray(rng.normal(size=(40, 136)), jnp.float32)
        want = _loop("jnp", boundary, unroll=T).run(a)
        got = _loop("pallas-multistep", boundary, unroll=T).run(a)
        assert int(got.iters) == int(want.iters)
        if boundary != "nan":
            np.testing.assert_allclose(np.asarray(got.a),
                                       np.asarray(want.a), atol=1e-5)
            np.testing.assert_allclose(float(got.reduced),
                                       float(want.reduced), atol=1e-6)

    @pytest.mark.parametrize("boundary", ["zero", "reflect"])
    def test_pallas_unrolled_matches_jnp(self, boundary, rng):
        """unroll>1 on the single-step pallas backend: intermediate
        sweeps skip the fused reduce (do_reduce=False) but the final
        one must still feed the condition identically."""
        a = jnp.asarray(rng.normal(size=(40, 136)), jnp.float32)
        want = _loop("jnp", boundary, unroll=2).run(a)
        got = _loop("pallas", boundary, unroll=2).run(a)
        assert int(got.iters) == int(want.iters)
        np.testing.assert_allclose(np.asarray(got.a), np.asarray(want.a),
                                   atol=1e-5)
        np.testing.assert_allclose(float(got.reduced),
                                   float(want.reduced), atol=1e-6)

    def test_env_fields_reach_f(self, rng):
        u0 = jnp.zeros((24, 40), jnp.float32)
        fxy = jnp.asarray(rng.normal(size=(24, 40)), jnp.float32)
        kw = dict(alpha=2.0, dx=0.2, tol=1e-5, max_iters=400)
        ur, dr, ir = ops.jacobi_solve(u0, fxy, backend="jnp", **kw)
        up, dp, ip = ops.jacobi_solve(u0, fxy, backend="pallas", **kw)
        um, dm, im = ops.jacobi_solve(u0, fxy, backend="pallas-multistep",
                                      unroll=3, **kw)
        assert int(ip) == int(ir)
        assert int(ir) <= int(im) < int(ir) + 3   # unroll may overshoot
        np.testing.assert_allclose(np.asarray(up), np.asarray(ur),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(um), np.asarray(ur),
                                   atol=1e-5)

    def test_bad_backend_and_mode_rejected(self):
        with pytest.raises(ValueError):
            LoopOfStencilReduce(f=heat, cond=lambda r: True,
                                backend="cuda")
        loop = LoopOfStencilReduce(f=lambda a: a, cond=lambda r: True,
                                   mode="step", backend="pallas")
        with pytest.raises(ValueError):
            loop.run(jnp.zeros((8, 8)))


class TestFrames:
    @pytest.mark.parametrize("boundary", BOUNDARIES)
    @pytest.mark.parametrize("pad", [1, 3])
    def test_make_frame_matches_jnp_pad(self, boundary, pad, rng):
        """On an exactly block-rounded domain the domain plus its
        pad-deep ring must equal jnp.pad's realisation of ⊥ (corners
        included), with the domain at a tile-aligned origin."""
        a = jnp.asarray(rng.normal(size=(16, 128)), jnp.float32)
        spec = frames.frame_spec(16, 128, k=1, block=(16, 128), sweeps=pad)
        assert spec.interior == (16, 128)
        r0, c0 = spec.origin
        assert r0 % 8 == 0 and c0 % 128 == 0 and min(r0, c0) >= pad
        got = frames.make_frame(a, spec, boundary)
        ring = got[r0 - pad:r0 + 16 + pad, c0 - pad:c0 + 128 + pad]
        want = Boundary(boundary).pad(a, pad)
        np.testing.assert_array_equal(np.asarray(ring), np.asarray(want))

    def test_refresh_is_edge_sized(self):
        """The refresh touches O(m+n) cells: its jaxpr must not contain
        any update covering the full interior."""
        spec = frames.frame_spec(256, 256, k=1, block=(64, 128))
        fr = jnp.zeros(spec.shape, jnp.float32)
        jaxpr = jax.make_jaxpr(
            lambda x: frames.refresh_frame(x, spec, "reflect"))(fr)
        interior = spec.interior[0] * spec.interior[1]
        for eq in jaxpr.jaxpr.eqns:
            if eq.primitive.name in ("dynamic_update_slice", "scatter"):
                upd = eq.invars[1].aval
                assert np.prod(upd.shape) < interior / 4

    def test_halo_too_wide_rejected(self):
        with pytest.raises(ValueError):
            frames.frame_spec(16, 128, k=1, block=(16, 128), sweeps=20)


def _subjaxprs(eq):
    """Nested sub-jaxprs of an equation (Jaxpr or ClosedJaxpr params)."""
    for v in eq.params.values():
        if hasattr(v, "eqns"):
            yield v
        elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
            yield v.jaxpr


def _flatten_eqns(jx, out):
    """All eqns of ``jx`` including nested sub-jaxprs (pjit/scan/...),
    but NOT Pallas kernel bodies — those are VMEM-tile-internal, not
    HBM staging passes."""
    for eq in jx.eqns:
        out.append(eq)
        if eq.primitive.name == "pallas_call":
            continue
        for sub in _subjaxprs(eq):
            _flatten_eqns(sub, out)


def _while_body_eqns(fn, *args):
    """Equations inside the while_loop bodies of fn's jaxpr, flattened
    through nested sub-jaxprs."""
    bodies = []

    def walk(jx):
        for eq in jx.eqns:
            if eq.primitive.name == "while":
                bodies.append(eq.params["body_jaxpr"].jaxpr)
                continue
            for sub in _subjaxprs(eq):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    assert bodies, "no while_loop in jaxpr"
    eqns = []
    for body in bodies:
        _flatten_eqns(body, eqns)
    return eqns


def _full_grid_ops(eqns, min_elems):
    """Ops *producing* a full-grid-sized array (staging passes)."""
    return [e for e in eqns
            if any(hasattr(v, "aval") and v.aval.shape
                   and int(np.prod(v.aval.shape)) >= min_elems
                   for v in e.outvars)]


class TestZeroCopy:
    def setup_method(self, _):
        self.u0 = jnp.zeros((256, 256), jnp.float32)
        self.fxy = jnp.ones((256, 256), jnp.float32)
        self.kw = dict(alpha=0.5, dx=1.0 / 256, tol=1e-6, max_iters=10)

    def _seed_style_loop(self, u0, fxy):
        """The pad-per-iteration strawman this PR retires: one
        frame/unframe per sweep inside the while body."""
        f = R.helmholtz_jacobi_taps(0.5, 1.0 / 256)

        def body(carry):
            u, d, it = carry
            new, d = ops.fused_sweep(
                u, f, env=(fxy,), k=1, combine="max", identity=-jnp.inf,
                measure=R.abs_delta, backend="pallas", interpret=True,
                block=(128, 128))
            return new, d, it + 1

        return jax.lax.while_loop(
            lambda c: jnp.logical_and(c[1] >= 1e-6, c[2] < 10), body,
            (u0, jnp.asarray(jnp.inf, jnp.float32), jnp.asarray(0)))

    def test_no_pad_in_pallas_while_body(self):
        for backend, unroll in (("pallas", 1), ("pallas-multistep", 4)):
            eqns = _while_body_eqns(
                lambda u, e: ops.jacobi_solve(
                    u, e, backend=backend, unroll=unroll, **self.kw),
                self.u0, self.fxy)
            names = [e.primitive.name for e in eqns]
            assert "pallas_call" in names
            assert "pad" not in names, f"{backend}: pad inside while body"

    def test_seed_style_loop_does_pad_per_iteration(self):
        names = [e.primitive.name
                 for e in _while_body_eqns(self._seed_style_loop,
                                           self.u0, self.fxy)]
        assert "pad" in names          # the strawman really pays it

    def test_fewer_full_grid_ops_than_seed_style(self):
        """Strictly fewer full-grid-producing ops per sweep than the
        pad-per-iteration path (CPU-CI realisation of the acceptance
        criterion).  A body holds one sweep per kernel call: one in the
        strawman, two in the two-frame loop."""
        min_elems = 256 * 256
        seed_eqns = _while_body_eqns(self._seed_style_loop,
                                     self.u0, self.fxy)
        pers_eqns = _while_body_eqns(
            lambda u, e: ops.jacobi_solve(u, e, backend="pallas",
                                          **self.kw),
            self.u0, self.fxy)
        per_sweep = lambda eqns: (len(_full_grid_ops(eqns, min_elems))
                                  / _kernel_calls(eqns))
        assert per_sweep(pers_eqns) < per_sweep(seed_eqns), \
            (per_sweep(pers_eqns), per_sweep(seed_eqns))

    @pytest.mark.parametrize("backend,unroll", [("pallas", 1),
                                                ("pallas-multistep", 4)])
    def test_two_frame_body_has_no_frame_select(self, backend, unroll):
        """The loop carries two frames and its body two kernel calls, and
        no select in the body produces a frame: the done mask picks
        scalars only."""
        fn = lambda u, e: ops.jacobi_solve(u, e, backend=backend,
                                           unroll=unroll, **self.kw)
        eqns = _while_body_eqns(fn, self.u0, self.fxy)
        calls = [e for e in eqns if e.primitive.name == "pallas_call"]
        assert len(calls) == 2
        frame_shapes = {e.outvars[0].aval.shape for e in calls}
        outer = []
        _flatten_eqns(jax.make_jaxpr(fn)(self.u0, self.fxy).jaxpr, outer)
        (loop,) = [e for e in outer if e.primitive.name == "while"]
        carry = [v.aval.shape for v in loop.outvars]
        assert sum(shape in frame_shapes for shape in carry) == 2
        selects = [e for e in eqns if e.primitive.name == "select_n"]
        assert selects, "the scalar picks are gone"
        assert not [e for e in selects
                    if e.outvars[0].aval.shape in frame_shapes]


def _kernel_calls(eqns):
    return sum(e.primitive.name == "pallas_call" for e in eqns)


def _drive_reference(loop, a, env=(), state0=None):
    """The frame engine driven by the done-masked ``_drive`` — the loop
    the two-frame driver replaces, kept here as its oracle."""
    eng = StencilEngine(
        f=loop.f, k=loop.k, boundary=loop.boundary, combine=loop.combine,
        identity=loop.identity, delta=loop.delta, measure=loop.measure,
        block=loop.block, unroll=loop.unroll, backend=loop.backend,
        interpret=loop.interpret)
    frame0, env_frames, spec = eng.prepare(a, env)
    view = lambda fr: eng.unframe(fr, spec)
    return loop._drive(frame0, state0,
                       step=lambda fr: eng.sweeps(fr, env_frames, spec),
                       state_view=view, finalize=view)


def _assert_bitwise(got, want):
    for g, w in zip(jax.tree.leaves((got.a, got.reduced, got.iters,
                                     got.health, got.state)),
                    jax.tree.leaves((want.a, want.reduced, want.iters,
                                     want.health, want.state))):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes(), (g, w)


CONFIGS = [("pallas", 1), ("pallas", 2), ("pallas-multistep", 4)]

# (backend, unroll, tol, max_iters, parity of the checks at the exit):
# tolerances from the 40x136 field of seed 0 under zero ⊥, caps with a
# condition that never fires
EXITS = [
    ("pallas", 1, 2e-2, 200, "odd"), ("pallas", 1, 1.2e-2, 200, "even"),
    ("pallas", 1, None, 7, "odd"), ("pallas", 1, None, 8, "even"),
    ("pallas", 2, 1.2e-2, 200, "odd"), ("pallas", 2, 2e-2, 200, "even"),
    ("pallas", 2, None, 5, "odd"), ("pallas", 2, None, 8, "even"),
    ("pallas-multistep", 4, 2e-2, 200, "odd"),
    ("pallas-multistep", 4, 1.5e-2, 200, "even"),
    ("pallas-multistep", 4, None, 9, "odd"),
    ("pallas-multistep", 4, None, 8, "even"),
]


def _field(shape=(40, 136)):
    return jnp.asarray(np.random.default_rng(0).normal(size=shape),
                       jnp.float32)


class TestTwoFrameDriver:
    @pytest.mark.parametrize(
        "backend,unroll,tol,max_iters,parity", EXITS,
        ids=[f"{b}-u{u}-{'tol' if t else 'cap'}-{p}"
             for b, u, t, _, p in EXITS])
    def test_equals_drive(self, backend, unroll, tol, max_iters, parity):
        cond = (lambda r: r < tol) if tol else (lambda r: False)
        loop = _loop(backend, "zero", unroll=unroll, max_iters=max_iters,
                     cond=cond)
        a = _field()
        got = jax.jit(loop.run)(a)
        want = jax.jit(lambda x: _drive_reference(loop, x))(a)
        _assert_bitwise(got, want)
        checks = int(got.iters) // unroll
        assert checks % 2 == (parity == "odd"), checks
        if tol is None:
            assert int(got.iters) >= max_iters > int(got.iters) - unroll
        else:
            assert float(got.reduced) < tol

    @pytest.mark.parametrize("backend,unroll", CONFIGS)
    def test_state_update_sees_every_check(self, backend, unroll):
        """-s: the update runs after each step of the two-frame body on
        the step's own frame, and the condition reads the state."""
        loop = _loop(
            backend, "zero", unroll=unroll, max_iters=200,
            cond=lambda r, s: jnp.logical_or(r < 1e-2, s[0] >= 11),
            state_init=lambda: (jnp.asarray(0, jnp.int32),
                                jnp.asarray(0.0, jnp.float32)),
            state_update=lambda s, a, it: (s[0] + 1,
                                           s[1] + jnp.sum(a) * it))
        a = _field()
        got = jax.jit(loop.run)(a)
        want = jax.jit(
            lambda x: _drive_reference(loop, x, state0=loop.state_init())
        )(a)
        _assert_bitwise(got, want)
        assert int(got.state[0]) == int(got.iters) // unroll

    @pytest.mark.parametrize("forcing", ["nan", "overflow"])
    @pytest.mark.parametrize("backend,unroll", CONFIGS)
    def test_poisoned_forcing_quarantined_at_the_same_sweep(
            self, backend, unroll, forcing):
        """A NaN cell in the forcing poisons the first check; a 1e30 cell
        doubled every sweep overflows to inf some thirty sweeps in.
        The sentinel quarantines the loop at the same check either way."""
        e = np.zeros((40, 136), np.float32)
        e[17, 60] = np.nan if forcing == "nan" else 1e30
        loop = LoopOfStencilReduce(
            f=lambda get, f: 2.0 * get(0, 0) + f, k=1, combine="max",
            cond=lambda r: r < 1e-6, delta=R.abs_delta, boundary="zero",
            max_iters=200, unroll=unroll, backend=backend,
            interpret=True, block=(32, 128), sentinel=Sentinel(nan=True))
        a = jnp.zeros((40, 136), jnp.float32)
        env = (jnp.asarray(e),)
        got = jax.jit(lambda x, f: loop.run(x, env=(f,)))(a, *env)
        want = jax.jit(lambda x, f: _drive_reference(loop, x, (f,)))(
            a, *env)
        _assert_bitwise(got, want)
        assert health_status(int(got.health)) == "poisoned"
        assert int(got.iters) < 200
        if forcing == "overflow":
            assert int(got.iters) > 20

    @pytest.mark.parametrize("backend,unroll", CONFIGS)
    def test_vmap_lanes_equal_separate_runs(self, backend, unroll):
        """vmap(run) over lanes with mixed trip counts: each lane equals
        its own run (JAX's batched while keeps a finished lane)."""
        loop = _loop(backend, "zero", unroll=unroll, max_iters=200,
                     tol=1.2e-2)
        a = _field()
        batch = jnp.stack([a, 3.0 * a, 0.3 * a])
        out = jax.jit(jax.vmap(loop.run))(batch)
        solo = [jax.jit(loop.run)(x) for x in batch]
        assert len({int(r.iters) for r in solo}) == 3
        for i, want in enumerate(solo):
            _assert_bitwise(jax.tree.map(lambda x: x[i], out), want)
