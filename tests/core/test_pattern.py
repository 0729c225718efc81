"""Loop-of-stencil-reduce pattern: variants ≡ the paper's pseudocode
(reference python-loop interpreters from the semantics module)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis "
                    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.core import (LoopOfStencilReduce, loop_of_stencil_reduce,
                        loop_of_stencil_reduce_d, loop_of_stencil_reduce_s)
from repro.core import semantics as sem


def jac_taps(get):
    return 0.25 * (get(-1, 0) + get(1, 0) + get(0, -1) + get(0, 1))


def jac_win(w):
    return 0.25 * (w[..., 0, 1] + w[..., 2, 1] + w[..., 1, 0]
                   + w[..., 1, 2])


def field(seed, shape=(24, 24)):
    return jnp.asarray(np.random.default_rng(seed)
                       .normal(size=shape).astype(np.float32))


class TestBaseVariant:
    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(1, 12))
    def test_matches_reference_interpreter(self, seed, iters):
        """Fixed-iteration run == the paper's repeat/until transcription."""
        a = field(seed)
        import operator
        # condition: sum < threshold chosen so it runs `iters` times is
        # hard to control; use -s style count via max_iters instead
        res = loop_of_stencil_reduce(
            1, jac_taps, "max", lambda r: False, a, max_iters=iters)
        a_ref, r_ref, it_ref = sem.loop_of_stencil_reduce_ref(
            1, jac_win, jnp.maximum, lambda r: False, a,
            identity=-jnp.inf, max_iters=iters)
        assert int(res.iters) == it_ref == iters
        np.testing.assert_allclose(np.asarray(res.a), np.asarray(a_ref),
                                   atol=1e-5)
        np.testing.assert_allclose(float(res.reduced), float(r_ref),
                                   atol=1e-5)

    def test_do_while_runs_at_least_once(self):
        a = field(3)
        res = loop_of_stencil_reduce(1, jac_taps, "max",
                                     lambda r: True, a, max_iters=50)
        assert int(res.iters) == 1       # condition true after first body

    def test_max_iters_cap(self):
        a = field(4)
        res = loop_of_stencil_reduce(1, jac_taps, "max",
                                     lambda r: False, a, max_iters=7)
        assert int(res.iters) == 7


class TestDVariant:
    @settings(max_examples=8, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_matches_reference(self, seed):
        a = field(seed, (16, 16))
        delta = lambda n, o: jnp.abs(n - o)
        res = loop_of_stencil_reduce_d(
            1, jac_taps, delta, "max", lambda r: r < 1e-3, a,
            max_iters=500)
        a_ref, r_ref, it_ref = sem.loop_of_stencil_reduce_d_ref(
            1, jac_win, delta, jnp.maximum, lambda r: r < 1e-3, a,
            identity=-jnp.inf, max_iters=500)
        assert int(res.iters) == it_ref
        np.testing.assert_allclose(np.asarray(res.a), np.asarray(a_ref),
                                   atol=1e-5)

    def test_unroll_overshoots_by_less_than_unroll(self):
        a = field(11, (16, 16))
        delta = lambda n, o: jnp.abs(n - o)
        exact = loop_of_stencil_reduce_d(
            1, jac_taps, delta, "max", lambda r: r < 1e-3, a,
            max_iters=500)
        un = loop_of_stencil_reduce_d(
            1, jac_taps, delta, "max", lambda r: r < 1e-3, a,
            max_iters=500, unroll=4)
        assert int(exact.iters) <= int(un.iters) < int(exact.iters) + 4
        assert float(un.reduced) < 1e-3


class TestSVariant:
    def test_state_controls_termination(self):
        a = field(5)
        res = loop_of_stencil_reduce_s(
            1, jac_taps, "sum", lambda r, s: s >= 9, a,
            init=lambda: jnp.asarray(0, jnp.int32),
            update=lambda s, a_, it: s + 1)
        assert int(res.iters) == 9
        assert int(res.state) == 9

    def test_matches_reference(self):
        a = field(6, (12, 12))
        res = loop_of_stencil_reduce_s(
            1, jac_taps, "sum", lambda r, s: s >= 5, a,
            init=lambda: jnp.asarray(0, jnp.int32),
            update=lambda s, a_, it: s + 1)
        a_ref, r_ref, it_ref, s_ref = sem.loop_of_stencil_reduce_s_ref(
            1, jac_win, jnp.add if False else __import__("operator").add,
            lambda r, s: s >= 5, a, identity=0.0,
            init=lambda: 0, update=lambda s: s + 1, max_iters=100)
        assert int(res.iters) == it_ref
        np.testing.assert_allclose(np.asarray(res.a), np.asarray(a_ref),
                                   atol=1e-4)


class TestStreaming:
    def test_farm_lanes_converge_independently(self):
        """1:1 mode: each stream item runs to its own trip count."""
        runner = LoopOfStencilReduce(
            f=jac_taps, k=1, combine="max", identity=-jnp.inf,
            cond=lambda r: r < 1e-3, delta=lambda n, o: jnp.abs(n - o),
            max_iters=2000)
        batch = jnp.stack([field(1), field(2) * 10.0, field(3) * 0.01])
        out = jax.vmap(runner.run)(batch)
        solo = [runner.run(batch[i]) for i in range(3)]
        for i in range(3):
            assert int(out.iters[i]) == int(solo[i].iters)
            np.testing.assert_allclose(np.asarray(out.a[i]),
                                       np.asarray(solo[i].a), atol=1e-5)
        # trip counts genuinely differ across lanes
        assert len({int(x) for x in out.iters}) >= 2

    def test_pattern_is_jittable_and_donatable(self):
        runner = LoopOfStencilReduce(
            f=jac_taps, k=1, combine="max", identity=-jnp.inf,
            cond=lambda r: r < 1e-3, delta=lambda n, o: jnp.abs(n - o),
            max_iters=100)
        out = runner.jit_run()(field(9))
        assert out.a.shape == (24, 24)


class TestIndexedVariant:
    """-i: the elemental function receives σ̄_k (value+index windows)."""

    def test_position_weighted_stencil(self):
        a = field(21, (12, 10))

        def f_indexed(w, idx):
            # value of each neighbour weighted by whether its ABSOLUTE
            # row index is even (needs σ̄_k, not σ_k)
            rows = idx[..., 0]
            weight = (rows % 2 == 0).astype(a.dtype)
            return (w * weight).sum(axis=(-1, -2))

        res = loop_of_stencil_reduce(1, f_indexed, "sum",
                                     lambda r: True, a, mode="indexed")
        # manual oracle
        import numpy as np
        an = np.asarray(jnp.pad(a, 1))
        want = np.zeros((12, 10), np.float32)
        for i in range(12):
            for j in range(10):
                for di in (-1, 0, 1):
                    for dj in (-1, 0, 1):
                        if (i + di) % 2 == 0:
                            want[i, j] += an[i + di + 1, j + dj + 1]
        np.testing.assert_allclose(np.asarray(res.a), want, atol=1e-4)
        assert int(res.iters) == 1

    def test_indexed_centre_equals_plain(self):
        """An index-ignoring f̄ gives exactly the base variant."""
        a = field(22, (16, 16))

        def f_idx(w, idx):
            return 0.25 * (w[..., 0, 1] + w[..., 2, 1] + w[..., 1, 0]
                           + w[..., 1, 2])
        r1 = loop_of_stencil_reduce(1, f_idx, "max", lambda r: False, a,
                                    mode="indexed", max_iters=3)
        r2 = loop_of_stencil_reduce(1, jac_taps, "max", lambda r: False,
                                    a, max_iters=3)
        np.testing.assert_allclose(np.asarray(r1.a), np.asarray(r2.a),
                                   atol=1e-5)
