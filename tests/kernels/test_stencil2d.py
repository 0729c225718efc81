"""Pallas fused stencil+reduce kernel: shape/dtype sweeps vs ref.py oracle
(interpret mode on CPU; TPU is the compile target)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref as R
from repro.kernels.stencil2d import stencil2d_fused

SHAPES = [(16, 128), (64, 128), (100, 130), (256, 256), (257, 300),
          (33, 520)]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("double_buffer", [False, True])
def test_heat_delta_max(shape, double_buffer, rng):
    a = jnp.asarray(rng.normal(size=shape), jnp.float32)
    f = R.heat_taps(0.1)
    new, red = stencil2d_fused(a, f, k=1, combine="max", identity=-jnp.inf,
                               measure=R.abs_delta, boundary="zero",
                               block=(64, 128),
                               double_buffer=double_buffer, interpret=True)
    wn, wr = R.stencil2d_fused_ref(a, f, k=1, combine="max",
                                   identity=-jnp.inf, measure=R.abs_delta,
                                   boundary="zero")
    np.testing.assert_allclose(np.asarray(new), np.asarray(wn), atol=1e-5)
    np.testing.assert_allclose(float(red), float(wr), atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("combine,identity",
                         [("sum", None), ("max", None), ("min", None)])
def test_monoids_and_dtypes(dtype, combine, identity, rng):
    a = jnp.asarray(rng.normal(size=(96, 160)), dtype)
    f = R.sobel_taps()
    new, red = stencil2d_fused(a, f, k=1, combine=combine,
                               identity=identity, boundary="reflect",
                               block=(32, 128), interpret=True)
    wn, wr = R.stencil2d_fused_ref(a, f, k=1, combine=combine,
                                   identity=identity, boundary="reflect")
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(new, np.float32),
                               np.asarray(wn, np.float32), atol=tol,
                               rtol=tol)
    np.testing.assert_allclose(float(red), float(wr), atol=tol, rtol=tol)


@pytest.mark.parametrize("k", [1, 2, 3])
def test_radii_and_env(k, rng):
    """k up to 3 (the AMF escalation bound) with env fields."""
    a = jnp.asarray(rng.uniform(size=(80, 144)), jnp.float32)
    fxy = jnp.asarray(rng.normal(size=(80, 144)), jnp.float32)

    def f(get, env):
        import itertools
        acc = env * 0.5
        for di, dj in itertools.product(range(-k, k + 1), repeat=2):
            acc = acc + get(di, dj)
        return acc / (2 * k + 1) ** 2
    new, red = stencil2d_fused(a, f, env=(fxy,), k=k, combine="sum",
                               identity=0.0, boundary="zero",
                               block=(32, 128), interpret=True)
    wn, wr = R.stencil2d_fused_ref(a, f, env=(fxy,), k=k, combine="sum",
                                   identity=0.0, boundary="zero")
    np.testing.assert_allclose(np.asarray(new), np.asarray(wn), atol=1e-4)
    np.testing.assert_allclose(float(red), float(wr), rtol=1e-4)


def test_vmap_runs_the_lane_grid(rng):
    """vmap of the framed kernel runs its own lane grid, with lanes
    sharing the env field: every lane matches its solo sweep."""
    from repro.core.frames import frame_spec, make_frame
    from repro.kernels.stencil2d import stencil2d_fused_framed
    spec = frame_spec(40, 136, k=1, block=(16, 128))
    a = jnp.asarray(rng.normal(size=(3, 40, 136)), jnp.float32)
    env = jnp.asarray(rng.normal(size=spec.interior), jnp.float32)
    sweep = lambda fr: stencil2d_fused_framed(
        fr, lambda get, e: R.heat_taps(0.1)(get) + e, spec,
        env_framed=(env,), combine="max", measure=R.abs_delta,
        interpret=True)
    frames = jax.vmap(lambda x: make_frame(x, spec, "zero"))(a)
    out, red = jax.vmap(sweep)(frames)
    for i in range(3):
        o, r = sweep(frames[i])
        np.testing.assert_array_equal(np.asarray(out[i][spec.domain]),
                                      np.asarray(o[spec.domain]))
        assert float(red[i]) == float(r)


class TestApps:
    def test_jacobi_solver_converges_and_matches_ref_path(self, rng):
        # alpha strengthens the diagonal => contraction converges quickly
        u0 = jnp.zeros((48, 64), jnp.float32)
        fx = jnp.asarray(rng.normal(size=(48, 64)), jnp.float32)
        kw = dict(alpha=2.0, dx=0.2, tol=1e-5, max_iters=800)
        up, dp_, ip_ = ops.jacobi_solve(u0, fx, use_pallas=True, **kw)
        ur, dr, ir_ = ops.jacobi_solve(u0, fx, use_pallas=False, **kw)
        assert int(ip_) == int(ir_)
        np.testing.assert_allclose(np.asarray(up), np.asarray(ur),
                                   atol=1e-5)
        assert int(ip_) < 800          # converged before the cap

    def test_sobel_pallas_matches_ref(self, rng):
        img = jnp.asarray(rng.uniform(size=(120, 200)), jnp.float32)
        e1, m1 = ops.sobel(img, use_pallas=True)
        e2, m2 = ops.sobel(img, use_pallas=False)
        np.testing.assert_allclose(np.asarray(e1), np.asarray(e2),
                                   atol=1e-5)
        np.testing.assert_allclose(float(m1), float(m2), rtol=1e-5)

    def test_restoration_two_phase_improves_psnr(self, rng):
        yy, xx = np.mgrid[0:96, 0:160]
        frame = np.clip(0.5 + 0.3 * np.sin(xx / 20.0) * np.cos(yy / 15.0),
                        0, 1).astype(np.float32)
        imp = rng.uniform(size=frame.shape) < 0.3
        sp = np.where(rng.uniform(size=frame.shape) < 0.5, 0.0, 1.0)
        noisy = jnp.asarray(np.where(imp, sp, frame), jnp.float32)
        mask, repaired = ops.adaptive_median_detect(noisy, use_pallas=True)
        out, d, it = ops.restore(repaired, mask, max_iters=60,
                                 use_pallas=True)

        def psnr(x):
            return -10 * np.log10(np.mean((np.asarray(x) - frame) ** 2)
                                  + 1e-12)
        assert psnr(out) > psnr(noisy) + 10.0
        # detection recall on true impulses
        assert (np.asarray(mask)[imp] > 0).mean() > 0.95
        # paper: convergence within 10–30 iterations at these settings
        assert int(it) <= 60

    def test_amf_detect_pallas_matches_ref(self, rng):
        noisy = jnp.asarray(rng.uniform(size=(64, 128)), jnp.float32)
        m1, r1 = ops.adaptive_median_detect(noisy, use_pallas=True)
        m2, r2 = ops.adaptive_median_detect(noisy, use_pallas=False)
        np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))
        np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
