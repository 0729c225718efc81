"""End-to-end behaviour tests for the paper's system: the three paper
applications run through the Loop-of-stencil-reduce machinery and produce
physically sensible results (paper §4 structure)."""
import jax.numpy as jnp
import numpy as np

from repro.core import FarmEngine, LoopOfStencilReduce
from repro.kernels import ops, ref as R


class TestHelmholtzApp:
    def test_converges_to_fixed_point(self, rng):
        """Jacobi fixed point satisfies the discrete Helmholtz relation."""
        n, alpha, dx = 48, 0.8, 0.1
        fxy = jnp.asarray(rng.normal(size=(n, n)), jnp.float32)
        u, delta, iters = ops.jacobi_solve(
            jnp.zeros((n, n), jnp.float32), fxy, alpha=alpha, dx=dx,
            tol=1e-6, max_iters=4000)
        # residual of (4+αdx²)u - Σneigh u - dx² f ≈ 0 at interior points
        up = jnp.pad(u, 1)
        neigh = (up[:-2, 1:-1] + up[2:, 1:-1] + up[1:-1, :-2]
                 + up[1:-1, 2:])
        res = (4 + alpha * dx * dx) * u - neigh - dx * dx * fxy
        assert float(jnp.abs(res[1:-1, 1:-1]).max()) < 1e-3
        assert int(iters) < 4000


class TestSobelApp:
    def test_stream_of_images(self, rng):
        """pipe(read, sobel, write) over a stream (paper §4.2): the
        pattern subsumes map, so the Sobel stage is a farm of one-sweep
        loops."""
        frames = [np.asarray(rng.uniform(size=(32, 64)), np.float32)
                  for _ in range(7)]
        sobel = LoopOfStencilReduce(
            f=R.sobel_taps(), k=1, combine="max", identity=-jnp.inf,
            cond=lambda r: True, boundary="reflect", max_iters=1)
        outs = []
        n = FarmEngine(sobel, lanes=3).run(frames, outs.append)
        assert n == 7
        assert sorted(r.index for r in outs) == list(range(7))
        for res in outs:
            assert res.status == "ok" and int(res.iters) == 1
            want, r = ops.sobel(jnp.asarray(frames[res.index]))
            np.testing.assert_allclose(res.a, np.asarray(want), atol=1e-5)
            np.testing.assert_allclose(res.reduced, float(r), rtol=1e-6)

    def test_edge_detector_finds_edges(self):
        img = np.zeros((40, 80), np.float32)
        img[:, 40:] = 1.0                      # vertical edge
        out, _ = ops.sobel(jnp.asarray(img))
        col_resp = np.asarray(out).mean(axis=0)
        assert col_resp[39:41].max() > 10 * (col_resp[:30].mean() + 1e-6)


class TestRestorationApp:
    def test_two_phase_pipeline(self, rng):
        """pipe(read, detect, ofarm(restore), write) (paper §4.3), one
        frame: detection, then restoration from the detected frame."""
        yy, xx = np.mgrid[0:48, 0:64]
        frame = np.clip(0.5 + 0.4 * np.sin(xx / 9.0) * np.cos(yy / 7.0),
                        0, 1).astype(np.float32)
        imp = rng.uniform(size=frame.shape) < 0.3
        sp = np.where(rng.uniform(size=frame.shape) < 0.5, 0.0, 1.0)
        noisy = jnp.asarray(np.where(imp, sp, frame), jnp.float32)

        mask, repaired = ops.adaptive_median_detect(noisy)
        restored, _, _ = ops.restore(repaired, mask, max_iters=50)

        def psnr(x):
            return -10 * np.log10(np.mean((np.asarray(x) - frame) ** 2)
                                  + 1e-12)
        assert psnr(restored) > psnr(noisy) + 8.0


class TestGameOfLife:
    def test_blinker_oscillates(self):
        """The paper's Fig. 1 example, through the core pattern."""
        a0 = np.zeros((8, 8), np.float32)
        a0[4, 3:6] = 1.0                      # horizontal blinker
        res = LoopOfStencilReduce(
            f=R.gol_taps(), k=1, combine="sum", identity=0.0,
            cond=lambda r: False, max_iters=2).run(jnp.asarray(a0))
        want = np.zeros((8, 8), np.float32)
        want[4, 3:6] = 1.0                    # period-2: back to start
        np.testing.assert_array_equal(np.asarray(res.a), want)
