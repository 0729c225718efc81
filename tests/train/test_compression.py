"""int8 + error-feedback gradient compression (pod-axis reduction)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis "
                    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.train.compression import quantize_int8

SRC = os.path.join(os.path.dirname(__file__), "..", "..", "src")


class TestQuantize:
    @settings(max_examples=30, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(1, 4))
    def test_error_bounded_by_half_step(self, seed, peers):
        x = jnp.asarray(np.random.default_rng(seed)
                        .normal(size=(64,)).astype(np.float32))
        q, scale = quantize_int8(x, peers)
        err = np.abs(np.asarray(q, np.float32) * float(scale)
                     - np.asarray(x))
        assert err.max() <= float(scale) * 0.5 + 1e-6
        assert q.dtype == jnp.int8

    def test_overflow_safe_for_n_peers(self):
        x = jnp.full((8,), 123.0)
        q, _ = quantize_int8(x, 2)
        assert int(np.abs(np.asarray(q)).max()) <= 63   # 127 // 2


@pytest.mark.slow
def test_ef_psum_unbiased_over_steps():
    """Across repeated steps, error feedback recovers the exact mean:
    cumulative compressed sum → cumulative true sum."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import sys
        sys.path.insert(0, %r)
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.sharding.specs import make_mesh
        from repro.train.compression import ef_int8_psum
        mesh = make_mesh((2,), ("pod",))
        rng = np.random.default_rng(0)
        gs = jnp.asarray(rng.normal(size=(2, 20, 256)).astype(np.float32))

        def run(gs_local):
            gs_local = gs_local[0]      # shard_map keeps a size-1 lead dim
            def body(err, g):
                s, err = ef_int8_psum(g, err, "pod")
                return err, s
            err0 = jnp.zeros((256,), jnp.float32)
            _, sums = jax.lax.scan(body, err0, gs_local)
            return sums
        f = jax.shard_map(run, mesh=mesh, in_specs=P("pod", None, None),
                          out_specs=P(None, None), check_vma=False)
        sums = f(gs)                      # (20, 256) compressed psums
        true = gs.sum(axis=0)             # (20, 256) exact per-step sums
        cum_c = np.cumsum(np.asarray(sums), axis=0)
        cum_t = np.cumsum(np.asarray(true), axis=0)
        # error feedback: cumulative drift stays bounded by ~one quant
        # step, so the RELATIVE error shrinks with the horizon
        rel = np.abs(cum_c[-1] - cum_t[-1]).max() / (
            np.abs(cum_t[-1]).max() + 1e-9)
        assert rel < 0.02, rel
        # and per-step compressed sums track the truth coarsely
        assert np.corrcoef(cum_c[-1], cum_t[-1])[0, 1] > 0.999
        print("OKEF")
    """ % SRC)
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OKEF" in out.stdout
