"""shard_map expert-parallel MoE ≡ GSPMD-auto dense path (subprocess,
8 placeholder devices) — modulo the documented capacity semantics."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "..", "src")


@pytest.mark.slow
def test_expert_parallel_matches_dense_dispatch():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, %r)
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.models.layers import init_moe, moe
        from repro.models.moe_parallel import expert_parallel_moe

        from repro.sharding.specs import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        E, D, F, topk = 8, 32, 64, 2
        params = init_moe(jax.random.PRNGKey(0), D, E, F, 1, 48, True,
                          jnp.float32)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(4, 16, D)) * 0.3, jnp.float32)

        # generous capacity => no drops on either path => exact match
        y_ref, aux_ref = moe(params, x, top_k=topk, dropless=True)
        with mesh:
            y_ep, aux_ep = jax.jit(lambda p, xx: expert_parallel_moe(
                p, xx, top_k=topk, act="silu", capacity_factor=8.0,
                mesh=mesh, dp_axes=("data",)))(params, x)
        err = np.abs(np.asarray(y_ep) - np.asarray(y_ref)).max()
        assert err < 2e-5, err
        # lb_loss uses per-data-shard statistics (mean of products !=
        # product of means): same expectation, small per-batch skew
        lb = abs(float(aux_ep["lb_loss"]) - float(aux_ref["lb_loss"]))
        assert lb < 0.05, lb
        print("OKMOE")
    """ % SRC)
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OKMOE" in out.stdout


@pytest.mark.slow
def test_expert_parallel_batch_one():
    """B=1 (long-context decode) runs token-replicated over data."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, %r)
        import jax, jax.numpy as jnp, numpy as np
        from repro.models.layers import init_moe, moe
        from repro.models.moe_parallel import expert_parallel_moe
        from repro.sharding.specs import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        params = init_moe(jax.random.PRNGKey(0), 32, 8, 64, 0, 0, True,
                          jnp.float32)
        x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 1, 32)),
                        jnp.float32)
        y_ref, _ = moe(params, x, top_k=2, dropless=True)
        with mesh:
            y_ep, _ = jax.jit(lambda p, xx: expert_parallel_moe(
                p, xx, top_k=2, act="silu", capacity_factor=8.0,
                mesh=mesh, dp_axes=("data",)))(params, x)
        assert np.abs(np.asarray(y_ep) - np.asarray(y_ref)).max() < 2e-5
        print("OKB1")
    """ % SRC)
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OKB1" in out.stdout
