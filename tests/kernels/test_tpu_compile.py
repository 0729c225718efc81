"""The main path's Pallas kernels compile for a described TPU v5e chip.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
scalar stores to VMEM, DMA windows or offsets off the (8, 128) tiling,
primitives without a TPU lowering, the generic Pallas batching rule on
HBM operands.  These tests hand the installed TPU compiler the kernels at
real widths for one chip of a described ``v5e:2x2`` topology — nothing
runs — and check that each program holds the kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import: one process
at a time may load the TPU library, and every worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.executor import StencilEngine
from repro.core.frames import frame_spec
from repro.kernels import ref as R
from repro.kernels.multistep import stencil2d_multistep_framed
from repro.kernels.stencil2d import stencil2d_fused_framed
from repro.kernels.swa_attention import swa_attention


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler or topology here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def helmholtz():
    return R.helmholtz_jacobi_taps(0.1, 1.0)


def test_fused_kernel_4096(one_chip):
    spec = frame_spec(4096, 4096, k=1, block=(256, 256))
    frame = jax.ShapeDtypeStruct(spec.shape, jnp.float32, sharding=one_chip)
    env = jax.ShapeDtypeStruct(spec.interior, jnp.float32,
                               sharding=one_chip)
    text = compiled_text(
        lambda fr, e: stencil2d_fused_framed(
            fr, helmholtz(), spec, env_framed=(e,), combine="max",
            measure=R.abs_delta), frame, env)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("boundary", ["zero", "reflect"])
def test_multistep_kernel_T4(boundary, one_chip):
    spec = frame_spec(4096, 4096, k=1, block=(256, 256), sweeps=4)
    frame = jax.ShapeDtypeStruct(spec.shape, jnp.float32, sharding=one_chip)
    text = compiled_text(
        lambda fr, e: stencil2d_multistep_framed(
            fr, helmholtz(), spec, T=4, env_framed=(e,), combine="max",
            measure=R.abs_delta, boundary=boundary), frame, frame)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("backend", ["pallas", "pallas-multistep"])
def test_lane_sweep_720p(backend, one_chip):
    """The farm's vmapped lane sweep, 8 lanes at 720x1280: restoration
    (reflect ⊥, two env fields) on the single-step kernel, Helmholtz
    (zero ⊥) at T=4 on the temporal-blocking one."""
    if backend == "pallas":
        eng = StencilEngine(f=R.restore_taps(2.0), k=1, boundary="reflect",
                            combine="max", delta=R.abs_delta,
                            backend=backend, interpret=False)
        n_env = 2
    else:
        eng = StencilEngine(f=helmholtz(), k=1, boundary="zero",
                            combine="max", delta=R.abs_delta,
                            backend=backend, unroll=4, interpret=False)
        n_env = 1
    lspec = eng.lane_spec(8, 720, 1280)
    env_shape = lspec.frame.shape if eng._halo_env else lspec.frame.interior
    frames = jax.ShapeDtypeStruct(lspec.shape, jnp.float32,
                                  sharding=one_chip)
    env = jax.ShapeDtypeStruct((8, *env_shape), jnp.float32,
                               sharding=one_chip)
    text = compiled_text(
        lambda fr, *e: eng.sweeps_lanes(fr, e, lspec),
        frames, *[env] * n_env)
    assert "tpu_custom_call" in text


def test_swa_attention_hd128(one_chip):
    qkv = jax.ShapeDtypeStruct((8, 2048, 128), jnp.bfloat16,
                               sharding=one_chip)
    text = compiled_text(
        lambda q, k, v: swa_attention(q, k, v, window=512), qkv, qkv, qkv)
    assert "tpu_custom_call" in text
