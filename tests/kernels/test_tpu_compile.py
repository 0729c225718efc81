"""The main path's Pallas kernels compile for a described TPU v5e chip.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
scalar stores to VMEM, DMA windows or offsets off the (8, 128) tiling,
primitives without a TPU lowering, the generic Pallas batching rule on
HBM operands.  These tests hand the installed TPU compiler the kernels at
real widths for one chip of a described ``v5e:2x2`` topology, and the
1:n solve for all four — nothing runs — and check that each program holds
the kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import: one process
at a time may load the TPU library, and every worker imports this file.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.core import GridPartition
from repro.core.executor import StencilEngine
from repro.core.frames import frame_spec
from repro.core.pattern import LoopOfStencilReduce
from repro.kernels import ops
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


def test_amf_prep_720p_sorts_nothing(one_chip):
    """The stream farm's prep, adaptive median detection of one 720x1280
    frame on the jnp path: the compiled program holds no sort, and its
    temporaries stay under 128 MiB (a sort of the stacked windows took
    547,547,136 B; a network that kept a frame per comparator in HBM
    would take gigabytes)."""
    frame = jax.ShapeDtypeStruct((720, 1280), jnp.float32,
                                 sharding=one_chip)
    compiled = jax.jit(
        lambda fr: ops.adaptive_median_detect(fr, kmax=3)).lower(
            frame).compile()
    assert not re.search(r" sort\(", compiled.as_text())
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 128 * 2**20, temp


def test_swa_attention_hd128(one_chip):
    qkv = jax.ShapeDtypeStruct((8, 2048, 128), jnp.bfloat16,
                               sharding=one_chip)
    text = compiled_text(
        lambda q, k, v: swa_attention(q, k, v, window=512), qkv, qkv, qkv)
    assert "tpu_custom_call" in text


def hlo_computations(text: str) -> dict:
    """Instruction lines of each computation of an HLO module's text."""
    comps, cur = {}, None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            cur = re.match(r"(?:ENTRY )?%?([\w.\-]+)", line).group(1)
            comps[cur] = []
        elif cur is not None and line.startswith("  "):
            comps[cur].append(line.strip())
    return comps


def while_body_lines(text: str) -> list:
    """Instructions of every while body, and of the computations they
    call (fusions, branches), transitively."""
    comps = hlo_computations(text)
    todo = [re.search(r"body=%?([\w.\-]+)", line).group(1)
            for lines in comps.values() for line in lines
            if " while(" in line]
    assert todo, "no while loop in the executable"
    seen, out = set(), []
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps[name]:
            out.append(line)
            todo += re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", line)
            for group in re.findall(r"branch_computations=\{([^}]*)\}",
                                    line):
                todo += [c.strip().lstrip("%") for c in group.split(",")]
    return out


@pytest.mark.parametrize("backend,unroll", [("pallas", 1),
                                            ("pallas-multistep", 4)])
def test_solve_body_4096_swaps_frames(backend, unroll, one_chip):
    """The compiled loop of a 4096^2 Helmholtz solve: two kernel calls,
    each writing the frame the other read, and no frame-sized select or
    copy anywhere in the body."""
    loop = LoopOfStencilReduce(
        f=helmholtz(), k=1, combine="max", cond=lambda r: r < 1e-5,
        delta=R.abs_delta, boundary="zero", max_iters=2000, unroll=unroll,
        backend=backend, interpret=False)
    grid = jax.ShapeDtypeStruct((4096, 4096), jnp.float32,
                                sharding=one_chip)
    text = compiled_text(lambda u, f: loop.run(u, env=(f,)), grid, grid)
    spec = frame_spec(4096, 4096, k=1, block=(256, 256),
                      sweeps=unroll if backend == "pallas-multistep" else 1)
    frame = "f32[{},{}]".format(*spec.shape)
    body = while_body_lines(text)
    kernels = [line for line in body if "tpu_custom_call" in line]
    assert len(kernels) == 2
    frame_ops = [line for line in body
                 if re.match(r"(?:ROOT )?%\S+ = " + re.escape(frame)
                             + r"\S* (select|copy|copy-start)\(", line)]
    assert not frame_ops, frame_ops


def test_sharded_solve_32768_on_2x2(topo):
    """The 1:n solve of the benchmark's four-chip cell, 32768² split 2×2
    by rows and columns: each chip's loop holds two kernel calls that
    swap its 16384² shard's frames, with no frame-sized select or copy,
    exchanges halos by collective-permutes, combines the reduce by
    all-reduces, and fits the chip's 16 GB."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("rows", "cols"))
    part = GridPartition(mesh=mesh, axis_names=("rows", "cols"),
                         array_axes=(0, 1))
    loop = LoopOfStencilReduce(
        f=helmholtz(), k=1, combine="max", cond=lambda r: r < 1e-5,
        delta=R.abs_delta, boundary="zero", max_iters=2000,
        backend="pallas-sharded", partition=part, interpret=False)
    grid = jax.ShapeDtypeStruct(
        (32768, 32768), jnp.float32,
        sharding=NamedSharding(part.mesh, part.pspec))
    compiled = jax.jit(lambda u, f: loop.run(u, env=(f,)).a).lower(
        grid, grid).compile()
    text = compiled.as_text()
    frame = "f32[{},{}]".format(*frame_spec(16384, 16384).shape)
    body = while_body_lines(text)
    assert len([ln for ln in body if "tpu_custom_call" in ln]) == 2
    assert not [ln for ln in body
                if re.match(r"(?:ROOT )?%\S+ = " + re.escape(frame)
                            + r"\S* (select|copy|copy-start)\(", ln)]
    assert any("collective-permute" in ln for ln in body)
    assert any("all-reduce" in ln for ln in body)
    mem = compiled.memory_analysis()
    per_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
    assert per_chip < 16e9, per_chip
