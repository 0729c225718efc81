"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run driver sets XLA_FLAGS before any jax initialisation).

Topology (TPU v5e-class):
    single pod : (16, 16)     axes ("data", "model")   = 256 chips
    multi-pod  : (2, 16, 16)  axes ("pod", "data", "model") = 512 chips

The "model" axis is mapped innermost so tensor-parallel collectives stay
on the shortest ICI rings; the "pod" axis carries only the gradient
all-reduce (data-parallel across pods, over the slow inter-pod links).

Meshes are built through :func:`repro.sharding.specs.make_mesh`, the
repo's one mesh constructor (every axis ``Auto``).
"""
from __future__ import annotations

from jax.sharding import Mesh

from repro.sharding.specs import make_mesh


def make_production_mesh(*, multi_pod: bool = False,
                         pod_shape: tuple[int, int] | None = None) -> Mesh:
    """``pod_shape`` overrides the (data, model) factorisation of the 256
    chips in a pod — the TP:DP trade is a first-class tuning knob (the
    §Perf hillclimb shows collective-bound dense models want less TP)."""
    dm = pod_shape or (16, 16)
    assert dm[0] * dm[1] == 256, "a pod is 256 chips"
    shape = (2, *dm) if multi_pod else dm
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over however many (fake) devices the test process has."""
    return make_mesh((data, model), ("data", "model"))
