"""Parallelism policy: param/activation/optimizer PartitionSpecs per arch.

Axes (production mesh, launch/mesh.py):
    pod    — data parallelism across pods (slow inter-pod links carry only
             the gradient all-reduce)
    data   — in-pod data parallelism + ZeRO-1 optimizer-state sharding +
             sequence sharding for the 500k decode cells
    model  — tensor parallelism (vocab / heads / d_ff / experts) and
             KV-cache sequence sharding for decode

Rules are divisibility-aware: e.g. K/V heads shard on the model axis only
when ``kv_heads % tp == 0``; otherwise the head_dim (always a multiple of
16 across the assigned archs) is sharded so K/V stay tensor-parallel
without GSPMD padding.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig


def make_mesh(axis_shapes: Sequence[int],
              axis_names: Sequence[str]) -> Mesh:
    """The repo's one mesh constructor: ``jax.make_mesh`` with every axis
    ``Auto`` (GSPMD-propagated shardings, which the engine's
    ``shard_map`` programs are written for)."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def auto_mesh(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis ``Auto``.  ``jax.make_mesh`` defaults to
    ``Explicit`` axes, under which host-side indexing of a lane-sharded
    buffer is a sharding type error; the engines normalise any mesh they
    are handed through here."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def shard_map(f: Callable, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` without replication checking — every SPMD entry
    point in the repo (halo exchange, sharded engine, MoE dispatch) goes
    through here."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def local_slot(idx, lanes_local: int, axis: str):
    """Map a GLOBAL lane-slot index onto this shard of mesh ``axis``.

    Runs inside ``shard_map``: each lane shard owns ``lanes_local``
    consecutive slots, so slot ``idx`` lives at local index
    ``idx - axis_index * lanes_local`` on exactly one shard.  Returns
    ``(owns, local_idx)`` with ``local_idx`` clipped into range — always
    safe to index with, while ``owns`` masks the actual write (the
    owner-masked scatter of the composed continuous farm refill,
    :func:`repro.core.frames.refill_slot_frame_sharded`).  Pure local
    arithmetic: no collective touches the lane axis.
    """
    me = jax.lax.axis_index(axis)
    li = idx - me * lanes_local
    owns = jnp.logical_and(li >= 0, li < lanes_local)
    return owns, jnp.clip(li, 0, lanes_local - 1)


@dataclasses.dataclass(frozen=True)
class GridPartition:
    """How a global stencil grid maps onto the device mesh (1:n mode).

    Frozen (hashable) so apps can carry it as a jit-static argument.
    ``axis_names`` are mesh axes; ``array_axes`` the array axes they split
    ("evenly for 1D array and by rows for 2D matrix", paper §3.4).
    """
    mesh: Mesh
    axis_names: tuple[str, ...]      # mesh axes carrying the decomposition
    array_axes: tuple[int, ...]      # which array axes they split

    def __post_init__(self):
        object.__setattr__(self, "mesh", auto_mesh(self.mesh))
        object.__setattr__(self, "axis_names", tuple(self.axis_names))
        object.__setattr__(self, "array_axes", tuple(self.array_axes))

    @property
    def pspec(self) -> P:
        spec = [None] * (max(self.array_axes) + 1)
        for name, ax in zip(self.axis_names, self.array_axes):
            spec[ax] = name
        return P(*spec)

    def axis_size(self, name: str) -> int:
        return self.mesh.shape[name]

    @property
    def shards(self) -> tuple[int, ...]:
        """Decomposition arity per decomposed array axis."""
        return tuple(self.axis_size(n) for n in self.axis_names)


def _div(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


def dp_axes(mesh: Mesh):
    """The data-parallel axes: ('pod','data') multi-pod, ('data',) single."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def mesh_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis] if axis in mesh.axis_names else 1


def param_spec(cfg: ArchConfig, path: str, shape, mesh: Mesh) -> P:
    """PartitionSpec for one parameter leaf, identified by its tree path.

    ``shape`` includes the leading unit-stack (reps) axis for scanned
    leaves; the path contains 'unit' in that case.
    """
    tp = mesh_size(mesh, "model")
    stacked = "unit" in path and "cache" not in path
    off = 1 if stacked else 0           # skip the layer-stack axis
    dims = list(shape)
    spec = [None] * len(dims)

    def set_if(idx, cond=True):
        if cond and _div(dims[idx], tp):
            spec[idx] = "model"
            return True
        return False

    if "embed" in path and "pos" not in path and "patch" not in path:
        # token embedding (V, D) / unembed (D, V): shard the vocab dim
        vdim = int(np.argmax(dims))
        set_if(vdim)
    elif any(k in path for k in ("wq", "wk", "wv", "wo")):
        # Megatron-style GQA TP.  Heads dims (H on wq/wo, KH on wk/wv)
        # shard on the model axis when divisible; when KH < tp the K/V
        # projections REPLICATE (classic GQA replication — keeps the
        # scores einsum head-sharded with no giant score all-reduce);
        # when even H < tp (whisper), every projection shards head_dim
        # so q·k contracts a sharded dim instead.
        h_dim = off + (0 if "wo" in path else 1)   # H/KH position
        d_dim = off + (1 if "wo" in path else 2)   # head_dim position
        is_kv = ("wk" in path) or ("wv" in path)
        nh = dims[h_dim]
        if cfg.attn_sequence_parallel:
            pass          # context-parallel attention: weights replicated,
            #               the sequence shards on the model axis instead
        elif _div(nh, tp):
            spec[h_dim] = "model"
        elif is_kv:
            pass                                   # replicate K/V heads
        else:
            set_if(d_dim)
    elif any(k in path for k in ("w_up", "w_gate", "w_down")):
        set_if(off + 0)                  # expert-parallel: experts axis
    elif "router" in path:
        pass                             # tiny; replicate
    elif "up" in path or "gate" in path:
        set_if(off + 1)                  # (D, F): column parallel
    elif "down" in path:
        set_if(off + 0)                  # (F, D): row parallel
    elif "in_proj" in path:
        set_if(off + 1) or set_if(off + 0)   # (D, d_proj)
    elif "out_proj" in path:
        set_if(off + 0) or set_if(off + 1)   # (d_inner, D)
    elif "vision_proj" in path:
        set_if(off + 1)
    # norms / biases / conv / A_log / dt_bias / pos_embed: replicated
    return P(*spec)


def zero1_spec(spec: P, shape, mesh: Mesh) -> P:
    """Extend a param spec with ZeRO-1 optimizer-state sharding: shard the
    largest still-unsharded dim divisible by the data axis."""
    dz = mesh_size(mesh, "data")
    if dz == 1:
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))
    cands = [(shape[i], i) for i, s in enumerate(entries)
             if s is None and _div(shape[i], dz) and shape[i] >= dz]
    if not cands:
        return P(*entries)
    _, idx = max(cands)
    entries[idx] = "data"
    return P(*entries)


def _path_str(kp) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in kp)


def params_shardings(cfg: ArchConfig, params_shape, mesh: Mesh):
    """NamedSharding pytree for the params (shape pytree or real params)."""
    def one(kp, leaf):
        return NamedSharding(
            mesh, param_spec(cfg, _path_str(kp), leaf.shape, mesh))
    return jax.tree_util.tree_map_with_path(one, params_shape)


def opt_shardings(cfg: ArchConfig, opt_shape, mesh: Mesh):
    """AdamState shardings: step replicated; master/m/v = param spec +
    ZeRO-1 over the data axis."""
    def one(kp, leaf):
        path = _path_str(kp)
        if leaf.ndim == 0:
            return NamedSharding(mesh, P())
        base = param_spec(cfg, path, leaf.shape, mesh)
        return NamedSharding(mesh, zero1_spec(base, leaf.shape, mesh))
    return jax.tree_util.tree_map_with_path(one, opt_shape)


def batch_spec(mesh: Mesh, batch_size: int, ndim: int = 2) -> P:
    """Shard the batch dim over every data-parallel axis that divides it."""
    axes = [a for a in dp_axes(mesh)]
    use = []
    rem = batch_size
    for a in axes:
        n = mesh_size(mesh, a)
        if rem % n == 0 and rem >= n:
            use.append(a)
            rem //= n
    lead = tuple(use) if use else None
    return P(lead, *([None] * (ndim - 1)))


def cache_shardings(cfg: ArchConfig, cache_shape, mesh: Mesh,
                    batch_size: int, seq_shard: bool = True):
    """Decode-cache shardings.

    KV caches (reps, B, S, KH, hd): batch over the dp axes; the cache
    *sequence* over the model axis (flash-decode style load balancing —
    every chip holds a slice of every head's history).  When B == 1
    (long_500k) the data axis joins the sequence sharding instead.
    SSM caches: batch over dp only (state is O(1), nothing else to shard).
    """
    dp = [a for a in dp_axes(mesh) if _div(batch_size, mesh_size(mesh, a))]
    # compose multi-axis batch sharding only while divisible
    bs = []
    rem = batch_size
    for a in dp:
        if rem % mesh_size(mesh, a) == 0:
            bs.append(a)
            rem //= mesh_size(mesh, a)
    seq_axes = ["model"] if seq_shard else []
    if batch_size == 1:
        seq_axes = [a for a in dp_axes(mesh)] + seq_axes if seq_shard \
            else []
        bs = []

    def one(kp, leaf):
        path = _path_str(kp)
        spec = [None] * leaf.ndim
        if leaf.ndim >= 2:
            spec[1] = tuple(bs) if bs else None       # batch dim
        if "conv" in path or path.endswith("h"):      # ssm caches
            return NamedSharding(mesh, P(*spec))
        seq_ok = (seq_axes and leaf.ndim >= 3 and all(
            _div(leaf.shape[2], mesh_size(mesh, a)) for a in seq_axes))
        if leaf.ndim == 5 and seq_ok:                 # (reps,B,S,KH,hd)
            spec[2] = tuple(seq_axes)
        elif leaf.ndim == 4 and "scale" in path and seq_ok:
            spec[2] = tuple(seq_axes)                 # int8 scales
        return NamedSharding(mesh, P(*spec))
    return jax.tree_util.tree_map_with_path(one, cache_shape)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())
