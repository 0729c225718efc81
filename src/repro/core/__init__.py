"""repro.core — the Loop-of-stencil-reduce pattern (paper's contribution).

Public API:
    semantics   — executable formal semantics (test oracle)
    stencil     — production stencil application (taps / windows / indexed)
    reduce      — /(⊕) tree reduce + two-phase reduce
    pattern     — LoopOfStencilReduce + -i/-d/-s variants (lax.while_loop)
    halo        — multi-device 1:n mode (shard_map + ppermute halo swap)
    streaming   — FarmEngine, the lane-resident streaming farm
                  (persistent-frame lane slots refilled in place as
                  their loops finish, results in completion order)
"""
from .semantics import Boundary
from .stencil import TapAccessor, stencil_taps, stencil_windows, conv_taps
from .reduce import (tree_reduce, two_phase_reduce, collective_combine,
                     MONOIDS, Sentinel, health_status)
from .pattern import (LoopOfStencilReduce, LoopResult, loop_of_stencil_reduce,
                      loop_of_stencil_reduce_d, loop_of_stencil_reduce_s)
from .halo import (GridPartition, exchange_halo,
                   distributed_loop_of_stencil_reduce)
from .streaming import (FarmEngine, StreamResult, NonFiniteItemError,
                        item_status)

__all__ = [
    "Boundary", "TapAccessor", "stencil_taps", "stencil_windows",
    "conv_taps", "tree_reduce", "two_phase_reduce", "collective_combine",
    "MONOIDS", "LoopOfStencilReduce", "LoopResult",
    "loop_of_stencil_reduce", "loop_of_stencil_reduce_d",
    "loop_of_stencil_reduce_s", "GridPartition", "exchange_halo",
    "distributed_loop_of_stencil_reduce", "FarmEngine", "StreamResult",
    "Sentinel", "health_status", "NonFiniteItemError", "item_status",
]
