"""Types the harness shares with the drivers and the metric readers."""
from __future__ import annotations

import dataclasses
from typing import Any, Optional


@dataclasses.dataclass
class Window:
    """What one measured window gives: the end-to-end numbers measured on
    the host clock, the requests attempted and failed, and the counts the
    per-layer readers take (engine counters, iteration counts)."""
    e2e: dict
    attempted: int
    failed: int
    counters: dict


@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Context:
    """What a per-layer reader sees: the reduced device trace (None when
    the run was not traced), the window's counters, the cell's
    configuration, and the device's peaks."""
    trace: Optional[Any]
    counters: dict
    config: dict
    peaks: Optional[dict]


def span(name: str):
    """A host span of the benchmark's own, written into the profiler's
    trace as ``bench.<name>`` (near free when no trace is taken)."""
    import jax
    return jax.profiler.TraceAnnotation(f"bench.{name}")
