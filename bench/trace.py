"""Reduce a profiler trace to the benchmark's device numbers.

The JAX profiler writes one ``.xplane.pb`` per traced run.  A TPU's plane
is named ``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per HLO
operation the chip ran, named by the operation's HLO text
(``%stencil2d_fused_framed.8 = (...) custom-call(...)``).  A ``while``
appears there as an event that encloses the events of its body, so only
the innermost events (leaves) are counted per operation.  Its
``XLA Modules`` line holds one event per run of an executable, named by
the executable (``jit__stage_impl(<fingerprint>)``).  The host plane
``/host:CPU`` holds the benchmark's own spans, ``bench.<name>``
(:func:`bench.common.span`), and the program's, ``repro.<name>``;
``bench.window`` marks the measured window.

Everything below works on plain tuples, so the tests can feed recorded
and hand-made traces alike:

* device busy time: the union of a device's op intervals in the window;
* time per named kernel: the leaf events whose operation name holds the
  kernel's name (the lane-batched kernel is ``vmap_<name>_``);
* time per executable: the leaf events inside the runs of the
  executables of one name;
* exposed collective time: collectives (collective-permute, all-reduce,
  ...) during which no other operation runs on that device.  A
  synchronous collective is an op of ``XLA Ops``; an asynchronous one
  spans its start and done on the ``Async XLA Ops`` line, in flight
  beside the compute it overlaps;
* idle gaps: the holes of the busy union, each named by the innermost
  program span open over most of it, or else by the innermost benchmark
  span.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
PROGRAM_PREFIX = "repro."
WINDOW_SPAN = "bench.window"
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather",
               "reduce-scatter", "all-to-all", "send", "recv")


@dataclasses.dataclass
class Trace:
    """A reduced trace: per device the leaf ops ``(name, start, end)`` in
    ns, the asynchronous collectives in flight and the executables' runs
    ``(executable, start, end)``, the benchmark's and the program's host
    spans, and the window ``(start, end)``."""
    devices: dict
    spans: list
    window: tuple
    async_collectives: dict = dataclasses.field(default_factory=dict)
    modules: dict = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def op_name(hlo_text: str) -> str:
    """``%stencil2d_fused_framed.8 = (...)`` -> ``stencil2d_fused_framed.8``."""
    return hlo_text.split(" = ", 1)[0].strip().lstrip("%")


def op_kind(name: str) -> str:
    """An operation's name without its HLO instance number."""
    base, _, num = name.rpartition(".")
    return base if base and num.isdigit() else name


def leaves(events):
    """The events that enclose no other event (a ``while`` encloses its
    body's operations).  ``events``: ``(name, start, end)`` tuples."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, ev in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and nxt[1] < ev[2] and nxt[2] <= ev[2]:
            continue
        out.append(ev)
    return out


def module_name(event_name: str) -> str:
    """``jit__stage_impl(8817...)`` -> ``jit__stage_impl``."""
    return event_name.split("(", 1)[0]


def from_events(devices: dict, spans: list, window=None,
                async_ops=None, modules=None) -> Trace:
    """Build a :class:`Trace` from raw ``(name, start_ns, end_ns)`` events
    per device (and per device the ``Async XLA Ops`` and ``XLA Modules``
    events) and host spans; ops are reduced to leaves and clipped to the
    window (by default the ``bench.window`` span, else the span of all
    device events)."""
    if window is None:
        win = [s for s in spans if s[0] == WINDOW_SPAN]
        if win:
            window = (win[0][1], win[0][2])
        else:
            allev = [e for evs in devices.values() for e in evs]
            window = (min(e[1] for e in allev), max(e[2] for e in allev))
    lo, hi = window

    def clip(evs):
        return [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                if e > lo and s < hi]
    return Trace(devices={d: clip(leaves(evs))
                          for d, evs in devices.items()},
                 spans=list(spans), window=(lo, hi),
                 async_collectives={
                     d: clip([e for e in evs if is_collective(e[0])])
                     for d, evs in (async_ops or {}).items()},
                 modules={d: clip([(module_name(n), s, e)
                                   for n, s, e in evs])
                          for d, evs in (modules or {}).items()})


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file, or the newest one under a directory."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    return from_profile(ProfileData.from_file(path))


def from_profile(data) -> Trace:
    """Reduce a ``jax.profiler.ProfileData``."""
    devices, async_ops, modules, spans = {}, {}, {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            rest = plane.name[len(DEVICE_PREFIX):]
            if not rest.isdigit():
                continue
            lines = {line.name: [(op_name(e.name), e.start_ns, e.end_ns)
                                 for e in line.events]
                     for line in plane.lines
                     if line.name in (OPS_LINE, ASYNC_LINE, MODULES_LINE)}
            devices[plane.name] = lines.get(OPS_LINE, [])
            async_ops[plane.name] = lines.get(ASYNC_LINE, [])
            modules[plane.name] = lines.get(MODULES_LINE, [])
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith((SPAN_PREFIX,
                                                   PROGRAM_PREFIX)))
    if not devices:
        raise ValueError(f"no {DEVICE_PREFIX}<n> plane in the trace")
    return from_events(devices, spans, async_ops=async_ops,
                       modules=modules)


def union(intervals):
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b):
    """Disjoint sorted intervals ``a`` minus disjoint sorted ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def intersect(a, b):
    """Disjoint sorted intervals ``a`` within disjoint sorted ``b``."""
    return subtract(a, subtract(a, b))


def busy_s(tr: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    return sum(length(union((s, e) for _, s, e in evs))
               for evs in tr.devices.values()) * 1e-9 / len(tr.devices)


def matches(name: str, kernel: str) -> bool:
    return kernel in op_kind(name)


def kernel_time(tr: Trace, kernel: str):
    """``(calls, seconds)`` of a named kernel, summed over the devices."""
    calls, ns = 0, 0.0
    for evs in tr.devices.values():
        for n, s, e in evs:
            if matches(n, kernel):
                calls += 1
                ns += e - s
    return calls, ns * 1e-9


def module_ops_s(tr: Trace, module: str, ops=None) -> float:
    """Seconds in which a leaf op ran inside a run of the executable
    ``module`` (``jit__stage_impl``), averaged over the devices; only
    the ops whose names ``ops`` holds, where it is given."""
    total = 0.0
    for dev, evs in tr.devices.items():
        runs = union((s, e) for n, s, e in tr.modules.get(dev, [])
                     if n == module)
        busy = union((s, e) for n, s, e in evs
                     if ops is None or n in ops)
        total += length(intersect(busy, runs))
    return total * 1e-9 / len(tr.devices)


def is_collective(name: str) -> bool:
    kind = op_kind(name)
    return any(kind.startswith(c) for c in COLLECTIVES)


def busy_outside_s(tr: Trace, kernels) -> float:
    """Seconds in which some operation other than ``kernels`` ran and no
    kernel did, averaged over the devices."""
    total = 0.0
    for evs in tr.devices.values():
        ker = union((s, e) for n, s, e in evs
                    if any(matches(n, k) for k in kernels))
        other = union((s, e) for n, s, e in evs
                      if not any(matches(n, k) for k in kernels))
        total += length(subtract(other, ker))
    return total * 1e-9 / len(tr.devices)


def exposed_collective_s(tr: Trace) -> float:
    """Seconds of collective operations with no other operation running
    on that device, averaged over the devices."""
    total = 0.0
    for dev, evs in tr.devices.items():
        coll = union([(s, e) for n, s, e in evs if is_collective(n)]
                     + [(s, e) for _, s, e in
                        tr.async_collectives.get(dev, [])])
        rest = union((s, e) for n, s, e in evs if not is_collective(n))
        total += length(subtract(coll, rest))
    return total * 1e-9 / len(tr.devices)


def idle_gaps(tr: Trace, top: int = 10):
    """The ``top`` longest holes of the busy union inside the window, over
    all devices, each as ``[span, seconds]``: of the spans that cover
    most of the hole, the innermost program span (``repro.*``), else the
    innermost benchmark span other than the window, else ``"none"``."""
    gaps = []
    lo, hi = tr.window
    for evs in tr.devices.values():
        busy = union((s, e) for _, s, e in evs)
        gaps.extend(subtract([(lo, hi)], busy))
    gaps.sort(key=lambda g: g[0] - g[1])
    inner = [sp for sp in tr.spans if sp[0] != WINDOW_SPAN]
    out = []
    for s, e in gaps[:top]:
        # of the spans open over most of the hole, the shortest, the
        # program's before the benchmark's
        cover = [(not name.startswith(PROGRAM_PREFIX), se - ss, name)
                 for name, ss, se in inner
                 if min(e, se) - max(s, ss) > 0.5 * (e - s)]
        out.append([min(cover)[2] if cover else "none", (e - s) * 1e-9])
    return out


def top_ops(tr: Trace, top: int = 10):
    """The ``top`` operation kinds by device time, ``[kind, seconds]``,
    averaged over the devices."""
    per = collections.Counter()
    for evs in tr.devices.values():
        for n, s, e in evs:
            per[op_kind(n)] += e - s
    return [[k, v * 1e-9 / len(tr.devices)]
            for k, v in per.most_common(top)]
