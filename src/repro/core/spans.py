"""The program's own names on the profiler's clock.

Three kinds, all ``repro.``-prefixed, none with a switch:

* host spans (:func:`span`): a ``jax.profiler.TraceAnnotation`` around a
  host call site, on the profiler's host plane beside the device ops of
  the same trace (near free when no trace is taken).  Never opened
  inside traced code: a span there would time tracing, not running;
* device scopes (:func:`scope`): a ``jax.named_scope`` inside traced
  code.  It lands in the ``op_name`` metadata of every HLO instruction
  the region lowers to, whatever XLA fuses it into, and changes no
  fusion.  A device trace names ops only by their HLO instruction names,
  so :func:`op_scopes` maps those names back to scopes from the compiled
  executable's text;
* counters (:data:`stats`): Python traces of the program's jitted
  entries (those built as :class:`Entry`), and the process's backend
  compiles and persistent-cache loads (``jax.monitoring`` listeners
  registered once, at import).

:class:`Entry` ties them together for a jitted entry point: its host call
opens ``repro.<name>``, its traced body counts a trace, and it sums the
traces, compiles and cache loads of the calls after its first.
"""
from __future__ import annotations

import functools
import re
import threading

import jax

PREFIX = "repro."

EVENTS = ("traces", "compiles", "cache_loads")

stats = dict.fromkeys(EVENTS, 0)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

_lock = threading.Lock()
_pending_loads = 0


def _on_duration(event: str, _seconds: float, **_) -> None:
    # JAX times a persistent-cache read inside the backend-compile
    # event that encloses it: the load is reported first, and the
    # enclosing event that follows it is no compile of its own
    global _pending_loads
    if event == CACHE_LOAD_EVENT:
        with _lock:
            stats["cache_loads"] += 1
            _pending_loads += 1
    elif event == COMPILE_EVENT:
        with _lock:
            if _pending_loads:
                _pending_loads -= 1
            else:
                stats["compiles"] += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def span(name: str):
    """Host span ``repro.<name>`` around a host call site."""
    return jax.profiler.TraceAnnotation(PREFIX + name)


def scope(name: str):
    """Device scope ``repro.<name>`` around traced code."""
    return jax.named_scope(PREFIX + name)


def counts() -> dict:
    """A copy of :data:`stats`, to difference against a later one."""
    with _lock:
        return dict(stats)


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+) .*\{\s*$")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _innermost(op_name: str):
    found = [c for c in op_name.split("/") if c.startswith(PREFIX)]
    return found[-1] if found else None


def op_scopes(compiled) -> dict:
    """``{instruction name: innermost repro.* scope}`` of a compiled
    executable (``jax.stages.Compiled``, or its HLO text), under the
    names a device trace prints (``broadcast_select_fusion.2``).  A
    fusion takes its root instruction's scope; instructions with no
    ``repro.*`` scope are left out."""
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    own, calls, roots = {}, {}, {}
    comp = None
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMP.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = _innermost(op.group(1)) if op else None
        if " fusion(" in line:
            called = _CALLS.search(line)
            if called:
                calls[name] = called.group(1)
        if line.lstrip().startswith("ROOT ") and comp is not None:
            roots[comp] = name

    def resolve(name):
        while calls.get(name) in roots:     # a fusion: its root's scope
            name = roots[calls[name]]
        return own[name]

    return {name: sc for name in own if (sc := resolve(name)) is not None}


class Entry:
    """A jitted entry point of the program under the name ``name``.

    Calling it opens the host span ``repro.<name>`` around the jitted
    call: dispatch, and any trace, lowering, compile or cache load the
    call triggers.  Its traced body adds one to ``stats["traces"]``.
    :attr:`after_first` sums the traces, compiles and cache loads of
    every call after the first: where the signature does not change,
    each one is a recompile.  :attr:`signature` is the abstract
    signature of the last call, shardings included, and
    :attr:`mixed` turns True once two calls' signatures differ, so
    that :meth:`op_scopes` can rebuild the one executable every call
    ran, and refuses where there was more than one.
    """

    def __init__(self, name: str, fun, *, static_argnames=()):
        self.name = name
        self.static = frozenset((static_argnames,)
                                if isinstance(static_argnames, str)
                                else static_argnames)
        self.calls = 0
        self.after_first = dict.fromkeys(EVENTS, 0)
        self.signature = None
        self.mixed = False

        @functools.wraps(fun)
        def traced(*args, **kwargs):
            with _lock:
                stats["traces"] += 1
            return fun(*args, **kwargs)

        self._jit = jax.jit(traced, static_argnames=tuple(self.static))
        functools.update_wrapper(self, fun)

    def __call__(self, *args, **kwargs):
        sig = (jax.tree.map(_abstract, args),
               {k: v if k in self.static else jax.tree.map(_abstract, v)
                for k, v in kwargs.items()})
        if self.signature is not None and sig != self.signature:
            self.mixed = True
        self.signature = sig
        before = counts()
        with span(self.name):
            out = self._jit(*args, **kwargs)
        if self.calls:
            after = counts()
            for k in EVENTS:
                self.after_first[k] += after[k] - before[k]
        self.calls += 1
        return out

    def lower(self, *args, **kwargs):
        """The inner jit's ``lower`` (ahead-of-time lowering)."""
        return self._jit.lower(*args, **kwargs)

    def op_scopes(self):
        """:func:`op_scopes` of the one executable every call of this
        entry ran, rebuilt from :attr:`signature` (a compile-cache
        hit); None before the first call or once the signature has
        changed."""
        if self.signature is None or self.mixed:
            return None
        args, kwargs = self.signature
        return op_scopes(self.lower(*args, **kwargs).compile())


def _abstract(x):
    """A traced argument as a ``ShapeDtypeStruct``, with its sharding
    where it is a ``jax.Array``."""
    aval = jax.typeof(x)
    return jax.ShapeDtypeStruct(aval.shape, aval.dtype,
                                weak_type=aval.weak_type,
                                sharding=getattr(x, "sharding", None))
