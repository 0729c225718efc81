"""Jaxpr introspection of the pattern's loop body — test/bench tooling.

The zero-copy and communication-avoiding claims are *structural*: no
``pad``/array-sized ``concatenate``/full-block ``dynamic_slice`` inside
the ``while_loop`` body, and ppermute rounds per body that amortise over
``unroll`` fused sweeps.  This module is the single place that knows how
to dig those bodies out of a traced jaxpr (shared by
``tests/core/test_sharded.py``, ``tests/core/test_farm.py`` and
``tests/core/test_executor.py``-style inspections).
"""
from __future__ import annotations

import numpy as np

import jax


def subjaxprs(eq):
    """Nested sub-jaxprs of an equation (Jaxpr or ClosedJaxpr params)."""
    for v in eq.params.values():
        if hasattr(v, "eqns"):
            yield v
        elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
            yield v.jaxpr


def flatten_eqns(jx, out):
    """All eqns of ``jx`` including nested sub-jaxprs (pjit/scan/...),
    but NOT Pallas kernel bodies — those are VMEM-tile-internal, not
    HBM/ICI staging passes."""
    for eq in jx.eqns:
        out.append(eq)
        if eq.primitive.name == "pallas_call":
            continue
        for sub in subjaxprs(eq):
            flatten_eqns(sub, out)
    return out


def while_body_eqns(fn, *args):
    """Equations inside the while_loop bodies of fn's jaxpr, flattened
    through nested sub-jaxprs."""
    bodies = []

    def walk(jx):
        for eq in jx.eqns:
            if eq.primitive.name == "while":
                bodies.append(eq.params["body_jaxpr"].jaxpr)
                continue
            for sub in subjaxprs(eq):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    assert bodies, "no while_loop in jaxpr"
    eqns = []
    for body in bodies:
        flatten_eqns(body, eqns)
    return eqns


def count_primitive(eqns, name: str) -> int:
    return sum(e.primitive.name == name for e in eqns)


def max_outsize(eq) -> int:
    """Largest output array size of one equation (1 for scalars)."""
    return max(int(np.prod(v.aval.shape)) if v.aval.shape else 1
               for v in eq.outvars)
