"""Benchmark utilities: timing + the paper's deployment comparisons.

The paper's GPU-vs-CPU columns become structure-vs-structure comparisons
on this host: the *naïve* deployment (host-driven loop, full D2H+H2D
round-trip per iteration — the strawman of §3.3) against the *persistent*
deployment (the Loop-of-stencil-reduce while_loop, device memory
persistence) across the engine's backend axis, and 1-device vs 1:n
over this process's devices.  Wall-clock ratios, not absolute
times, carry the claims.

Every suite emits ``record`` dicts — one per configuration — which the
harness (:mod:`benchmarks.run`) prints as CSV *and* dumps as
machine-readable ``BENCH_<suite>.json`` so the perf trajectory is
tracked across PRs.
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable

import jax
import numpy as np


def time_fn(fn: Callable, *args, warmup: int = 1, iters: int = 3,
            **kw) -> float:
    """Median wall-time in seconds (blocking on the result)."""
    for _ in range(warmup):
        r = fn(*args, **kw)
        jax.block_until_ready(r)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        r = fn(*args, **kw)
        jax.block_until_ready(r)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def record(name: str, seconds: float, *, backend: str = "", unroll: int = 1,
           mesh: str = "1", gbps: float | None = None,
           derived: str = "") -> dict:
    """One benchmark result row (the BENCH_summary.json record schema).

    ``mesh`` is the device-mesh axis ("1" = single device, "8x1" = an
    8-way 1-D decomposition, ...) so the perf trajectory distinguishes
    deployments, not just backends.
    """
    return {"name": name, "backend": backend, "unroll": unroll,
            "mesh": mesh, "seconds": seconds,
            "gbps": None if gbps is None else round(gbps, 3),
            "derived": derived}


def stencil_gbps(size: int, iters: int, seconds: float,
                 arrays_per_iter: int = 3, bytes_per_cell: int = 4) -> float:
    """Effective (algorithmic) bandwidth of an iterated 2-D stencil:
    ``arrays_per_iter`` full-grid HBM streams per iteration (read + write
    + env by default), regardless of what the backend actually moved —
    so temporal blocking shows up as *higher* effective GB/s."""
    return arrays_per_iter * bytes_per_cell * size * size * iters \
        / max(seconds, 1e-12) / 1e9


def csv_row(rec: dict) -> str:
    """CSV line (``name,us_per_call,derived``) for a record dict."""
    tags = [t for t in (rec["backend"],
                        f"T={rec['unroll']}" if rec["unroll"] > 1 else "",
                        f"mesh={rec['mesh']}"
                        if rec.get("mesh", "1") != "1" else "",
                        f"{rec['gbps']}GB/s" if rec["gbps"] else "",
                        rec["derived"]) if t]
    # negative seconds is the failure sentinel: keep the literal '-1'
    # the CSV contract (and run.py's own suite-error line) uses
    us = "-1" if rec["seconds"] < 0 else f"{rec['seconds'] * 1e6:.1f}"
    return f"{rec['name']},{us},{';'.join(tags)}"


SUMMARY_SCHEMA = 1


def write_summary(suite_rows: dict, out_dir: str = ".") -> str:
    """Merge every suite's records into ONE schema-stable
    BENCH_summary.json (replaces the per-suite BENCH_<suite>.json
    scatter) — diff this single file across PRs to read the perf
    trajectory.  ``suite_rows`` maps suite name -> list of record dicts.
    """
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "BENCH_summary.json")
    records = []
    for suite, recs in suite_rows.items():
        for r in recs:
            records.append({"suite": suite, **r})
    payload = {"schema": SUMMARY_SCHEMA,
               "jax_backend": jax.default_backend(),
               "records": records}
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=1)
        fh.write("\n")
    return path
