"""The restoration farm against the plain reference.

A handful of 70%-noise frames (``bench/generators.py``) stream through
``FarmEngine`` with the adaptive median detection as its ``prep`` and
the regularisation sweep as its loop, on both dispatchers and both
array backends, at one lane and at more lanes than the frames need.
Every frame is held to ``bench/reference_restore.py``, which imports
nothing of the program, under the limits of the benchmark's
``restore-720p`` configuration: the result within ``restore_err``, the
sweep count within ``iters_gap``, and every frame emitted exactly once.
"""
import functools
import json
import os

import numpy as np
import pytest

from bench import generators, reference_restore
from repro.core import FarmEngine, LoopOfStencilReduce
from repro.kernels import ops, ref as R

CONFIG = os.path.join(os.path.dirname(__file__), "..", "..", "bench",
                      "configs", "restore-720p.json")
SHAPE, FRAMES, POOL_SEED, LEVEL = (40, 136), 5, 160904567, 0.7


@functools.lru_cache(maxsize=None)
def config():
    with open(CONFIG) as fh:
        return json.load(fh)


@functools.lru_cache(maxsize=None)
def frames_and_refs():
    c = config()
    frames = generators.restoration_pool(7, SHAPE, FRAMES, POOL_SEED, LEVEL)
    refs = []
    for x in frames:
        a, it = reference_restore.restore_frame(
            x, kmax=int(c["amf_kmax"]), beta=float(c["beta"]),
            tol=float(c["tol"]), max_iters=int(c["max_iters"]))
        refs.append((np.asarray(a), int(it)))
    return frames, refs


@pytest.mark.parametrize("lanes", [1, 3])
@pytest.mark.parametrize("chained", [False, True])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_restoration_farm_matches_reference(backend, chained, lanes):
    c = config()
    tol, kmax = float(c["tol"]), int(c["amf_kmax"])

    def detect(x):
        mask, repaired = ops.adaptive_median_detect(x, kmax=kmax)
        return repaired, (repaired, mask)

    loop = LoopOfStencilReduce(
        f=R.restore_taps(float(c["beta"])), k=1, combine="max",
        delta=R.abs_delta, cond=lambda r: r < tol,
        boundary=c["boundary"], max_iters=int(c["max_iters"]),
        backend=backend)
    eng = FarmEngine(loop, lanes=lanes, prep=detect, chained=chained)
    frames, refs = frames_and_refs()
    outs = []
    assert eng.run(frames, outs.append) == FRAMES
    counts = np.bincount([r.index for r in outs], minlength=FRAMES)
    assert counts.tolist() == [1] * FRAMES, counts
    limits = c["limits"]
    for res in outs:
        a_ref, it_ref = refs[res.index]
        assert res.status == "ok", (res.index, res.status)
        assert abs(int(res.iters) - it_ref) <= limits["iters_gap"], (
            res.index, int(res.iters), it_ref)
        err = float(np.max(np.abs(res.a - a_ref)))
        assert err <= limits["restore_err"], (res.index, err)
