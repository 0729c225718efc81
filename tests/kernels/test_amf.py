"""The adaptive median filter's sort-free window statistics are exact.

The oracle is the filter as it was written with a full sort of each
window; the program's selection network must pick bitwise the same
elements, so the noise mask and the repaired frame are unchanged.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.introspect import flatten_eqns
from repro.core.stencil import stencil_taps
from repro.kernels import ops
from repro.kernels import ref as R


def sorted_amf_core(kmax):
    """The sort-based AMF ``core``, verbatim: the oracle."""
    def core(get):
        x = get(0, 0)
        decided = jnp.zeros_like(x, dtype=bool)
        noise = jnp.zeros_like(x, dtype=bool)
        repl = x
        for k in range(1, kmax + 1):
            w = jnp.stack([get(di, dj)
                           for di in range(-k, k + 1)
                           for dj in range(-k, k + 1)])
            srt = jnp.sort(w, axis=0)
            mn, med, mx = srt[0], srt[w.shape[0] // 2], srt[-1]
            level_a = (med > mn) & (med < mx)
            is_noise_here = ~((x > mn) & (x < mx))
            newly = level_a & ~decided
            noise = jnp.where(newly, is_noise_here, noise)
            repl = jnp.where(newly & is_noise_here, med, repl)
            decided = decided | level_a
        noise = jnp.where(decided, noise, True)
        repl = jnp.where(~decided, med, repl)  # last-level median fallback
        return noise.astype(x.dtype), repl
    return core


@jax.jit
def sorted_detect(frame):
    mask, repl = stencil_taps(sorted_amf_core(3), frame, 3, "reflect")
    return mask, jnp.where(mask > 0, repl, frame)


def bits(x):
    return np.asarray(x).view(np.uint32)


def smooth(h, w):
    yy, xx = np.mgrid[0:h, 0:w]
    return np.clip(0.5 + 0.3 * np.sin(xx / 20.0) * np.cos(yy / 15.0),
                   0, 1).astype(np.float32)


def impulses(frame, level, seed):
    rng = np.random.default_rng(seed)
    hit = rng.uniform(size=frame.shape) < level
    sp = np.where(rng.uniform(size=frame.shape) < 0.5, 0.0, 1.0)
    return np.where(hit, sp, frame).astype(np.float32)


FRAMES = {
    "noise0": lambda: impulses(smooth(96, 160), 0.0, 1),
    "noise30": lambda: impulses(smooth(96, 160), 0.3, 2),
    "noise70": lambda: impulses(smooth(96, 160), 0.7, 3),
    "noise90": lambda: impulses(smooth(96, 160), 0.9, 4),
    "constant": lambda: np.full((40, 56), 0.375, np.float32),
    "zero_one": lambda: (np.random.default_rng(5).uniform(size=(48, 64))
                         < 0.5).astype(np.float32),
    "odd_11x13": lambda: impulses(smooth(11, 13), 0.5, 6),
    "odd_96x160_uniform": lambda: np.random.default_rng(7).uniform(
        size=(96, 160)).astype(np.float32),
}


@pytest.mark.parametrize("name", list(FRAMES))
def test_detect_is_bitwise_the_sorted_filter(name):
    frame = jnp.asarray(FRAMES[name]())
    mask, repaired = ops.adaptive_median_detect(frame)
    want_mask, want_repaired = sorted_detect(frame)
    np.testing.assert_array_equal(bits(mask), bits(want_mask))
    np.testing.assert_array_equal(bits(repaired), bits(want_repaired))


def _select_cases(n):
    if n == 9:      # every 0-1 input: exact on these, exact on all
        return np.array(list(itertools.product([0.0, 1.0], repeat=n)),
                        np.float32)
    rng = np.random.default_rng(n)
    few = rng.integers(0, 3, size=(4096, n)).astype(np.float32)
    many = rng.integers(0, 2 * n, size=(4096, n)).astype(np.float32) / n
    return np.concatenate([few, many])


@pytest.mark.parametrize("n", [9, 25, 49])
def test_select_ranks_is_the_sort(n):
    x = _select_cases(n)
    ranks = (0, n // 2, n - 1)
    got = R.select_ranks([jnp.asarray(x[:, i]) for i in range(n)], ranks)
    want = np.sort(x, axis=1)[:, ranks]
    for r, g in enumerate(got):
        np.testing.assert_array_equal(bits(g), bits(want[:, r]))


def test_detect_evaluates_one_network_and_no_sort():
    frame = jnp.zeros((24, 40), jnp.float32)
    eqns = flatten_eqns(
        jax.make_jaxpr(ops.adaptive_median_detect)(frame).jaxpr, [])
    names = [eq.primitive.name for eq in eqns]
    assert "sort" not in names
    one = sum(keep_min
              for n in (9, 25, 49)
              for _, _, keep_min, _ in R._selection_network(
                  n, (0, n // 2, n - 1)))
    assert names.count("min") == one
