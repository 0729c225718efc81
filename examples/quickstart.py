"""Quickstart: the Loop-of-stencil-reduce pattern in five minutes.

Runs Conway's Game of Life (the paper's Fig. 1 example) and a Jacobi
solve through the public API, then shows the -d and -s variants and the
streaming farm.  CPU-friendly; finishes in seconds.

    PYTHONPATH=src python examples/quickstart.py
"""
import sys

sys.path.insert(0, "src")

import jax.numpy as jnp
import numpy as np

from repro.core import (FarmEngine, LoopOfStencilReduce,
                        loop_of_stencil_reduce, loop_of_stencil_reduce_d,
                        loop_of_stencil_reduce_s)


def main():
    rng = np.random.default_rng(0)

    # -- Game of Life: base variant --------------------------------------
    # stencil f = the GoL rule over a 3×3 neighbourhood (taps protocol);
    # reduce ⊕ = sum of alive cells; condition c = extinction.
    def gol(get):
        n = sum(get(di, dj) for di in (-1, 0, 1) for dj in (-1, 0, 1)
                if (di, dj) != (0, 0))
        return jnp.where((n == 3) | ((get(0, 0) > 0) & (n == 2)), 1.0, 0.0)

    world = jnp.asarray(rng.integers(0, 2, (64, 64)), jnp.float32)
    res = loop_of_stencil_reduce(1, gol, "sum", lambda alive: alive <= 0,
                                 world, max_iters=200)
    print(f"[GoL]     ran {int(res.iters)} generations, "
          f"{int(res.reduced)} cells alive")

    # -- Jacobi: -d variant (convergence on the delta) --------------------
    def jacobi(get):
        return 0.25 * (get(-1, 0) + get(1, 0) + get(0, -1) + get(0, 1))

    u0 = jnp.asarray(rng.normal(size=(96, 96)), jnp.float32)
    res = loop_of_stencil_reduce_d(
        1, jacobi, lambda new, old: jnp.abs(new - old), "max",
        lambda d: d < 1e-4, u0, max_iters=5000)
    print(f"[Jacobi]  converged in {int(res.iters)} iterations "
          f"(max |Δ| = {float(res.reduced):.2e})")

    # -- -s variant: loop state in the condition --------------------------
    res = loop_of_stencil_reduce_s(
        1, jacobi, "sum", lambda r, steps: steps >= 10, u0,
        init=lambda: jnp.asarray(0, jnp.int32),
        update=lambda s, a, it: s + 1)
    print(f"[Jacobi-s] fixed-budget run stopped at {int(res.iters)} steps")

    # -- streaming farm (1:1 mode): items converge independently ----------
    # farm_run drives the whole batch as ONE done-masked while_loop over
    # a stacked (lanes, grid) carry — each lane to its own trip count
    runner = LoopOfStencilReduce(
        f=jacobi, k=1, combine="max", identity=-jnp.inf,
        cond=lambda d: d < 1e-4, delta=lambda n, o: jnp.abs(n - o),
        max_iters=5000)
    batch = jnp.stack([u0, u0 * 5.0, u0 * 0.1])
    out = runner.farm_run(batch)
    print(f"[farm]    per-item trip counts: {out.iters.tolist()}")

    # -- FarmEngine: a whole stream through persistent lane slots ---------
    # backend="pallas" is the point: frames are built once per lane slot
    # and REFILLED in place with each next item — no re-pad, no re-alloc,
    # no host round-trip of the frame (interpret-mode kernels on CPU, so
    # the demo uses a smaller grid + tolerance to stay quick); a finished
    # lane takes the next item while the other lane runs on
    v0 = u0[:48, :48]
    streamer = LoopOfStencilReduce(
        f=jacobi, k=1, combine="max", identity=-jnp.inf,
        cond=lambda d: d < 1e-2, delta=lambda n, o: jnp.abs(n - o),
        max_iters=600, backend="pallas", block=(48, 128))
    # and results arrive in completion order, each carrying its index
    eng = FarmEngine(streamer, lanes=2)
    done = []
    n = eng.run([v0 * s for s in (1.0, 5.0, 0.1, 2.0, 0.5)], done.append)
    iters = [int(r.iters) for r in sorted(done, key=lambda r: r.index)]
    print(f"[stream]  {n} items through 2 persistent lane slots "
          f"({eng.stats['segments']} segments, backend=pallas); "
          f"emission order: {[r.index for r in done]}; "
          f"trip counts: {iters}")


if __name__ == "__main__":
    main()
