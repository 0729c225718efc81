"""On-chip smoke test of the Loop-of-stencil-reduce main path.

Runs in ONE process on the TPU, through the entry points a user calls
(:mod:`repro.kernels.ops` and :class:`repro.core.FarmEngine`), with the
Pallas kernels compiled for the chip:

  (a) ``jacobi_solve`` at 16384² float32 (the largest grid of the paper's
      Table 1) for a fixed 20 iterations on ``pallas`` and on
      ``pallas-multistep`` (T=4), each compared with ``backend="jnp"``;
  (b) a converging ``jacobi_solve`` at 4096² (tol=1e-5) on ``pallas``;
  (c) a chained continuous ``FarmEngine(lanes=8, backend="pallas")``
      stream of 32 seeded 720x1280 restoration items (paper §4.3), each
      compared with its own ``loop.run`` on ``jnp``.

With ``--chips 4`` it runs only the four-chip phases and what each is
compared with: the sharded solve over a 2x2 rows x cols ``GridPartition``
at 16384² against the one-chip ``pallas`` result, and FarmEngine lanes
over a 4-way ``data`` mesh against the solo runs.

Each phase prints one JSON line (device kind, shapes, compile and warm
wall seconds, max error, whether the compiled program holds the Pallas
kernel as ``tpu_custom_call``).  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed check raises; without a TPU the script exits non-zero and
prints no result.

    python chip_smoke.py [--chips 4]
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

# Helmholtz/Jacobi in grid units: (∇² − α)u = −f with dx = 1, so every
# iteration contracts by at most 4 / (4 + α) and tol=1e-5 is reached in
# a few hundred sweeps at any grid size.
ALPHA, DX = 0.1, 1.0
EPS32 = 2.0 ** -23
RESTORE_SHAPE = (720, 1280)
RESTORE_ITEMS = 32


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def report(phase: str, **row) -> None:
    print(json.dumps({"phase": phase, **row}), flush=True)


def has_kernel(compiled) -> bool:
    """Whether a compiled program holds a Pallas TPU kernel."""
    return "tpu_custom_call" in compiled.as_text()


def compile_and_time(fn, *args):
    """Compile ``fn`` for ``args``; run it once warm, then once timed.

    Returns (outputs, compile seconds, warm wall seconds, whether the
    compiled program holds a Pallas TPU kernel)."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    kernel = has_kernel(compiled)
    jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, compile_s, time.perf_counter() - t0, kernel


def max_abs_diff(a, b) -> float:
    import jax.numpy as jnp
    return float(jnp.max(jnp.abs(a - b)))


def normal_field(seed: int, n: int):
    """A seeded N(0, 1) float32 (n, n) field, made on the device."""
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda k: jax.random.normal(k, (n, n), jnp.float32))(
        jax.random.key(seed))


def solver(backend: str, iters: int, tol: float, unroll: int = 1,
           part=None):
    from repro.kernels import ops
    return lambda u0, fxy: ops.jacobi_solve(
        u0, fxy, alpha=ALPHA, dx=DX, tol=tol, max_iters=iters,
        backend=backend, unroll=unroll, part=part)


def solve_fixed(n: int, iters: int):
    """Phase (a): fixed-iteration solves at n², Pallas vs the jnp path."""
    import jax.numpy as jnp

    u0 = jnp.zeros((n, n), jnp.float32)
    fxy = normal_field(0, n)
    (u_ref, d_ref, it_ref), c_ref, w_ref, _ = compile_and_time(
        solver("jnp", iters, 0.0), u0, fxy)
    assert int(it_ref) == iters, int(it_ref)
    umax = float(jnp.max(jnp.abs(u_ref)))
    # each sweep rounds a few times in float32 and the Jacobi map is a
    # contraction, so the two paths drift apart by at most a few ulp of
    # max|u| per iteration
    tol = 8 * iters * EPS32 * umax
    report("a_solve_fixed", backend="jnp", shape=[n, n], iters=iters,
           compile_s=c_ref, wall_s=w_ref, max_abs_u=umax)
    for backend, unroll in (("pallas", 1), ("pallas-multistep", 4)):
        (u, d, it), c, w, kernel = compile_and_time(
            solver(backend, iters, 0.0, unroll), u0, fxy)
        err = max_abs_diff(u, u_ref)
        report("a_solve_fixed", backend=backend, unroll=unroll,
               shape=[n, n], iters=int(it), compile_s=c, wall_s=w,
               max_err_vs_jnp=err, tol=tol,
               reduce_err_vs_jnp=abs(float(d) - float(d_ref)),
               tpu_custom_call=kernel)
        assert kernel, f"{backend}: no Pallas kernel in the program"
        assert int(it) == iters, (backend, int(it))
        assert err <= tol, (backend, err, tol)
        del u


def solve_converged(n: int, tol: float):
    """Phase (b): a converging solve at n² on pallas (jnp alongside)."""
    import jax.numpy as jnp

    u0 = jnp.zeros((n, n), jnp.float32)
    fxy = normal_field(1, n)
    cap = 20000
    (u_ref, _, it_ref), *_ = compile_and_time(
        solver("jnp", cap, tol), u0, fxy)
    (u, delta, it), c, w, kernel = compile_and_time(
        solver("pallas", cap, tol), u0, fxy)
    up = jnp.pad(u, 1)
    neigh = up[:-2, 1:-1] + up[2:, 1:-1] + up[1:-1, :-2] + up[1:-1, 2:]
    res = float(jnp.max(jnp.abs((4 + ALPHA * DX * DX) * u - neigh
                                - DX * DX * fxy)))
    umax = float(jnp.max(jnp.abs(u)))
    # at the stopping iterate the residual is the change of the four
    # neighbours' sum (≤ 4·max|Δ|) plus float32 rounding of the update
    res_bound = 4 * tol + 16 * EPS32 * (4 + ALPHA * DX * DX) * umax
    err = max_abs_diff(u, u_ref)
    report("b_solve_converged", backend="pallas", shape=[n, n], tol=tol,
           iters=int(it), iters_jnp=int(it_ref), max_delta=float(delta),
           residual=res, residual_bound=res_bound, compile_s=c, wall_s=w,
           max_err_vs_jnp=err, tpu_custom_call=kernel)
    assert kernel, "pallas: no Pallas kernel in the program"
    assert int(it) < cap and float(delta) < tol, (int(it), float(delta))
    assert abs(int(it) - int(it_ref)) <= 1, (int(it), int(it_ref))
    assert res <= res_bound, (res, res_bound)


def restoration_items(count: int, shape, seed: int, level: float = 0.3):
    """Seeded 720p salt-and-pepper frames (paper §4.3), made on the device
    in one batch: a smooth textured scene with ``level`` of its pixels
    replaced by 0 or 1."""
    import jax
    import jax.numpy as jnp

    def make(key):
        h, w = shape
        yy, xx = jnp.mgrid[0:h, 0:w].astype(jnp.float32)
        base = (0.5 + 0.3 * jnp.sin(xx / 25.0) * jnp.cos(yy / 18.0)
                + 0.2 * ((xx // 40 + yy // 30) % 2))
        base = jnp.clip(base, 0.0, 1.0)
        k1, k2 = jax.random.split(key)
        hit = jax.random.uniform(k1, (count, h, w)) < level
        salt = (jax.random.uniform(k2, (count, h, w)) < 0.5)
        return jnp.where(hit, salt.astype(jnp.float32), base)

    frames = jax.jit(make)(jax.random.key(seed))
    return [frames[i] for i in range(count)]


def restoration_loop(backend: str):
    from repro.core import LoopOfStencilReduce
    from repro.kernels import ref as R
    return LoopOfStencilReduce(
        f=R.restore_taps(2.0), k=1, combine="max", delta=R.abs_delta,
        cond=lambda r: r < 1e-3, boundary="reflect", max_iters=50,
        backend=backend)


def detect(frame):
    """The farm's prep stage: AMF noise mask + repaired initial guess."""
    from repro.kernels import ops
    mask, repaired = ops.adaptive_median_detect(frame)
    return repaired, (repaired, mask)


def stream_farm(phase: str, lanes: int, mesh=None):
    """Phase (c), and the four-chip lanes-over-mesh phase: a chained
    continuous restoration stream, every item against its solo jnp run."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import FarmEngine

    items = restoration_items(RESTORE_ITEMS, RESTORE_SHAPE, seed=2)
    solo = restoration_loop("jnp")

    @jax.jit
    def solo_run(x):
        a0, env = detect(x)
        return solo.run(a0, env=env)

    refs = [solo_run(x) for x in items]
    loop = restoration_loop("pallas")
    eng = FarmEngine(loop, lanes=lanes, prep=detect, mesh=mesh)
    walls = []
    for _ in range(2):       # cold stream (compiles), then a warm one
        outs = []
        t0 = time.perf_counter()
        n = eng.run(items, outs.append)
        walls.append(time.perf_counter() - t0)
        assert n == RESTORE_ITEMS, n
        idx = sorted(r.index for r in outs)
        assert idx == list(range(RESTORE_ITEMS)), idx   # exactly once
        assert not eng.dead_letter, eng.dead_letter
    # the farm's kernel, compiled at the stream's lane shapes through the
    # pattern's own lane entry point
    a0, envs = jax.vmap(detect)(jnp.stack(items[:lanes]))
    kernel = has_kernel(jax.jit(
        lambda a, e0, e1: loop.farm_run(a, env=(e0, e1))
    ).lower(a0, *envs).compile())
    outs.sort(key=lambda r: r.index)
    tol = 8 * solo.max_iters * EPS32     # values lie in [0, 1]
    err, iters = 0.0, []
    for res, ref in zip(outs, refs):
        assert res.status == "ok", (res.index, res.status)
        assert int(res.iters) == int(ref.iters), (res.index, int(res.iters),
                                                  int(ref.iters))
        err = max(err, float(np.max(np.abs(np.asarray(res.a)
                                           - np.asarray(ref.a)))))
        iters.append(int(res.iters))
    report(phase, backend="pallas", lanes=lanes,
           mesh=None if mesh is None else dict(mesh.shape),
           items=RESTORE_ITEMS, item_shape=list(RESTORE_SHAPE),
           compile_s=walls[0] - walls[1], compile_s_is="cold minus warm stream",
           wall_s=walls[1], items_per_s=RESTORE_ITEMS / walls[1],
           iters_min=min(iters), iters_max=max(iters), max_err_vs_jnp=err,
           tol=tol, segments=eng.stats["segments"],
           tpu_custom_call=kernel)
    assert kernel, "farm: no Pallas kernel in the program"
    assert err <= tol, (err, tol)


def solve_sharded(n: int, iters: int):
    """Four-chip phase: the 1:n deployment over a 2x2 rows x cols mesh
    against the one-chip pallas solve."""
    import jax
    import jax.numpy as jnp
    from repro.core import GridPartition
    from repro.sharding.specs import make_mesh

    part = GridPartition(mesh=make_mesh((2, 2), ("rows", "cols")),
                         axis_names=("rows", "cols"), array_axes=(0, 1))
    dev0 = jax.devices()[0]
    u0 = jnp.zeros((n, n), jnp.float32)
    fxy = normal_field(0, n)
    (u_ref, _, _), c_ref, w_ref, _ = compile_and_time(
        solver("pallas", iters, 0.0), u0, fxy)
    (u, _, it), c, w, kernel = compile_and_time(
        solver("pallas-sharded", iters, 0.0, part=part), u0, fxy)
    spread = len(u.sharding.device_set)
    err = max_abs_diff(jax.device_put(u, dev0), jax.device_put(u_ref, dev0))
    # same kernel, same tile arithmetic: only the ghost source differs
    # (a neighbour's ppermute instead of a local frame read)
    tol = 8 * iters * EPS32 * float(jnp.max(jnp.abs(u_ref)))
    report("d_solve_sharded", backend="pallas-sharded", mesh={"rows": 2,
           "cols": 2}, shape=[n, n], iters=int(it), devices_holding_result=
           spread, compile_s=c, wall_s=w, one_chip_wall_s=w_ref,
           max_err_vs_one_chip=err, tol=tol, tpu_custom_call=kernel)
    assert kernel, "pallas-sharded: no Pallas kernel in the program"
    assert int(it) == iters, int(it)
    assert spread == 4, f"result lives on {spread} device(s), not 4"
    assert err <= tol, (err, tol)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phases")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"no src/repro next to {__file__}: run from a checkout")
    sys.path.insert(0, SRC)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU found (JAX platform is {devices[0].platform!r})")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} chips; JAX sees "
             f"{len(devices)}")
    from repro.launch.compile_cache import enable_compile_cache
    report("setup", device_kind=devices[0].device_kind,
           devices=len(devices), jax=jax.__version__,
           compile_cache=enable_compile_cache())

    if args.chips == 4:
        from repro.sharding.specs import make_mesh
        solve_sharded(16384, 20)
        stream_farm("e_farm_lanes_over_data", 8,
                    mesh=make_mesh((4,), ("data",)))
    else:
        solve_fixed(16384, 20)
        solve_converged(4096, 1e-5)
        stream_farm("c_stream_farm", 8)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
