"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

(``python3 -m bench.run`` with the same arguments is the same.)  Run from
the root of a checkout on a machine that holds the chips the cell asks
for.  ``BENCHMARK.json`` names the cell's configuration and traffic;
``bench/configs/<config>.json`` and ``bench/traffic/<traffic>.json`` hold
them, ``bench/drivers/<driver>.py`` drives the entry the configuration
names, and ``bench/metrics/<metric>.py`` reads each per-layer metric.

Set-up (timed from the start of this process: JAX start-up, inputs made
from the seed on the device, every shape of the cell warmed) is followed
by the measured window of ``--seconds``.  Then the same program solves
one more input drawn from the seed itself (the window's inputs are
symmetries of one draw, so that every seed does the same work), its
state is freed, and what the window and that probe produced is compared
with the plain reference (``bench/reference.py``).  ``--trace 1`` takes a profiler trace of the
window and reports the per-layer metrics instead of the end-to-end ones.
The last line of standard output is one JSON object; the numbers compared
with the reference are the last lines of standard error and the last key
of that object.  Without a TPU, or with fewer chips than the cell needs,
it exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")


class Refused(Exception):
    """The run cannot be made here (no chip, unknown cell, no program)."""


def load_cell(workload: str, root: str = ROOT) -> dict:
    """The cell's entry, configuration, traffic and metrics, by name."""
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.isfile(path):
        raise Refused(f"no BENCHMARK.json at {root}")
    with open(path) as fh:
        bench = json.load(fh)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    with open(os.path.join(root, "bench", "configs",
                           cell["config"] + ".json")) as fh:
        config = json.load(fh)
    with open(os.path.join(root, "bench", "traffic",
                           cell["traffic"] + ".json")) as fh:
        traffic = json.load(fh)
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": e2e, "per_layer": per_layer}


def read_metric(name: str, ctx):
    """Run the reader ``bench/metrics/<name>.py`` on ``ctx``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench.metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def enable_cache():
    """JAX's persistent compile cache at the checkout's fixed
    ``.jax_cache/``, keeping every program, so that only a cell's first
    run in a checkout compiles.  The program's own cache set-up is handed
    the same directory (it honours ``JAX_COMPILATION_CACHE_DIR``), so no
    cache outside the checkout is shared between two checkouts."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    path = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def run(spec: dict, seed: int, seconds: float, trace: bool, devices,
        t_start: float, control: bool = False) -> dict:
    """One run of a cell on ``devices``; returns the result object.
    ``control`` puts the reference one precision down in the program's
    place for the comparison (``bench/limits.py``)."""
    import jax

    from bench import roofline
    from bench import trace as tracing
    from bench.common import Context, span

    config, traffic = spec["config"], spec["traffic"]
    driver = importlib.import_module("bench.drivers." + config["driver"])
    peaks = roofline.peaks(devices[0].device_kind) if trace else None
    t_cell = time.perf_counter()
    with span("setup"):
        cell = driver.Cell(config, traffic, seed, devices)
    setup_s = time.perf_counter() - t_start
    print(f"setup {setup_s:.3f} s: {t_cell - t_start:.3f} s to the devices "
          f"and the cache, {setup_s - (t_cell - t_start):.3f} s to make "
          f"the inputs and warm the cell", file=sys.stderr, flush=True)

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            with span("window"):
                win = cell.window(seconds)
        finally:
            if trace:
                jax.profiler.stop_trace()
        peak = memory_peak(devices)
        with span("probe"):
            cell.probe()
        cell.release()
        checks = cell.check(control=control)
        reduced = tracing.load(tdir) if trace else None
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)

    metrics = {}
    if trace:
        ctx = Context(trace=reduced, counters=win.counters, config=config,
                      peaks=peaks)
        for m in spec["per_layer"]:
            value = read_metric(m["name"], ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        measured = dict(win.e2e, setup_s=setup_s)
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": measured[m["name"]],
                                  "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": all(c.ok for c in checks),
              "attempted": win.attempted, "failed": win.failed,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = tracing.busy_s(reduced)
        device["window_s"] = reduced.window_s
        result["breakdown"] = {
            "device_ops": tracing.top_ops(reduced),
            "idle_gaps": tracing.idle_gaps(reduced)}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        src = os.path.join(ROOT, "src")
        if not os.path.isdir(os.path.join(src, "repro")):
            raise Refused(f"no program (src/repro) in {ROOT}")
        spec = load_cell(args.workload)
        sys.path[:0] = [p for p in (ROOT, src) if p not in sys.path]
        import jax

        devices = jax.devices()
        if devices[0].platform != "tpu":
            raise Refused(f"no TPU: JAX runs on {devices[0].platform!r}")
        chips = int(spec["cell"]["chips"])
        if len(devices) < chips:
            raise Refused(f"{args.workload} needs {chips} chips; JAX sees "
                          f"{len(devices)}")
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    enable_cache()
    result = run(spec, args.seed, args.seconds, bool(args.trace),
                 devices[:chips], T_START)
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    sys.exit(main())
