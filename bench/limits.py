"""Readings that the limits of ``correct`` are set from, in one process.

    python3 bench/limits.py --workload <name> --seconds <s> \
        --seeds <a,b,...> --control-seeds <c,d,...>

For each seed of ``--seeds`` the cell is set up, driven for a short
window at its own load and compared with the reference: the lower
readings.  For each of ``--control-seeds`` the same, with the reference
one precision down in the program's place: the upper readings, which
have to fail.  One JSON line per run; run on the chips the cell needs.
The benchmark's own runs never run this.
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from bench import run

    spec = run.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("limits: no TPU", file=sys.stderr)
        return 2
    run.enable_cache()
    devices = devices[:int(spec["cell"]["chips"])]
    plan = [(int(s), False) for s in args.seeds.split(",") if s]
    plan += [(int(s), True) for s in args.control_seeds.split(",") if s]
    for seed, control in plan:
        t0 = time.perf_counter()
        res = run.run(spec, seed, args.seconds, False, devices, t0,
                      control=control)
        print(json.dumps({"seed": seed, "control": control,
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          "correct": res["correct"],
                          "checks": res["checks"],
                          "wall_s": time.perf_counter() - t0}), flush=True)
        del res
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
