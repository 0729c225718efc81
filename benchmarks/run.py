"""Benchmark harness — one function per paper table.

Prints ``name,us_per_call,derived`` CSV for eyeballing AND merges every
suite's records into ONE machine-readable ``BENCH_summary.json``
(schema: suite, name, backend, mesh, unroll, median seconds, derived
GB/s) so the perf trajectory is tracked across PRs — diff that single
file, not the stdout.  The committed repo-root BENCH_summary.json is the
current baseline.

    Table 1 (Helmholtz)      -> bench_helmholtz   (backend/unroll axis)
    Table 2 (Sobel stream)   -> bench_sobel
    Table 3 (restoration)    -> bench_restoration (backend/unroll axis)
    1:n sharded (§3.4 + CA)  -> bench_sharded (mesh over this process's
                                devices, per-iteration time + ppermute
                                rounds)
    1:1 streaming (§4.2/4.3) -> bench_streaming (lane-slot reuse vs the
                                per-batch sharded_farm path; items/sec +
                                host-transfer bytes/item; round vs
                                continuous incl. the composed
                                lanes × spatial deployment)
    serve (DESIGN.md §Serve) -> bench_serve (ragged-queue continuous
                                batching: single pool vs exact-length
                                groups; tok/s + idle_slot_steps)
    §Roofline (TPU target)   -> bench_roofline (reads runs/dryrun)

``--quick`` shrinks sizes for CI-speed runs; ``--out-dir`` relocates the
JSON file (default: current directory).  Every suite runs in this one
process (a chip belongs to one process); the multi-device suites span
whatever devices it has — on CPU,
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` gives it eight.
A suite that raises is reported and the harness exits non-zero.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma list: helmholtz,sobel,restoration,"
                         "sharded,streaming,serve,roofline")
    ap.add_argument("--out-dir", default=".",
                    help="where BENCH_summary.json is written")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (bench_helmholtz, bench_restoration, bench_roofline,
                   bench_serve, bench_sharded, bench_sobel,
                   bench_streaming)
    from .common import csv_row, write_summary

    suites = {
        "helmholtz": lambda: bench_helmholtz.run(
            sizes=(256, 512) if args.quick else (512, 1024, 2048)),
        "sobel": lambda: bench_sobel.run(
            sizes=(256, 512) if args.quick else (512, 1024, 2048),
            stream_n=20 if args.quick else 100),
        "restoration": lambda: bench_restoration.run(
            resolutions=("vga",) if args.quick else ("vga", "720p"),
            frames=2 if args.quick else 8),
        "sharded": lambda: bench_sharded.run(
            sizes=(256,) if args.quick else (256, 512)),
        "streaming": lambda: bench_streaming.run(
            sizes=(64,) if args.quick else (64, 128),
            stream_n=16 if args.quick else 32,
            iters=9),
        "serve": lambda: bench_serve.run(
            n_requests=8 if args.quick else 12,
            iters=2 if args.quick else 3),
        "roofline": bench_roofline.run,
    }
    only = set(args.only.split(",")) if args.only else set(suites)

    all_rows: dict[str, list] = {}
    failed = []
    print("name,us_per_call,derived")
    for name, fn in suites.items():
        if name not in only:
            continue
        try:
            rows = list(fn())
        except Exception:  # report, run the other suites, exit non-zero
            traceback.print_exc(file=sys.stderr)
            failed.append(name)
            continue
        for row in rows:
            print(csv_row(row), flush=True)
        all_rows[name] = rows
    path = write_summary(all_rows, args.out_dir)
    print(f"# wrote {path}", file=sys.stderr)
    if failed:
        sys.exit(f"failed suites: {', '.join(failed)}")


if __name__ == "__main__":
    main()
