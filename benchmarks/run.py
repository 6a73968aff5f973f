"""Benchmark orchestrator — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig1,...]

Prints ``name,us_per_call,derived`` CSV rows (plus the roofline table if
dry-run artifacts exist under experiments/dryrun/).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--skip-roofline", action="store_true")
    ap.add_argument("--engine-json", default=None, metavar="PATH",
                    help="also write the per-strategy engine baseline "
                         "(steps/s, syncs, comm bytes) to PATH")
    args = ap.parse_args()

    from benchmarks import engine_baseline, kernel_bench, paper_figures

    jobs = [(fn.__name__, fn) for fn in paper_figures.ALL]
    jobs.append(("engine_baseline", engine_baseline.rows))
    jobs.append(("kernel_bench", kernel_bench.bench))
    if args.only:
        keep = args.only.split(",")
        jobs = [(n, f) for n, f in jobs if any(k in n for k in keep)]

    print("name,us_per_call,derived")
    t_start = time.time()
    failed = 0
    for name, fn in jobs:
        try:
            t0 = time.time()
            for row in fn():
                print(row, flush=True)
            print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
        except Exception:  # noqa: BLE001
            failed += 1
            print(f"# {name} FAILED:", file=sys.stderr)
            traceback.print_exc()

    if not args.skip_roofline:
        try:
            from benchmarks import roofline
            rows = roofline.table()
            if rows:
                print("# --- roofline (from dry-run artifacts) ---")
                for row in rows:
                    print(row)
        except Exception:  # noqa: BLE001
            failed += 1
            traceback.print_exc()
    if args.engine_json:
        try:
            engine_baseline.write_json(args.engine_json)
            print(f"# engine baseline -> {args.engine_json}")
        except Exception:  # noqa: BLE001
            failed += 1
            traceback.print_exc()
    print(f"# total {time.time() - t_start:.1f}s, {failed} failures")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
