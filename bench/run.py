#!/usr/bin/env python3
"""Benchmark of the local-SGD trainer on the chip, one cell per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout, on a machine with the chips the cell
names in ``BENCHMARK.json``.  The last line of standard output is the
result as one JSON object; the numbers that decide ``correct`` are the
last lines of standard error.  Without a TPU, or with another number of
chips, it exits non-zero and prints no result."""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


if __name__ == "__main__":
    import harness
    sys.exit(harness.main(parse(), t_start=T_START))
