#!/usr/bin/env python3
"""Readings that a cell's limits (``bench/limits/<cell>.json``) are set
from, in one process on the cell's chips.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --faults 3

For each of ``--seeds`` seeds the program is built and driven through the
checked iterations as a benchmark run does, and compared with the
reference: the largest reading of each number over the seeds is its lower
reading.  On the first ``--faults`` seeds the reference put in the
program's place is compared with itself: with its weights and AdamW
state kept in bfloat16 (the control), with float8 matrix operands,
scoring half of each sequence, without the replica exchange, and with its
reported losses altered by 1 %; a step that returns its state unchanged
reads 1 on ``update`` and needs no run.  The last line of standard output
is all readings as one JSON object.  This is not part of a benchmark run.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

import compare  # noqa: E402
import harness  # noqa: E402


def program_reading(cell, seed):
    engine, feed, init, key, fam, _ = harness.build(cell, seed)
    got = harness.first_iterations(engine, feed, init, key,
                                   cell.config["optimizer"]["b1"])
    del engine, feed
    gc.collect()
    return got, fam


def reference(cell, fam, seed, batches, fault=None, **numerics):
    from reference import train as ref_train
    from reference.numerics import Numerics
    return ref_train.run(fam, cell.config, cell.traffic["averaging"],
                         harness.k_sample(cell), harness.seed31(seed),
                         batches, Numerics(**numerics), fault=fault)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_000_000_001)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.devices(cell.chips)
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    out = {"cell": cell.name, "program": [], "control": [],
           "control_fp8": [], "half": [],
           "no_exchange": [], "answer": []}
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.monotonic()
        got, fam = program_reading(cell, seed)
        ref = reference(cell, fam, seed, got["batches"])
        r = compare.numbers(got, ref)
        out["program"].append(dict(r, seed=seed))
        print(f"seed {seed} program {r} ({time.monotonic() - t0:.1f} s)",
              flush=True)
        if i < args.faults:
            for name, kw in (("control", {"store": "bfloat16",
                                          "operands": "bfloat16"}),
                             ("control_fp8", {"operands": "fp8"}),
                             ("half", {"fault": "half"}),
                             ("no_exchange", {"fault": "no_exchange"})):
                alt = reference(cell, fam, seed, got["batches"], **kw)
                r = compare.numbers(alt, ref)
                out[name].append(dict(r, seed=seed))
                print(f"seed {seed} {name} {r}", flush=True)
            alt = dict(ref, losses=[v * 1.01 for v in ref["losses"]])
            out["answer"].append(dict(compare.numbers(alt, ref), seed=seed))
        gc.collect()
    summary = {}
    for n in ("loss", "grad", "s_k", "update"):
        summary[n] = {k: max(x[n] for x in out[k]) if k == "program"
                      else min(x[n] for x in out[k])
                      for k in out if k != "cell" and out[k]}
    out["summary"] = summary
    print(f"total {time.monotonic() - T_START:.1f} s", flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
