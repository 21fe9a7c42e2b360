#!/usr/bin/env python3
"""One run of one cell: data in, one line out.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's files are found by name (``benchmarks/lib/manifest.py``);
this file holds no table of cells, configurations, drivers or metrics.
The last line of standard output is the contract's JSON object; every
other line is an earlier line.  Without a TPU the run fails, unless
``--rehearsal`` is given: that runs the same control flow on the CPU at
a tiny preset and says ``"platform": "cpu"`` — never a measurement.
"""

import time

T_START = time.time()   # set-up is counted from here

import argparse  # noqa: E402
import json      # noqa: E402
import os        # noqa: E402
import sys       # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU, tiny preset, never a measurement")
    args = ap.parse_args(argv)

    if args.rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from benchmarks.lib import harness, manifest

    cell = manifest.load_cell(args.workload)
    work_dir = os.path.join(ROOT, ".bench_work")
    os.makedirs(work_dir, exist_ok=True)
    run = harness.Run(cell=cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), rehearsal=args.rehearsal,
                      t_start=T_START, work_dir=work_dir)
    try:
        driver = manifest.load_driver(cell["driver"])
        result = driver.run(run)
    except harness.BenchFailure as e:
        print(f"benchmark failed: {e}", file=sys.stderr, flush=True)
        return 2
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
