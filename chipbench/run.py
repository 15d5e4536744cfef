#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 chipbench/run.py --workload netflix.train --seed 7 \
        --seconds 20 --trace 0

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for, or when the program (``src/``) is not beside
this directory.  The last line of standard output is the result object;
the numbers the check compared, each with its limit, are the last lines
of standard error.  ``--trace 1`` reports the cell's per-layer metrics,
read from a profiler trace of the end of the window, in place of the
end-to-end ones.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    sys.path.insert(0, str(ROOT))
    from chipbench import harness

    bench = harness.load_benchmark(ROOT)
    wl = harness.workload_entry(bench, args.workload)
    if not harness.enter(int(wl["chips"])):
        return 2
    out = harness.run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), T_PROCESS, bench=bench)
    harness.print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
