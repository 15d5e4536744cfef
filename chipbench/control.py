#!/usr/bin/env python3
"""Readings that the check's limits are set from, at a cell's own size.

    python3 chipbench/control.py --workload netflix.train \
        --program-seeds 12 --control-seeds 3 --seconds 1

In one process (set-up is long, so the seeds share it): the program's
sound runs on ``--program-seeds`` seeds give each compared number its
lower reading; the control and the planted faults, on
``--control-seeds`` seeds, give the upper one.

* training: the control is the program's own lower-precision path,
  ``FastTuckerConfig(dtype="bfloat16")``; the fault is half of Psi left
  out (a state left unchanged reads 1 by construction);
* serving: the control is the reference, rounded to a lower precision,
  put in the program's place (``bfloat16``, and ``float8_e4m3fn``, the
  step below, since the program's float32 tables already meet its dots
  as one bfloat16 pass); the faults are an altered answer in every flush
  and half of each flush answered wrongly.

Each run prints one JSON line; ``--out`` appends them to a file too.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
SEED_BASE = 1_000_003


def plans(kind: str) -> list[tuple[str, dict]]:
    if kind == "train":
        return [("control_bf16", {"variant": {"dtype": "bfloat16"}}),
                ("fault_half_batch", {"fault": "half_batch"})]
    return [("control_bf16", {"check_dtype": "bfloat16"}),
            ("control_fp8", {"check_dtype": "float8_e4m3fn"}),
            ("fault_altered", {"fault": "altered"}),
            ("fault_half_batch", {"fault": "half_batch"})]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--first-seed", type=int, default=SEED_BASE)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT))
    from chipbench import harness

    bench = harness.load_benchmark(ROOT)
    wl = harness.workload_entry(bench, args.workload)
    if not harness.enter(int(wl["chips"])):
        return 2
    kind = harness.load_mix(wl["traffic"])["kind"]
    runs = [("program", {}, args.first_seed + i)
            for i in range(args.program_seeds)]
    for label, opts in plans(kind):
        runs += [(label, opts, args.first_seed + 1000 + i)
                 for i in range(args.control_seeds)]
    for label, opts, seed in runs:
        t0 = time.perf_counter()
        out = harness.run_cell(args.workload, seed, args.seconds, False,
                               t0, bench=bench, **opts)
        line = {"workload": args.workload, "run": label, "seed": seed,
                "checks": {k: v["value"] for k, v in out["checks"].items()},
                "metrics": {k: v["value"] for k, v in
                            out["metrics"].items()},
                "failed": out["failed"], "attempted": out["attempted"],
                "seconds": time.perf_counter() - t0}
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    print(f"control readings done in {time.perf_counter() - T_PROCESS:.1f} s",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
