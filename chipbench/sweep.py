#!/usr/bin/env python3
"""Find a serving cell's knee: its traffic at a list of fixed rates.

    python3 chipbench/sweep.py --workload yahoo.serve_topk \
        --rates 2000,4000,8000 --seconds 5

One process, one short window per rate (set-up repeated, so every rate
starts from the same state).  Prints one JSON line per rate: offered and
completed requests per second, p50/p95/p99 from the due time, shed and
unanswered requests, how late the generator ran, queries per flush and
compiles inside the window.  The rate a cell offers is about four fifths
of the highest rate at which nothing is shed and the generator keeps up.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=2_000_003)
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT))
    from chipbench import harness

    bench = harness.load_benchmark(ROOT)
    wl = harness.workload_entry(bench, args.workload)
    if not harness.enter(int(wl["chips"])):
        return 2
    import numpy as np

    for rate in (float(r) for r in args.rates.split(",")):
        mix = harness.load_mix(wl["traffic"])
        mix["arrivals"] = dict(mix["arrivals"], rate_per_s=rate)
        ctx = harness.context(bench, args.workload, args.seed, args.seconds,
                              False, time.perf_counter(), mix=mix)
        rec = harness.driver(mix["kind"])(ctx, keep_latencies=True)
        lat = np.sort(rec["latencies_s"]) * 1e3
        n = len(lat)
        q = {p: float(lat[min(n - 1, int(np.ceil(p / 100 * n)) - 1)])
             for p in (50, 95, 99)}
        sizes = [b for _, _, b in rec["counters"]["engine_calls"]]
        print(json.dumps({
            "rate": rate, "requests": n,
            "completed_per_s": (n - rec["failed"]) / args.seconds,
            "p50_ms": q[50], "p95_ms": q[95], "p99_ms": q[99],
            "failed": rec["failed"], "late_max_ms":
            rec["counters"]["late_max_s"] * 1e3,
            "flushes": rec["counters"]["flushes"],
            "queries_per_flush_mean": float(np.mean(sizes)) if sizes else 0,
            "queries_per_flush_max": max(sizes) if sizes else 0,
            "engine_ms_mean": 1e3 * float(np.mean(
                [e - s for s, e, _ in rec["counters"]["engine_calls"]])),
            "engine_ms_max": 1e3 * float(np.max(
                [e - s for s, e, _ in rec["counters"]["engine_calls"]])),
            "compiles_in_window": len(ctx.compiles_in_window()),
            "compiled": sorted(set(ctx.compiles_in_window()))[:8],
            "setup_s": ctx.t_window - ctx.t_process,
            "checks": rec["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
