"""Serving-path benchmark → the canonical ``BENCH_serve.json``.

Four measurements, one document (schema ``bench_serve/v1``, validated by
``benchmarks.common.validate_bench_serve``; CI smoke-checks the emitted
file the same way it checks ``BENCH_step.json``):

  * **throughput** — the original serving acceptance evidence: ≥10×
    bucketed-batched over per-query jit dispatch, and a BOUNDED compile
    count across a 1→512 batch-size sweep (the bucket ladder caps the
    jit cache).
  * **collectives** — the tentpole's HLO-asserted win: lower the row-
    sharded ``top_k`` fast path (shard-local ``lax.top_k`` + one
    all-gather of M·k candidates) and the GSPMD-compiled unsharded
    program on the SAME row-sharded tables, and compare collective
    operand bytes via ``repro.launch.hlo_analysis``.  The fast path
    moves O(B·R + M·k·B); GSPMD all-gathers the O(B·rows) score matrix.
  * **closed_loop** — the async front end (``repro.serve.frontend``)
    under offered load: per-mode (unsharded / row / batch / gspmd-
    baseline top_k) achieved QPS, p50/p99 request latency, shed counts.
  * **crossover** — row- vs batch-sharded capacity at saturating offered
    load: where replicated-table batch parallelism overtakes the
    row-sharded layout (the measurement behind ``serve.policy``).

Multi-device sections run in a subprocess with forced host devices
(``--xla_force_host_platform_device_count``, same idiom as
``bench_ingest``), so one invocation produces the full document:

    PYTHONPATH=src python -m benchmarks.bench_serve \
        [--smoke] [--devices 4] [--out BENCH_serve.json]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

from .common import BENCH_SERVE_SCHEMA, row, validate_bench_serve

DEVICES = 4

FULL = dict(dims=(2000, 1200, 150), nnz=100_000, rank=8, k=10,
            microbatch=256, max_request=64, duration_s=3.0,
            predict_qps=(4_000.0, 16_000.0, 64_000.0),
            top_k_qps=2_000.0, concurrency=16)
SMOKE = dict(dims=(120, 90, 30), nnz=4_000, rank=4, k=5,
             microbatch=64, max_request=16, duration_s=1.0,
             predict_qps=(2_000.0,),
             top_k_qps=500.0, concurrency=8)


# ---------------------------------------------------------------------------
# child: the actual measurement (runs under forced host devices)
# ---------------------------------------------------------------------------

def _closed_loop_row(server, *, shard_mode: str, query: str, qps: float,
                     cfgp: dict, pool, top_k_args=None, seed=0) -> dict:
    from repro.serve import AdmissionConfig, run_closed_loop

    rep = run_closed_loop(
        server, qps=qps, duration_s=cfgp["duration_s"],
        concurrency=cfgp["concurrency"], max_request=cfgp["max_request"],
        admission=AdmissionConfig(microbatch=cfgp["microbatch"]),
        query=query, top_k_args=top_k_args,
        request_pool=pool if query == "predict" else None, seed=seed)
    lat = rep["latency_ms"]
    return {
        "shard_mode": shard_mode,
        "query": query,
        "offered_qps": float(qps),
        "achieved_qps": float(rep["achieved_qps"]),
        "p50_ms": float(lat["p50"] if lat["p50"] is not None else -1.0),
        "p99_ms": float(lat["p99"] if lat["p99"] is not None else -1.0),
        "served_requests": int(rep["served_requests"]),
        "shed": int(rep["shed_queue_full"] + rep["shed_deadline"]),
        "by_bucket": rep["by_bucket"],
    }


def measure(smoke: bool) -> dict:
    from functools import partial

    import jax
    import numpy as np

    from repro.core import fasttucker as ft
    from repro.data.synthetic import ratings_tensor
    from repro.launch import hlo_analysis
    from repro.launch.mesh import make_host_mesh
    from repro.serve import TuckerServer
    from repro.serve.engine import _top_k_impl

    cfgp = SMOKE if smoke else FULL
    dims, J, k = cfgp["dims"], cfgp["rank"], cfgp["k"]
    M = jax.device_count()
    tensor = ratings_tensor(dims, nnz=cfgp["nnz"], rank=J, seed=0)
    cfg = ft.FastTuckerConfig(dims=dims, ranks=(J,) * len(dims),
                              core_rank=J, batch_size=1024)
    params = ft.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    all_idx = np.asarray(tensor.indices, np.int32)
    queries = all_idx[rng.integers(0, len(all_idx), 2048)]

    out: dict = {"devices": M}
    base = TuckerServer(params)

    # ---- throughput: bucketed batched vs per-query, bounded compiles -------
    single = jax.jit(lambda p, i: ft.predict(p, i))
    jax.block_until_ready(single(params, queries[:1]))
    n_pq = 128 if smoke else 256
    t0 = time.perf_counter()
    for q in range(n_pq):
        jax.block_until_ready(single(params, queries[q:q + 1]))
    per_query_qps = n_pq / (time.perf_counter() - t0)

    sizes = rng.integers(1, 513, 32 if smoke else 64)
    requests, used = [], 0
    for sz in sizes:
        sel = np.arange(used, used + int(sz)) % len(queries)
        requests.append(queries[sel])
        used += int(sz)
    for r_ in requests:                       # warm every bucket (compile)
        jax.block_until_ready(base.predict(r_))
    total = sum(len(r_) for r_ in requests)
    t0 = time.perf_counter()
    for r_ in requests:
        pred = base.predict(r_)
    jax.block_until_ready(pred)
    bucketed_qps = total / (time.perf_counter() - t0)

    sweep = TuckerServer(params)
    for b in range(1, 513):
        if b in (1, 2, 3, 5, 7) or b % 16 == 0 or b in (511, 512):
            sweep.predict(queries[:b])
    out["throughput"] = {
        "per_query_qps": float(per_query_qps),
        "bucketed_qps": float(bucketed_qps),
        "speedup": float(bucketed_qps / per_query_qps),
        "sweep_compiles": int(sweep.predict_cache_size),
        "ladder_bound": len(sweep.ladder),
    }

    # ---- closed loop: unsharded reference -----------------------------------
    def warm(server, query="predict", top_k_args=None):
        # compile every ladder bucket up front so the closed-loop
        # percentiles measure steady-state serving, not jit compiles
        for b in server.ladder:
            if query == "predict":
                jax.block_until_ready(server.predict(queries[
                    np.arange(b) % len(queries)]))
            else:
                m, kk, t = top_k_args
                jax.block_until_ready(server.top_k(
                    m, np.zeros(b, np.int32), kk, target_mode=t))

    warm(base)
    cl_rows = [_closed_loop_row(base, shard_mode="none", query="predict",
                                qps=cfgp["predict_qps"][0], cfgp=cfgp,
                                pool=queries)]

    if M > 1:
        mesh = make_host_mesh()
        row_srv = TuckerServer(params, mesh=mesh, shard_mode="row")
        batch_srv = TuckerServer(params, mesh=mesh, shard_mode="batch")
        # the pre-fast-path baseline: same row-sharded tables, but top_k
        # compiled from the UNSHARDED program — GSPMD picks the layouts
        # (and all-gathers the full (B, I_target) score matrix)
        gspmd_srv = TuckerServer(params, mesh=mesh, shard_mode="row")
        gspmd_srv._top_k_fn = jax.jit(
            _top_k_impl,
            static_argnames=("mode", "target", "k", "true_target_dim"))

        # ---- collectives: HLO-asserted bytes, fast path vs GSPMD ----------
        # score the LARGEST mode (the millions-of-candidates axis in a
        # recommender): GSPMD's payload grows with the scored dimension,
        # the shard-local merge's only with M·k
        bucket = cfgp["microbatch"]
        ids = np.zeros(bucket, np.int32)
        kw = dict(mode=1, target=0, k=k, true_target_dim=dims[0])
        fast_txt = row_srv._top_k_fn.lower(
            row_srv._tables, row_srv._colsums, ids, **kw
        ).compile().as_text()
        gspmd_txt = gspmd_srv._top_k_fn.lower(
            row_srv._tables, row_srv._colsums, ids, **kw
        ).compile().as_text()
        fast = hlo_analysis.analyze(fast_txt)
        gspmd = hlo_analysis.analyze(gspmd_txt)
        out["collectives"] = {
            "devices": M,
            "bucket": int(bucket),
            "k": int(k),
            "sharded_operand_bytes": int(fast["collective_operand_total"]),
            "gspmd_operand_bytes": int(gspmd["collective_operand_total"]),
            "reduction": float(gspmd["collective_operand_total"]
                               / max(fast["collective_operand_total"], 1)),
        }

        # ---- closed loop: sharded modes ------------------------------------
        warm(row_srv)
        warm(batch_srv)
        warm(row_srv, "top_k", (1, k, 0))
        warm(gspmd_srv, "top_k", (1, k, 0))
        for qps in cfgp["predict_qps"]:
            cl_rows.append(_closed_loop_row(
                row_srv, shard_mode="row", query="predict", qps=qps,
                cfgp=cfgp, pool=queries))
            cl_rows.append(_closed_loop_row(
                batch_srv, shard_mode="batch", query="predict", qps=qps,
                cfgp=cfgp, pool=queries))
        cl_rows.append(_closed_loop_row(
            row_srv, shard_mode="row", query="top_k", qps=cfgp["top_k_qps"],
            cfgp=cfgp, pool=None, top_k_args=(1, k, 0)))
        cl_rows.append(_closed_loop_row(
            gspmd_srv, shard_mode="gspmd", query="top_k",
            qps=cfgp["top_k_qps"], cfgp=cfgp, pool=None,
            top_k_args=(1, k, 0)))

        row_max = max(r["achieved_qps"] for r in cl_rows
                      if r["shard_mode"] == "row" and r["query"] == "predict")
        batch_max = max(r["achieved_qps"] for r in cl_rows
                        if r["shard_mode"] == "batch")
        out["crossover"] = {
            "row_max_qps": float(row_max),
            "batch_max_qps": float(batch_max),
            "batch_vs_row": float(batch_max / row_max),
            "note": "max achieved predict q/s per table layout at the "
                    "offered-load ladder; serve.policy picks 'batch' "
                    "when traffic clears its threshold and the tables "
                    "fit replicated",
        }

    out["closed_loop"] = {"rows": cl_rows}
    out["platform"] = jax.default_backend()
    return out


# ---------------------------------------------------------------------------
# parent: subprocess with forced host devices, CSV rows, document assembly
# ---------------------------------------------------------------------------

def _run_child(smoke: bool, devices: int) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices}")
    # forced host devices are CPU devices: pin the platform so the child
    # never contends for an accelerator the parent's machine may hold
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(repo, "src"), repo,
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    cmd = [sys.executable, "-m", "benchmarks.bench_serve", "--measure"]
    if smoke:
        cmd.append("--smoke")
    proc = subprocess.run(cmd, env=env, cwd=repo, capture_output=True,
                          text=True, timeout=3600)
    if proc.returncode != 0:
        raise RuntimeError(
            f"serve child failed\nSTDOUT:\n{proc.stdout}\n"
            f"STDERR:\n{proc.stderr}")
    return json.loads(proc.stdout)


def run(smoke: bool = False, devices: int = DEVICES,
        out_path: str | None = None) -> dict:
    cfgp = SMOKE if smoke else FULL
    res = _run_child(smoke, devices)

    doc = {
        "schema": BENCH_SERVE_SCHEMA,
        "generated_by": "benchmarks/bench_serve.py",
        "smoke": smoke,
        "platform": res["platform"],
        "config": {
            "dims": list(cfgp["dims"]),
            "nnz": cfgp["nnz"],
            "rank": cfgp["rank"],
            "core_rank": cfgp["rank"],
            "k": cfgp["k"],
            "backend": "xla",
            "devices": res["devices"],
            "microbatch": cfgp["microbatch"],
            "max_request": cfgp["max_request"],
            "duration_s": cfgp["duration_s"],
            "concurrency": cfgp["concurrency"],
        },
        "throughput": res["throughput"],
        "closed_loop": res["closed_loop"],
    }
    for key in ("collectives", "crossover"):
        if key in res:
            doc[key] = res[key]
    validate_bench_serve(doc)

    thr = doc["throughput"]
    row("serve/per_query_us", 1e6 / thr["per_query_qps"],
        f"{thr['per_query_qps']:.0f} q/s")
    row("serve/bucketed_us", 1e6 / thr["bucketed_qps"],
        f"{thr['bucketed_qps']:.0f} q/s")
    row("serve/speedup_x", thr["speedup"], "bucketed vs per-query")
    row("serve/sweep_compiles", thr["sweep_compiles"],
        f"ladder bound {thr['ladder_bound']}")
    if "collectives" in doc:
        col = doc["collectives"]
        row("serve/topk_collective_sharded_B", col["sharded_operand_bytes"],
            f"M={col['devices']} bucket={col['bucket']} k={col['k']}")
        row("serve/topk_collective_gspmd_B", col["gspmd_operand_bytes"],
            f"{col['reduction']:.1f}x more than shard-local merge")
    for r in doc["closed_loop"]["rows"]:
        row(f"serve/loop_{r['shard_mode']}_{r['query']}"
            f"@{r['offered_qps']:.0f}",
            r["p50_ms"] * 1e3,
            f"p99={r['p99_ms']:.1f}ms achieved={r['achieved_qps']:.0f}q/s "
            f"shed={r['shed']}")
    if "crossover" in doc:
        x = doc["crossover"]
        row("serve/crossover_batch_vs_row", x["batch_vs_row"],
            f"row={x['row_max_qps']:.0f} batch={x['batch_max_qps']:.0f} q/s")

    if thr["speedup"] < 10:
        print(f"WARNING: bucketed speedup {thr['speedup']:.1f}x below "
              f"the 10x target")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(f"# wrote {out_path}", flush=True)
    return doc


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes / short loops (CI schema check)")
    ap.add_argument("--devices", type=int, default=DEVICES,
                    help="forced host devices for the child process")
    ap.add_argument("--out", default="",
                    help="write the validated BENCH_serve.json here")
    ap.add_argument("--measure", action="store_true",
                    help="internal: measure in-process and print JSON")
    args = ap.parse_args()
    if args.measure:
        print(json.dumps(measure(args.smoke)))
        return
    run(smoke=args.smoke, devices=args.devices, out_path=args.out or None)


if __name__ == "__main__":
    main()
