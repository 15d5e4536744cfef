"""Batched FastTucker serving driver — microbatch queue over a TuckerServer.

The Tucker counterpart of the LM driver (``repro.launch.serve``): loads
trained ``(factors, core_factors)`` from a ``checkpoint.manager`` directory
(or trains a quick model first when the directory is empty), stands up a
``repro.serve.TuckerServer``, and pushes a stream of variable-size query
batches through a microbatch queue, reporting per-flush latency
percentiles, sustained queries/s, and the (bounded) compile count.

    PYTHONPATH=src python -m repro.launch.serve_tucker \
        --dims 300,200,40 --nnz 30000 --train-steps 200 \
        --requests 200 --microbatch 256 --backend pallas_interpret

``--sharded`` serves the per-mode tables over the host mesh (forced
device counts via XLA_FLAGS work the same as for training);
``--shard-mode {auto,row,batch}`` picks the layout (``auto`` consults
``serve.policy`` with ``--expected-qps``).

``--qps RATE --duration SECONDS`` switches the driver to the CLOSED-LOOP
front end (``repro.serve.frontend``): concurrent clients offer ``RATE``
queries/s through the asyncio microbatch queue with real admission
control — ``--admission-max-queue`` bounds waiting queries,
``--admission-deadline-ms`` sheds stale ones at flush — and the report
is achieved QPS, shed counts, and per-bucket latency percentiles.
"""
from __future__ import annotations

import argparse
import json
import logging
import time

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.core import FastTuckerConfig, init_state, rmse_mae
from repro.core import fasttucker as ft
from repro.data.synthetic import ratings_tensor
from repro.distributed import get_strategy
from repro.launch.mesh import make_host_mesh
from repro.serve import (
    AdmissionConfig, TuckerServer, load_params_from_checkpoint,
    run_closed_loop,
)

log = logging.getLogger("repro.serve_tucker")


def _train_and_save(args, tensor, cfg, ckpt: CheckpointManager | None):
    """Quick `local`-strategy training run so the CLI works standalone."""
    st = get_strategy("local")
    plan = st.prepare(tensor, cfg, None, seed=args.seed)
    key = jax.random.PRNGKey(args.seed)
    key, init_key, loop_key = jax.random.split(key, 3)
    ds = st.init(plan, init_state(init_key, cfg), loop_key)
    step = st.make_step(plan)
    t0 = time.time()
    while int(ds.step) < args.train_steps:
        ds = step(ds)
    log.info("trained %d steps in %.1fs", args.train_steps, time.time() - t0)
    if ckpt is not None:
        st.save(plan, ckpt, ds)
        log.info("checkpointed step %d to %s", int(ds.step), ckpt.dir)
    return st.eval_params(plan, ds)


def main() -> None:
    ap = argparse.ArgumentParser(
        description="Batched FastTucker (STD) serving; the LM decode driver "
                    "is repro.launch.serve.")
    ap.add_argument("--dims", default="300,200,40")
    ap.add_argument("--nnz", type=int, default=30_000)
    ap.add_argument("--rank", type=int, default=8)
    ap.add_argument("--core-rank", type=int, default=8)
    ap.add_argument("--train-steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=2048,
                    help="training |Ψ| (only when training fresh)")
    ap.add_argument("--ckpt-dir", default="",
                    help="load factors from here when it has a committed "
                         "step; otherwise train then save here")
    ap.add_argument("--backend", default=None,
                    help="kernel backend: xla | pallas | pallas_interpret")
    ap.add_argument("--sharded", action="store_true",
                    help="serve the tables sharded over the host mesh")
    ap.add_argument("--shard-mode", default="auto",
                    choices=("auto", "row", "batch"),
                    help="sharded table layout (auto → serve.policy "
                         "decides from table bytes × --expected-qps)")
    ap.add_argument("--expected-qps", type=float, default=None,
                    help="declared traffic for the auto shard policy")
    ap.add_argument("--requests", type=int, default=200,
                    help="number of query batches to stream")
    ap.add_argument("--max-request", type=int, default=512,
                    help="largest single request (batch sizes are drawn "
                         "log-uniform in [1, max])")
    ap.add_argument("--microbatch", type=int, default=256,
                    help="queue flush threshold (queries per served batch)")
    ap.add_argument("--top-k", type=int, default=5)
    ap.add_argument("--qps", type=float, default=None,
                    help="closed-loop mode: offered query rate (switches "
                         "the driver to the async front end)")
    ap.add_argument("--duration", type=float, default=5.0,
                    help="closed-loop mode: seconds of offered load")
    ap.add_argument("--concurrency", type=int, default=16,
                    help="closed-loop mode: number of clients")
    ap.add_argument("--admission-max-queue", type=int, default=4096,
                    help="bounded queue: max waiting queries before "
                         "submissions shed")
    ap.add_argument("--admission-deadline-ms", type=float, default=200.0,
                    help="shed queued requests older than this at flush")
    ap.add_argument("--admission-max-wait-ms", type=float, default=2.0,
                    help="flush timer: max time a lone request waits "
                         "for a microbatch to fill")
    ap.add_argument("--admission-slo-ms", type=float, default=None,
                    help="latency SLO budget per request (alarm counter "
                         "slo_violations in the closed-loop report; "
                         "answers still flow past the budget)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    logging.basicConfig(level=logging.INFO)

    from repro.kernels import dispatch
    backend = dispatch.resolve_backend_name(args.backend)
    dispatch.get_backend(backend)  # fail fast on typos, before data gen

    dims = tuple(int(x) for x in args.dims.split(","))
    tensor = ratings_tensor(dims, nnz=args.nnz, seed=args.seed)
    train_t, test_t = tensor.split(0.1)
    cfg = FastTuckerConfig(
        dims=dims, ranks=(args.rank,) * len(dims), core_rank=args.core_rank,
        batch_size=args.batch, backend=backend,
    )

    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    if ckpt is not None and ckpt.latest_step() is not None:
        params, step = load_params_from_checkpoint(args.ckpt_dir, dims=dims)
        log.info("loaded checkpoint step %d from %s", step, args.ckpt_dir)
    else:
        params = _train_and_save(args, train_t, cfg, ckpt)

    mesh = make_host_mesh() if args.sharded else None
    server = TuckerServer(params, backend=backend, mesh=mesh,
                          shard_mode=args.shard_mode if mesh else "auto",
                          expected_qps=args.expected_qps)
    r, m = rmse_mae(params, test_t, ft.predict)
    log.info("serving %s (backend=%s, shard_mode=%s) — held-out rmse %.4f "
             "mae %.4f", "×".join(map(str, dims)), backend,
             server.shard_mode, float(r), float(m))
    if server.shard_decision is not None:
        log.info("shard policy: %s", server.shard_decision)

    if args.qps is not None:
        # ---- closed-loop async front end with admission control -----------
        admission = AdmissionConfig(
            max_queue=args.admission_max_queue,
            deadline_ms=args.admission_deadline_ms,
            microbatch=args.microbatch,
            max_wait_ms=args.admission_max_wait_ms,
            slo_ms=args.admission_slo_ms,
        )
        report = run_closed_loop(
            server, qps=args.qps, duration_s=args.duration,
            concurrency=args.concurrency, max_request=args.max_request,
            admission=admission,
            request_pool=np.asarray(test_t.indices, np.int32),
            seed=args.seed + 1,
        )
        log.info("closed loop: offered %.0f q/s → achieved %.0f q/s over "
                 "%.1fs (%d served / %d shed-queue / %d shed-deadline), "
                 "latency p50 %.2fms p99 %.2fms across %d flushes",
                 report["offered_qps"], report["achieved_qps"],
                 report["duration_s"], report["served_requests"],
                 report["shed_queue_full"], report["shed_deadline"],
                 report["latency_ms"]["p50"] or float("nan"),
                 report["latency_ms"]["p99"] or float("nan"),
                 report["flushes"])
        for bucket, row in report["by_bucket"].items():
            log.info("  bucket %s: p50 %.2fms p95 %.2fms p99 %.2fms "
                     "(%d requests)", bucket, row["p50"], row["p95"],
                     row["p99"], row["count"])
        if report.get("slo_violations"):
            log.info("  SLO violations (budget %s ms): %s",
                     report["slo_budget_ms"], report["slo_violations"])
        print(json.dumps(report, indent=1))
        return

    # ---- microbatch queue over a stream of variable-size requests ----------
    rng = np.random.default_rng(args.seed + 1)
    sizes = np.exp(rng.uniform(0, np.log(args.max_request),
                               args.requests)).astype(int).clip(1)
    all_idx = np.asarray(test_t.indices)
    queue: list[np.ndarray] = []
    queued = 0
    flush_lat: list[float] = []
    served = 0
    t0 = time.time()
    for sz in sizes:
        pick = rng.integers(0, len(all_idx), int(sz))
        queue.append(all_idx[pick])
        queued += int(sz)
        if queued >= args.microbatch:
            batch = np.concatenate(queue)
            t1 = time.time()
            jax.block_until_ready(server.predict(batch))
            flush_lat.append(time.time() - t1)
            served += len(batch)
            queue, queued = [], 0
    if queue:
        batch = np.concatenate(queue)
        t1 = time.time()
        jax.block_until_ready(server.predict(batch))
        flush_lat.append(time.time() - t1)
        served += len(batch)
    wall = time.time() - t0

    lat = np.array(flush_lat) * 1e3
    log.info("served %d queries in %d flushes / %.2fs — %.0f q/s, "
             "flush latency p50 %.2fms p95 %.2fms, %d compiled buckets "
             "(ladder bound %d)",
             served, len(flush_lat), wall, served / max(wall, 1e-9),
             float(np.percentile(lat, 50)), float(np.percentile(lat, 95)),
             server.predict_cache_size, len(server.ladder))

    # ---- top-k recommendation demo -----------------------------------------
    ids = rng.integers(0, dims[0], 3)
    scores, items = server.top_k(0, ids, k=args.top_k)
    for b, uid in enumerate(ids):
        log.info("mode-0 entity %d → top-%d mode-1 items %s (scores %s)",
                 int(uid), args.top_k, np.asarray(items[b]).tolist(),
                 np.round(np.asarray(scores[b]), 3).tolist())


if __name__ == "__main__":
    main()
