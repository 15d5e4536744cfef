"""Online training driver: supervised ingest → refresh → patch rounds.

The streaming loop production recommenders run.  Since PR 9 the round
itself lives in ``repro.serve.supervisor.RefreshSupervisor`` — a
background thread inside the serving process running

    1. **Ingest** — arrivals fold into the chunk-sharded ``NonzeroStore``
       (``store.append``) and the recent-nonzero window advances;
    2. **Refresh** — ``strategy.refresh_steps`` runs K factor-phase SGD
       steps over the window and reports the per-mode dirty-row union;
    3. **Patch** — ``TuckerServer.update_rows`` republishes only the
       dirty C^(n) rows behind the versioned atomic swap (or, when the
       drift tracker says so, one full ``refresh_tables()`` rebuild)

with retry/backoff per stage, a breaker into degraded serving when a
stage stays broken, and clean recovery after.  This driver is the
harness: it submits each round's arrivals, drains, probes the LIVE
server, and logs ``health()``.

``--inject-faults`` threads a deterministic ``FaultPlan`` through the
supervisor (grammar ``site@i:j:k`` / ``site%p`` over sites ingest,
transfer, refresh, publish — e.g. ``"refresh@0:1:2"`` fails the first
three refresh attempts then clears).  ``--expect-breaker`` asserts the
run degraded AND recovered — the CI fault-injection smoke contract.
``--verify`` cross-checks the final patched server against a fresh
``TuckerServer`` rebuilt from the refreshed params — bitwise for f32
tables, even after faulted rounds (stage-resume runs each refresh
exactly once).

Example (CI smoke shape):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    PYTHONPATH=src python -m repro.launch.online_train \
        --dims 24,18,12 --nnz 800 --warmup-steps 6 --rounds 3 \
        --refresh-steps 2 --batch 64 --rank 3 --core-rank 3 \
        --serve-shard-mode row --verify
"""
from __future__ import annotations

import argparse
import logging
import time

import jax
import numpy as np

from repro.core import FastTuckerConfig, init_state, rmse_mae
from repro.core import fasttucker as ft
from repro.core.sptensor import SparseTensor
from repro.data.pipeline import NonzeroStore
from repro.data.synthetic import planted_tensor
from repro.distributed import get_strategy
from repro.launch.mesh import make_host_mesh
from repro.runtime.fault import FaultPlan
from repro.serve import RefreshSupervisor, SupervisorConfig, TuckerServer

log = logging.getLogger("repro.online")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--strategy", default="local",
                    help="distributed strategy for warmup + refresh "
                         "(local | sync | strata | strata_overlap)")
    ap.add_argument("--dims", default="200,160,120")
    ap.add_argument("--nnz", type=int, default=20_000,
                    help="total planted nonzeros; --stream-fraction of "
                         "them arrive during the online rounds")
    ap.add_argument("--stream-fraction", type=float, default=0.3)
    ap.add_argument("--rank", type=int, default=4)
    ap.add_argument("--core-rank", type=int, default=4)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--warmup-steps", type=int, default=50,
                    help="offline SGD steps before serving starts")
    ap.add_argument("--rounds", type=int, default=5,
                    help="online ingest→refresh→patch rounds")
    ap.add_argument("--refresh-steps", type=int, default=4,
                    help="factor-phase steps per round (K)")
    ap.add_argument("--window", type=int, default=0,
                    help="recent-nonzero window per refresh "
                         "(0: one round's arrivals)")
    ap.add_argument("--backend", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--serve-shard-mode", default="none",
                    choices=["none", "row", "batch"],
                    help="serving-table layout (row/batch build a host "
                         "mesh over all devices)")
    ap.add_argument("--table-dtype", default=None,
                    choices=[None, "float32", "bfloat16"])
    ap.add_argument("--spill-dir", default="",
                    help="spill the ingest store to memory-mapped chunks")
    ap.add_argument("--verify", action="store_true",
                    help="assert the final patched tables match a full "
                         "server rebuild (bitwise for f32 tables)")
    ap.add_argument("--inject-faults", default="",
                    help="deterministic FaultPlan spec, e.g. "
                         "'refresh@0:1:2,publish%%0.1' (sites: ingest, "
                         "transfer, refresh, publish)")
    ap.add_argument("--expect-breaker", action="store_true",
                    help="assert the supervisor tripped into degraded "
                         "mode AND recovered (CI fault-smoke contract)")
    ap.add_argument("--max-attempts", type=int, default=3,
                    help="per-cycle retry budget before the breaker trips")
    args = ap.parse_args()
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    logging.basicConfig(level=logging.INFO)

    from repro.kernels import dispatch
    backend = dispatch.resolve_backend_name(args.backend)
    dispatch.get_backend(backend)

    dims = tuple(int(x) for x in args.dims.split(","))
    tensor = planted_tensor(dims, args.nnz, rank=args.rank,
                            core_rank=args.core_rank, noise=0.05,
                            seed=args.seed)
    train_t, test_t = tensor.split(0.1)

    # hold back the streaming tail: these nonzeros are NOT in the warmup
    # training set — they arrive round by round
    all_idx = np.asarray(train_t.indices)
    all_val = np.asarray(train_t.values)
    n_stream = int(len(all_val) * args.stream_fraction)
    n_warm = len(all_val) - n_stream
    warm_t = SparseTensor(train_t.indices[:n_warm], train_t.values[:n_warm],
                          dims)
    stream_idx, stream_val = all_idx[n_warm:], all_val[n_warm:]
    per_round = max(1, n_stream // max(args.rounds, 1))
    window = args.window or per_round

    strategy = get_strategy(args.strategy)
    mesh = make_host_mesh() if strategy.needs_mesh else None
    cfg = FastTuckerConfig(
        dims=dims, ranks=(args.rank,) * len(dims),
        core_rank=args.core_rank, batch_size=args.batch, backend=backend,
    )
    plan = strategy.prepare(warm_t, cfg, mesh, seed=args.seed)
    key = jax.random.PRNGKey(args.seed)
    key, init_key, loop_key = jax.random.split(key, 3)
    dstate = strategy.init(plan, init_state(init_key, cfg), loop_key)

    # ingest store mirrors the warmup set; each round appends into it
    # (the strata sampling layout for a later out-of-core retrain)
    num_workers = mesh.devices.size if mesh is not None else 1
    store = NonzeroStore.build(warm_t, num_workers,
                               spill_dir=args.spill_dir or None)

    log.info("warmup: %d steps of %s on %d resident nnz "
             "(%d held back to stream)",
             args.warmup_steps, strategy.name, n_warm, n_stream)
    step_fn = strategy.make_step(plan)
    while int(dstate.step) < args.warmup_steps:
        dstate = step_fn(dstate)
    fetch = getattr(step_fn, "prefetcher", None)
    if fetch is not None:
        fetch.close()
    params = strategy.eval_params(plan, dstate)
    r, m = rmse_mae(params, test_t, ft.predict)
    log.info("warmup done at step %d: rmse %.4f mae %.4f",
             int(dstate.step), r, m)

    serve_mesh = None
    if args.serve_shard_mode in ("row", "batch"):
        serve_mesh = mesh if mesh is not None else make_host_mesh()
    server = TuckerServer(
        params, backend=backend, mesh=serve_mesh,
        shard_mode=args.serve_shard_mode if serve_mesh else "auto",
        table_dtype=args.table_dtype)
    log.info("serving %s tables (%s, version %d)", server.shard_mode,
             server.table_dtype, server.table_version)

    fault_plan = (FaultPlan.parse(args.inject_faults, seed=args.seed)
                  if args.inject_faults else None)
    sup = RefreshSupervisor(
        server, strategy, plan, dstate, store=store,
        config=SupervisorConfig(
            refresh_steps=args.refresh_steps, window=window,
            max_attempts=args.max_attempts, backoff_base_s=0.005,
            backoff_cap_s=0.05, degraded_retry_s=0.02, seed=args.seed),
        fault_plan=fault_plan,
        history=(all_idx[:n_warm], all_val[:n_warm]))
    sup.start()
    try:
        for rd in range(args.rounds):
            lo = rd * per_round
            hi = n_stream if rd == args.rounds - 1 else (rd + 1) * per_round
            new_idx, new_val = stream_idx[lo:hi], stream_val[lo:hi]
            if len(new_val) == 0:
                break
            t0 = time.time()
            sup.submit(new_idx, new_val)
            if not sup.drain(timeout=600):
                raise RuntimeError(
                    f"round {rd} did not publish within 600s: "
                    f"{sup.health()}")
            # probe the LIVE server with queries drawn from the arrivals
            probe = new_idx[: min(64, len(new_idx))]
            pred = np.asarray(server.predict(probe))
            params = strategy.eval_params(plan, sup.dstate)
            r, m = rmse_mae(params, test_t, ft.predict)
            h = sup.health()
            log.info(
                "round %d: +%d nnz (store %d), refresh K=%d dirty %s, "
                "table v%d %s, state %s (trips %d, recoveries %d, "
                "faults %d), probe |x̂| %.3f, rmse %.4f mae %.4f (%.0f ms)",
                rd, len(new_val), sup.store.meta["nnz"],
                args.refresh_steps, h["last_dirty"], h["generation"],
                h["last_publish"]["kind"], h["state"], h["breaker_trips"],
                h["recoveries"], h["faults_injected"],
                float(np.abs(pred).mean()), r, m, (time.time() - t0) * 1e3)
    finally:
        sup.stop()

    health = sup.health()
    params = strategy.eval_params(plan, sup.dstate)
    if args.inject_faults:
        assert health["faults_injected"] > 0, (
            "--inject-faults given but no fault fired — check the spec "
            f"against the round count: {args.inject_faults!r}")
        log.info("fault injection: %d faults fired (%s), %d retries, "
                 "%d breaker trips, %d recoveries",
                 health["faults_injected"], fault_plan.fired_by_site(),
                 health["retries"], health["breaker_trips"],
                 health["recoveries"])
    if args.expect_breaker:
        assert health["breaker_trips"] >= 1, (
            f"expected a breaker trip, got none: {health}")
        assert health["recoveries"] >= 1, (
            f"expected a recovery after degradation: {health}")
        log.info("degraded-then-recovered contract OK "
                 "(%d trips, %d recoveries)",
                 health["breaker_trips"], health["recoveries"])

    if args.verify:
        ref = TuckerServer(
            params, backend=backend, mesh=serve_mesh,
            shard_mode=args.serve_shard_mode if serve_mesh else "auto",
            table_dtype=args.table_dtype)
        exact = np.dtype(server.table_dtype) == np.dtype(np.float32)
        for n in range(server.order):
            a = np.asarray(server._tables[n], np.float32)
            b = np.asarray(ref._tables[n], np.float32)
            if exact:
                assert (a == b).all(), f"mode {n}: patched ≠ rebuilt"
            else:
                np.testing.assert_allclose(a, b, rtol=0.05, atol=0.05)
            np.testing.assert_allclose(
                np.asarray(server._colsums[n]), np.asarray(ref._colsums[n]),
                rtol=1e-4, atol=1e-4)
        log.info("verify OK: patched tables match a full rebuild "
                 "(%s) after %d generations",
                 "bitwise" if exact else "tolerance-banded",
                 server.table_version)


if __name__ == "__main__":
    main()
