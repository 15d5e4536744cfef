"""STD (sparse Tucker) training driver — the paper's own workload.

ONE strategy-agnostic loop: ``--strategy`` selects from the distributed
registry (``repro.distributed``):

    ``local``           single device
    ``sync``            data-parallel minibatch, psum'd gradients
    ``strata``          faithful Fig.-2 stratified rotation (LHC schedule)
    ``strata_overlap``  fused strata chunks with communication-hidden
                        rotations

``--compress`` (int8 error-feedback gradient compression) and
``--ckpt-dir`` (uniform save/restore, ``--resume`` to continue) work under
every strategy. ``--mode`` is a deprecated alias for ``--strategy``;
``--backend`` selects the kernel backend from ``repro.kernels.dispatch``
(``xla`` reference jnp, ``pallas`` compiled, ``pallas_interpret``
CPU-testable kernels; default resolves ``$REPRO_KERNEL_BACKEND`` then
``xla``).

``--phase-split`` routes every strategy's step through the
``StepIntermediates``-cached two-phase update (bitwise identical in f32,
fewer real kernel dots on the Pallas backends); ``--sorted-batches``
switches every strategy to the mode-sorted batch layout (deduplicated
row gather + segmented-reduce scatter — f32-bitwise on xla, and on the
Pallas backends replaces the O(rows×B) one-hot scatter sweep with the
O(B) ``segment_reduce`` kernel); ``--dtype bfloat16``
stores factors/core factors in bf16 with f32 MXU accumulation
(``--accum-dtype``); ``--donate on`` (default ``auto``: off-CPU only)
donates the step's DistState buffers into the compiled update so XLA
aliases instead of reallocating them.

``--warm-start`` initializes with the randomized sketched warm start
(``core.sketch``: sampled Khatri–Rao range finders → sketched core LS →
alternating-LS refinement) instead of the cold uniform draw —
deterministic under ``--seed`` and strategy-agnostic (the warm params
are built before the strategy pads/partitions them).  ``--sketch-*``
expose the sketch knobs and ``--warm-step-offset`` resumes the decaying
LR schedule mid-way (see docs/convergence.md).

``--adaptive-rank`` turns on the validation-plateau rank controller
(``core.adaptive``): when eval RMSE stalls the Kruskal core rank doubles
(up to ``--max-core-rank``); if a doubling buys nothing it reverts and
freezes.  Transitions are pad/truncate on the core factors, the strategy
re-prepares at the new rank (compiled steps stay log-many), and
``--refine als|ccd`` optionally polishes the factors with the exact
baseline epochs after each transition.  Incompatible with
``--out-of-core`` (the prefetcher pins per-stratum buffers to one plan)
and ``--ckpt-dir`` (checkpoints assume one config per run).

``--out-of-core`` (strata flavors) feeds the schedule from a
chunk-sharded ``data.pipeline.NonzeroStore`` (``--spill-dir`` memory-maps
the chunks to disk) through the ``StratumPrefetcher`` — each stratum's
block is ``device_put`` on a background thread ``--prefetch-depth``
strata ahead of use, so steady-state step time is max(compute, transfer)
and the full Ω never has to be device-resident.  The trajectory is
bitwise-identical to the resident path under the same seed/schedule.
End-of-interval throughput (steps/s, nnz/s) and peak live device bytes
are logged so ingestion-bound runs are diagnosable from the console.
Example:

    PYTHONPATH=src python -m repro.launch.std_train --strategy strata_overlap \
        --dims 2000,1500,1000 --nnz 500000 --steps 300 --rank 8 \
        --core-rank 8 --backend pallas_interpret --phase-split \
        --dtype bfloat16
"""
from __future__ import annotations

import argparse
import contextlib
import logging
import time

import jax

from repro.checkpoint.manager import CheckpointManager
from repro.core import FastTuckerConfig, init_state, rmse_mae
from repro.core import fasttucker as ft
from repro.data.synthetic import planted_tensor
from repro.distributed import available_strategies, get_strategy
from repro.launch.mesh import make_host_mesh

log = logging.getLogger("repro.std")


def peak_device_bytes() -> tuple[int, str]:
    """(bytes, how-measured) for the busiest local device.

    Real allocators report ``peak_bytes_in_use``; CPU XLA has no
    memory_stats, so fall back to the current live-buffer total — an
    instantaneous lower bound, labeled as such.
    """
    peak = 0
    for d in jax.local_devices():
        stats = getattr(d, "memory_stats", None)
        stats = stats() if callable(stats) else None
        if stats:
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    if peak:
        return peak, "allocator peak"
    return sum(x.nbytes for x in jax.live_arrays()), "live arrays"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--strategy", default=None,
                    help="distributed strategy: "
                         "local | sync | strata | strata_overlap "
                         "(default: $REPRO_DIST_STRATEGY or local)")
    ap.add_argument("--mode", default=None,
                    choices=["local", "sync", "strata"],
                    help="DEPRECATED: alias for --strategy")
    ap.add_argument("--dims", default="1000,800,600")
    ap.add_argument("--nnz", type=int, default=200_000)
    ap.add_argument("--rank", type=int, default=8)
    ap.add_argument("--core-rank", type=int, default=8)
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--eval-every", type=int, default=50)
    ap.add_argument("--compress", action="store_true",
                    help="int8 error-feedback gradient compression "
                         "(any strategy)")
    ap.add_argument("--seed", type=int, default=0,
                    help="data/schedule/init seed")
    ap.add_argument("--backend", default=None,
                    help="kernel backend: xla | pallas | pallas_interpret "
                         "(default: $REPRO_KERNEL_BACKEND or xla)")
    ap.add_argument("--phase-split", action="store_true",
                    help="two-phase factor/core step with the "
                         "StepIntermediates cache (bitwise-identical "
                         "numerics; fewer real kernel dots on Pallas)")
    ap.add_argument("--sorted-batches", action="store_true",
                    help="mode-sorted batch layout: gather each unique "
                         "factor row once and scatter through the "
                         "segmented-reduce op (f32-bitwise on xla; "
                         "replaces the O(rows×B) one-hot sweep on the "
                         "Pallas backends)")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="parameter storage dtype (bf16 halves parameter "
                         "memory and rotation bytes)")
    ap.add_argument("--accum-dtype", default="float32",
                    choices=["float32"],
                    help="MXU dot / gradient accumulation dtype")
    ap.add_argument("--donate", default="auto",
                    choices=["auto", "on", "off"],
                    help="donate the DistState buffers into the compiled "
                         "step (auto: off-CPU only)")
    ap.add_argument("--out-of-core", action="store_true",
                    help="feed the strata strategies from a chunk-sharded "
                         "NonzeroStore through the host→device stratum "
                         "prefetcher instead of resident device buckets "
                         "(trajectory-identical under the same seed)")
    ap.add_argument("--prefetch-depth", type=int, default=2,
                    help="strata issued to device ahead of use "
                         "(0 = synchronous load per step)")
    ap.add_argument("--spill-dir", default="",
                    help="spill the nonzero store to memory-mapped .npy "
                         "chunks in this directory (default: in-memory "
                         "chunks — same prefetch path, no disk)")
    ap.add_argument("--warm-start", action="store_true",
                    help="sketched randomized warm start (core.sketch) "
                         "instead of the cold uniform init")
    ap.add_argument("--sketch-passes", type=int, default=2,
                    help="sample passes feeding the range finder")
    ap.add_argument("--sketch-oversample", type=int, default=4,
                    help="sketch width = rank + oversample")
    ap.add_argument("--sketch-batch", type=int, default=0,
                    help="sketch samples per pass (0 → --batch)")
    ap.add_argument("--sketch-refine-passes", type=int, default=4,
                    help="alternating ALS/core-LS polish passes")
    ap.add_argument("--warm-step-offset", type=int, default=0,
                    help="start the decaying LR schedule at this step "
                         "after a warm start (0 = cold schedule)")
    ap.add_argument("--adaptive-rank", action="store_true",
                    help="grow/shrink the Kruskal core rank on "
                         "validation-RMSE plateaus (core.adaptive)")
    ap.add_argument("--max-core-rank", type=int, default=0,
                    help="adaptive-rank growth cap (0 → 4x --core-rank)")
    ap.add_argument("--plateau-tol", type=float, default=0.01,
                    help="relative RMSE improvement below this counts "
                         "as a plateau observation")
    ap.add_argument("--plateau-patience", type=int, default=2,
                    help="consecutive plateau observations before a "
                         "rank transition")
    ap.add_argument("--refine", default="", choices=["", "als", "ccd"],
                    help="polish factors with exact baseline epochs "
                         "after each rank transition")
    ap.add_argument("--refine-passes", type=int, default=1,
                    help="epochs per post-transition refinement")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest checkpoint in --ckpt-dir "
                         "(the dir must belong to a run with the same "
                         "config/strategy — the manager keeps only the "
                         "highest-numbered steps)")
    args = ap.parse_args()
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    logging.basicConfig(level=logging.INFO)

    # the strategies read the donation policy when they BUILD their jitted
    # steps, so pin it before any strategy.make_step/lower_step call
    import os

    from repro.distributed.base import DONATE_ENV_VAR
    os.environ[DONATE_ENV_VAR] = args.donate

    from repro.kernels import dispatch
    backend = dispatch.resolve_backend_name(args.backend)
    dispatch.get_backend(backend)  # fail fast on typos, before data gen

    # fail fast on strategy typos too (--mode maps through with a warning)
    strategy = get_strategy(args.strategy, mode=args.mode)
    log.info("strategy: %s (available: %s), kernel backend: %s, "
             "phase_split: %s, sorted_batches: %s, dtype: %s (accum %s), "
             "donate: %s",
             strategy.name, "/".join(available_strategies()), backend,
             args.phase_split, args.sorted_batches, args.dtype,
             args.accum_dtype, args.donate)

    dims = tuple(int(x) for x in args.dims.split(","))
    tensor = planted_tensor(dims, args.nnz, rank=args.rank,
                            core_rank=args.core_rank, noise=0.05,
                            seed=args.seed)
    train_t, test_t = tensor.split(0.1)
    cfg = FastTuckerConfig(
        dims=dims, ranks=(args.rank,) * len(dims),
        core_rank=args.core_rank, batch_size=args.batch,
        backend=backend, phase_split=args.phase_split,
        sorted_batches=args.sorted_batches,
        dtype=args.dtype, accum_dtype=args.accum_dtype,
        init="sketched" if args.warm_start else "random",
        sketch_passes=args.sketch_passes,
        sketch_oversample=args.sketch_oversample,
        sketch_batch=args.sketch_batch,
        sketch_refine_passes=args.sketch_refine_passes,
        warm_step_offset=args.warm_step_offset,
    )

    controller = None
    if args.adaptive_rank:
        if args.out_of_core:
            raise SystemExit(
                "--adaptive-rank rebuilds the strategy plan at each rank "
                "transition, which the out-of-core prefetcher does not "
                "support; drop --out-of-core")
        if args.ckpt_dir:
            raise SystemExit(
                "--adaptive-rank changes the config mid-run; checkpoints "
                "assume one config per run — drop --ckpt-dir")
        from repro.core import RankController
        max_rank = args.max_core_rank or 4 * args.core_rank
        controller = RankController(
            args.core_rank, max_rank, tol=args.plateau_tol,
            patience=args.plateau_patience)

    mesh = make_host_mesh() if strategy.needs_mesh else None
    if args.out_of_core:
        if strategy.name not in ("strata", "strata_overlap"):
            raise SystemExit(
                "--out-of-core streams per-stratum chunks and therefore "
                "requires a strata strategy (got "
                f"{strategy.name!r}); run with --strategy strata or "
                "strata_overlap")
        from repro.data.pipeline import NonzeroStore
        store = NonzeroStore.build(train_t, mesh.devices.size,
                                   spill_dir=args.spill_dir or None)
        log.info(
            "out-of-core store: %d strata x %d workers x chunk %d "
            "(%.1f MiB total, %.2f MiB/stratum, %s), prefetch depth %d",
            store.num_strata, store.num_workers, store.chunk_len,
            store.nbytes / 2**20, store.stratum_nbytes / 2**20,
            f"spilled to {store.path}" if store.spilled else "in-memory",
            args.prefetch_depth)
        plan = strategy.prepare(train_t, cfg, mesh, compress=args.compress,
                                seed=args.seed, store=store,
                                prefetch_depth=args.prefetch_depth)
    else:
        plan = strategy.prepare(train_t, cfg, mesh, compress=args.compress,
                                seed=args.seed)

    key = jax.random.PRNGKey(args.seed)
    key, init_key, loop_key = jax.random.split(key, 3)
    if args.warm_start:
        t_warm = time.time()
        state0 = init_state(init_key, cfg, train_t.indices, train_t.values)
        jax.block_until_ready(state0.params.factors)
        log.info("sketched warm start in %.2fs (LR schedule from step %d)",
                 time.time() - t_warm, int(state0.step))
    else:
        state0 = init_state(init_key, cfg)
    dstate = strategy.init(plan, state0, loop_key)

    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    if ckpt and args.resume and ckpt.latest_step() is not None:
        dstate = strategy.restore(plan, ckpt, dstate)
        log.info("resumed from step %d", int(dstate.step))
        if int(dstate.step) >= args.steps:
            log.warning(
                "checkpoint step %d >= --steps %d: nothing to train — "
                "is %s a stale dir from another run?",
                int(dstate.step), args.steps, args.ckpt_dir)

    step_fn = strategy.make_step(plan)
    nnz_step = strategy.nnz_per_step(plan)
    t0 = time.time()
    start_step = last_eval = last_logged = int(dstate.step)
    t_int = t0
    with (mesh if mesh is not None else contextlib.nullcontext()):
        while int(dstate.step) < args.steps:
            dstate = step_fn(dstate)
            i = int(dstate.step)
            if i // args.eval_every > last_eval // args.eval_every \
                    or i >= args.steps:
                last_eval = i
                # throughput over the train-only interval (evals excluded)
                now = time.time()
                if i > last_logged and now > t_int:
                    sps = (i - last_logged) / (now - t_int)
                    mem, how = peak_device_bytes()
                    log.info(
                        "throughput: %.2f steps/s, %.3g nnz/s, "
                        "device bytes %.1f MiB (%s)",
                        sps, sps * nnz_step, mem / 2**20, how)
                last_logged = i
                params = strategy.eval_params(plan, dstate)
                r, m = rmse_mae(params, test_t, ft.predict)
                log.info("step %d rmse %.4f mae %.4f (core rank %d)",
                         i, r, m, cfg.core_rank)
                if ckpt:
                    strategy.save(plan, ckpt, dstate)
                decision = controller.observe(r) if controller else None
                if decision is not None and i < args.steps:
                    from repro.core import (TrainState, refine_factors,
                                            resize_core_rank)
                    from repro.core.sampling import sample_batch_arrays
                    from repro.core.sptensor import SparseTensor
                    rank_key = jax.random.fold_in(key, 1000 + i)
                    params, cfg = resize_core_rank(
                        params, cfg, decision.new_rank, rank_key)
                    if args.refine:
                        ridx, rval = sample_batch_arrays(
                            jax.random.fold_in(key, 2000 + i),
                            train_t.indices, train_t.values,
                            min(train_t.indices.shape[0], 65536))
                        params = refine_factors(
                            params, cfg, SparseTensor(ridx, rval, dims),
                            method=args.refine, passes=args.refine_passes)
                    log.info("rank %s -> %d at step %d (%s)",
                             decision.action, decision.new_rank, i,
                             decision.reason)
                    plan = strategy.prepare(train_t, cfg, mesh,
                                            compress=args.compress,
                                            seed=args.seed)
                    dstate = strategy.init(
                        plan, TrainState(params, dstate.step), loop_key)
                    step_fn = strategy.make_step(plan)
                    nnz_step = strategy.nnz_per_step(plan)
                t_int = time.time()
    fetch = getattr(step_fn, "prefetcher", None)
    if fetch is not None:
        fetch.close()
    elapsed = time.time() - t0
    steps_done = int(dstate.step) - start_step
    log.info("%s done in %.1fs (%.2f steps/s, %.3g nnz/s end to end)",
             strategy.name, elapsed, steps_done / max(elapsed, 1e-9),
             steps_done * nnz_step / max(elapsed, 1e-9))


if __name__ == "__main__":
    main()
