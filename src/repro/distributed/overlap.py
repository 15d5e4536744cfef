"""``strata_overlap`` strategy — Fig. 2's pipeline with hidden rotations.

Same stratified schedule and per-stratum math as ``strata``, fused over a
chunk of K consecutive schedule entries inside ONE jitted shard_map step,
with the factor-shard rotations double-buffered:

  * shards stay in rotated position between strata — moving from stratum
    digits d to d' costs one ppermute by (d' − d) mod M per mode instead of
    the rotate-back + rotate-in pair (≤ half the collective bytes of
    ``strata``, fewer when consecutive digits coincide and the rotation is
    skipped entirely);
  * stratum s+1's rotation is ISSUED immediately after stratum s's row
    update, BEFORE stratum s's core-factor psum/update and stratum s+1's
    sampling/gather — none of which depend on the rotated shards — so XLA's
    scheduler is free to run the collective-permutes concurrently with that
    compute (async collective-permute-start/done on TPU). This is the
    communication-hiding emphasis of cuFasterTucker, expressed at the HLO
    level; ``launch.hlo_analysis.overlap_stats`` measures the hidden-flops
    window in the compiled step.

The chunk's digit sequence is static per compiled variant (the schedule is
pre-sampled per run), so rotations stay static ppermutes; at most ⌈S/K⌉
variants compile and are reused every epoch. Trajectories are identical to
``strata`` under the same seed/schedule: same per-stratum sample keys
(``fold_in(base, global_step)``), same update expressions — only the
rotation bookkeeping differs, and rotations are pure data movement.

Phase-split / mixed precision / mode-sorted batches ride through
``stratum_row_update`` (shared with ``strata``):
``FastTuckerConfig(phase_split=True)`` routes each stratum's gradients
through the ``StepIntermediates``-cached two-phase kernels,
``dtype="bfloat16"`` shards/rotates bf16 factor rows — HALF the ppermute
bytes per rotation — while the gradient psum stays f32, and
``sorted_batches=True`` sorts each device's localized draw per mode
(dedup gather + ``segment_reduce`` scatter; block localization preserves
row order, so the sorted layout composes with the rotated shard
positions).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.fasttucker import FastTuckerParams

from .base import DistState, step_donation
from .strata import (
    StrataRunPlan, StrataStrategy, _prepare_run_plan, core_update,
    rotate_shard, strata_state_spec, stratum_row_update,
)

DEFAULT_CHUNK = 4


@dataclasses.dataclass
class OverlapPlan(StrataRunPlan):
    chunk: int = DEFAULT_CHUNK


def _build_chunk_specializer(plan: OverlapPlan):
    from jax import shard_map

    cfg, layout, axis = plan.cfg, plan.layout, plan.axis
    M, N = layout.num_workers, cfg.order
    spec = strata_state_spec(cfg, axis, plan.compress)
    home = (0,) * N

    @functools.lru_cache(maxsize=None)
    def specialized(digit_seq: tuple):
        K = len(digit_seq)

        def local_chunk(dstate: DistState, idx_c, val_c, msk_c) -> DistState:
            # per-device blocks (1, K, L, ·) → (K, L, ·)
            idx_c, val_c, msk_c = idx_c[0], val_c[0], msk_c[0]
            rot = [rotate_shard(dstate.params.factors[n], digit_seq[0][n],
                                M, axis) for n in range(N)]
            core_f = dstate.params.core_factors
            ef = tuple(e[0] for e in dstate.ef)
            for k, digits in enumerate(digit_seq):
                step_no = dstate.step + k
                skey = jax.random.fold_in(dstate.key, step_no)
                new_rot, core_grads = stratum_row_update(
                    cfg, layout, axis, digits, rot, core_f,
                    idx_c[k], val_c[k], msk_c[k], step_no, skey)
                # double buffer: issue the rotation toward the NEXT stratum
                # (home after the last) right away; the core psum/update and
                # the next stratum's sampling/gather below don't touch the
                # rotated shards, so the permutes overlap that compute
                nxt = digit_seq[k + 1] if k + 1 < K else home
                rot = [
                    rotate_shard(new_rot[n], (nxt[n] - digits[n]) % M,
                                 M, axis)
                    for n in range(N)
                ]
                core_f, ef = core_update(cfg, axis, M, core_f, core_grads,
                                         ef, step_no, plan.compress)
            ef = tuple(e[None] for e in ef)
            return DistState(FastTuckerParams(tuple(rot), core_f),
                             dstate.step + K, dstate.key, ef)

        sharded = shard_map(
            local_chunk,
            mesh=plan.mesh,
            in_specs=(spec, P(axis), P(axis), P(axis)),
            out_specs=spec,
            check_vma=False,
        )
        return jax.jit(sharded, donate_argnums=step_donation())

    return specialized


class StrataOverlapStrategy(StrataStrategy):
    """Inherits ``init`` (padded factors + EF) and the row-trimming
    ``eval_params`` from ``StrataStrategy``; only the step changes."""

    name = "strata_overlap"

    def __init__(self, chunk: int = DEFAULT_CHUNK):
        self.chunk = chunk

    def prepare(self, tensor, cfg, mesh, *, compress: bool = False,
                seed: int = 0, store=None,
                prefetch_depth: int = 2) -> OverlapPlan:
        base = _prepare_run_plan(tensor, cfg, mesh, compress, seed,
                                 store=store, prefetch_depth=prefetch_depth)
        chunk = max(1, min(self.chunk, len(base.schedule)))
        return OverlapPlan(
            cfg=base.cfg, mesh=base.mesh, layout=base.layout,
            schedule=base.schedule, digits=base.digits,
            compress=base.compress, axis=base.axis, store=base.store,
            prefetch_depth=base.prefetch_depth, chunk=chunk)

    def steps_per_call(self, plan: OverlapPlan) -> int:
        return plan.chunk

    def nnz_per_step(self, plan: OverlapPlan) -> int:
        return plan.cfg.batch_size * plan.layout.num_workers

    def make_step(self, plan: OverlapPlan
                  ) -> Callable[[DistState], DistState]:
        specialized = _build_chunk_specializer(plan)
        S = len(plan.schedule)

        def digit_seq_at(pos: int):
            K = min(plan.chunk, S - pos)
            return tuple(
                tuple(int(d) for d in plan.digits[pos + k])
                for k in range(K)
            )

        if plan.store is not None:
            # out-of-core: the prefetcher walks K-stratum GROUPS (the
            # unit this strategy consumes), assembling each (M, K, L, ·)
            # block + issuing it to device ahead of the fused step —
            # host→device double buffering layered on top of the
            # rotation double buffering inside the compiled chunk
            fetch = _make_chunk_prefetcher(plan)

            def step(dstate: DistState) -> DistState:
                pos = int(dstate.step) % S
                idx_c, val_c, msk_c = fetch.take(pos)
                return specialized(digit_seq_at(pos))(
                    dstate, idx_c, val_c, msk_c)

            step.prefetcher = fetch
            return step

        chunk_for = _chunk_data_cache(plan)

        def step(dstate: DistState) -> DistState:
            pos = int(dstate.step) % len(plan.schedule)
            digit_seq, idx_c, val_c, msk_c = chunk_for(pos)
            return specialized(digit_seq)(dstate, idx_c, val_c, msk_c)

        return step

    def lower_step(self, plan: OverlapPlan, dstate: DistState):
        specialized = _build_chunk_specializer(plan)
        if plan.store is not None:
            K = min(plan.chunk, len(plan.schedule))
            digit_seq = tuple(
                tuple(int(d) for d in plan.digits[k]) for k in range(K))
            idx_c, val_c, msk_c = plan.store.strata_block(
                plan.schedule[:K])
        else:
            digit_seq, idx_c, val_c, msk_c = _chunk_data_cache(plan)(0)
        return specialized(digit_seq).lower(dstate, idx_c, val_c, msk_c)


def _make_chunk_prefetcher(plan: OverlapPlan):
    """Prefetcher over K-stratum schedule groups (device-major blocks)."""
    from repro.data.pipeline import StratumPrefetcher
    from repro.distributed.strata import _block_sharding

    store, S = plan.store, len(plan.schedule)
    sharding = _block_sharding(plan)

    def load(pos: int):
        K = min(plan.chunk, S - pos)
        return store.strata_block(plan.schedule[pos: pos + K])

    def next_pos(pos: int) -> int:
        return (pos + min(plan.chunk, S - pos)) % S

    return StratumPrefetcher(
        load, next_pos, depth=plan.prefetch_depth,
        place_fn=lambda blocks: jax.device_put(blocks, sharding),
    )


def _chunk_data_cache(plan: OverlapPlan):
    """Schedule position → (static digit sequence, device-major buckets).

    Bucket blocks are rearranged (K, M, L, ·) → (M, K, L, ·) so the mesh
    axis shards the leading dim. Memoized per position (≤ ⌈S/K⌉ entries on
    the aligned path; restores from a foreign step counter just start a
    shorter chunk at the next boundary).
    """
    b = plan.layout.buckets
    S = len(plan.schedule)

    @functools.lru_cache(maxsize=None)
    def chunk_for(pos: int):
        K = min(plan.chunk, S - pos)
        ids = np.asarray(plan.schedule[pos: pos + K])
        digit_seq = tuple(
            tuple(int(d) for d in plan.digits[pos + k])
            for k in range(K)
        )
        idx_c = jnp.swapaxes(b["indices"][ids], 0, 1)  # (M, K, L, N)
        val_c = jnp.swapaxes(b["values"][ids], 0, 1)   # (M, K, L)
        msk_c = jnp.swapaxes(b["mask"][ids], 0, 1)     # (M, K, L)
        return digit_seq, idx_c, val_c, msk_c

    return chunk_for
