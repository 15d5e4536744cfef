"""``strata`` strategy — the faithful cuFastTucker Fig. 2 analogue.

Factor matrices are ROW-SHARDED over M devices; each step handles one
stratum s (a generalized diagonal of the M^N block grid): ``ppermute``
rotates each mode's factor shards by the stratum digit so that every device
holds exactly the rows its bucket touches, updates locally (conflict-free
by construction), and rotates back. Communication per step = 2·N shard
rotations (point-to-point), independent of M — the property behind the
paper's near-linear M-GPU scaling. Core factors B^(n) are small →
replicated, gradient psum'd (optionally int8 error-feedback compressed:
that psum is the only gradient collective this strategy has).

Strata are visited in a pre-sampled Latin-hypercube epoch schedule
(``core.sampling.latin_hypercube_schedule``): every stratum — hence every
block — exactly once per epoch, replacing the old i.i.d. host draws which
left ~1/e of the blocks unvisited per S draws. The schedule is fixed per
run (seeded), so each stratum's rotations compile to STATIC ppermutes; at
most S specialized step variants exist and the jit cache holds them.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.fasttucker import (
    FastTuckerConfig, FastTuckerParams, TrainState, _sgd_update,
    batch_layout, dynamic_lr, scatter_row_grads, step_gradients,
)
from repro.core.sptensor import SparseTensor, partition_for_workers

from .base import DistState, DistStrategy, compressed_reduce, step_donation


# ---------------------------------------------------------------------------
# layout: buckets + padded row blocks (was ``StrataPlan`` pre-registry)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StrataLayout:
    """Host-side prep for the stratified schedule.

    Backed either by resident device buckets (``buckets``, from
    ``partition_for_workers``) or by an out-of-core ``NonzeroStore``
    (``store``) whose chunks have the identical (S, M, L, ·) layout —
    the per-stratum math never sees the difference.
    """
    buckets: dict | None   # from partition_for_workers (resident path)
    rows_per_block: tuple  # per mode (padded row count / M)
    num_workers: int
    store: "NonzeroStore | None" = None

    @classmethod
    def build(cls, tensor: SparseTensor, num_workers: int):
        M = num_workers
        padded_dims = tuple(-(-d // M) * M for d in tensor.dims)
        padded = SparseTensor(tensor.indices, tensor.values, padded_dims)
        buckets = partition_for_workers(padded, M)
        return cls(buckets, tuple(d // M for d in padded_dims), M)

    @classmethod
    def from_store(cls, store: "NonzeroStore"):
        """Out-of-core layout: chunks stay host-side in the store."""
        M = store.num_workers
        return cls(None, tuple(d // M for d in store.padded_dims), M,
                   store=store)

    @property
    def num_strata(self) -> int:
        if self.store is not None:
            return self.store.num_strata
        return self.buckets["indices"].shape[0]

    @property
    def order(self) -> int:
        if self.store is not None:
            return self.store.order
        return self.buckets["indices"].shape[-1]

    def stratum_digits(self, s: int) -> np.ndarray:
        """Base-M digits (mode 1..N-1 shifts) of stratum s."""
        from repro.core.sampling import stratum_digits

        return np.asarray(
            stratum_digits(jnp.asarray([s]), self.num_workers,
                           self.order))[0]


def pad_factors_for_strata(params: FastTuckerParams, plan: StrataLayout
                           ) -> FastTuckerParams:
    M = plan.num_workers
    factors = tuple(
        jnp.pad(f, ((0, plan.rows_per_block[n] * M - f.shape[0]), (0, 0)))
        for n, f in enumerate(params.factors)
    )
    return FastTuckerParams(factors, params.core_factors)


# ---------------------------------------------------------------------------
# per-stratum body (shared with ``strata_overlap``)
# ---------------------------------------------------------------------------

def rotate_shard(f: jax.Array, shift: int, M: int, axis: str) -> jax.Array:
    """Rotate row shards so each device ends up holding the block owned by
    (me + shift): send mine to (me − shift). Shifts COMPOSE additively, so
    moving from stratum digits d to d' is a rotation by (d' − d) mod M and
    returning home is a rotation by (−d) mod M."""
    if shift % M == 0:
        return f
    perm = [(i, (i - shift) % M) for i in range(M)]
    return jax.lax.ppermute(f, axis, perm)


def stratum_row_update(cfg: FastTuckerConfig, layout: StrataLayout,
                       axis: str, digits: tuple, rot, core_f,
                       idx_b, val_b, msk_b, step_no, key):
    """One stratum's conflict-free local row update, shards pre-rotated.

    ``rot`` holds each mode's factor shard rotated into ``digits`` position
    (device me owns rows block (me + digits[n]) of mode n). Samples |Ψ|
    nonzeros from this device's bucket, localizes indices, runs the fused
    gradient kernel, and applies the row update. The core-factor gradient
    psum/update is left to the caller so it can be ordered AFTER the next
    rotation is issued (communication hiding).

    Returns (updated rotated shards, per-device core gradients).
    """
    M = layout.num_workers
    me = jax.lax.axis_index(axis)
    key = jax.random.fold_in(key, me)
    pick = jax.random.randint(key, (cfg.batch_size,), 0, idx_b.shape[0])
    idx = idx_b[pick]
    val = val_b[pick]
    msk = msk_b[pick]

    # localize rows: mode-n block digit here is (me + digits[n]) % M
    local_idx = []
    for n in range(cfg.order):
        digit = (me + digits[n]) % M
        local_idx.append(idx[:, n] - digit * layout.rows_per_block[n])
    lidx = jnp.stack(local_idx, axis=1)

    # mode-sorted view of this device's draw: localization subtracts a
    # per-mode constant, so sorting the LOCAL ids is the same order the
    # global rows have — the layout composes with the rotated block
    # positions unchanged (masked padding entries may localize negative;
    # both scatter paths drop out-of-range rows identically)
    blayout = batch_layout(lidx, cfg)
    lparams = FastTuckerParams(tuple(rot), core_f)
    grads = step_gradients(lparams, lidx, val, cfg, mask=msk,
                           layout=blayout)
    dense = scatter_row_grads(lparams.factors, lidx, grads.row_grads,
                              backend=cfg.backend, layout=blayout)
    lr_a = dynamic_lr(cfg.alpha_a, cfg.beta_a, step_no)
    new_rot = tuple(_sgd_update(f, lr_a, g) for f, g in zip(rot, dense))
    return new_rot, grads.core_grads


def core_update(cfg: FastTuckerConfig, axis: str, M: int, core_f,
                core_grads, ef, step_no, compress: bool):
    """psum'd (optionally int8-EF-compressed) core-factor update."""
    if compress:
        summed, ef = compressed_reduce(core_grads, ef, axis)
    else:
        summed = jax.lax.psum(core_grads, axis)
    lr_b = dynamic_lr(cfg.alpha_b, cfg.beta_b, step_no)
    core_f = tuple(
        _sgd_update(b, lr_b / M, g) for b, g in zip(core_f, summed))
    return core_f, ef


def strata_state_spec(cfg: FastTuckerConfig, axis: str, compress: bool
                      ) -> DistState:
    """shard_map spec: factor rows sharded, core replicated, EF stacked."""
    N = cfg.order
    ef_spec = tuple(P(axis) for _ in range(N)) if compress else ()
    return DistState(
        params=FastTuckerParams(
            tuple(P(axis, None) for _ in range(N)),
            tuple(P() for _ in range(N)),
        ),
        step=P(), key=P(), ef=ef_spec,
    )


# ---------------------------------------------------------------------------
# legacy entry point (pre-registry API, kept for existing call sites)
# ---------------------------------------------------------------------------

def make_strata_step(cfg: FastTuckerConfig, mesh: Mesh, plan: StrataLayout,
                     axis: str = "data"):
    """Step over ONE stratum: rotate shards in, local conflict-free update,
    rotate back. Factor rows sharded over `axis`; B^(n) replicated."""
    M = plan.num_workers
    N = cfg.order

    from jax import shard_map

    # The stratum is host-chosen per step, so specialize the compiled step
    # per digit tuple: rotations become STATIC ppermutes (no lax.switch over
    # collectives, which deadlocks/blows up compile). At most M^(N-1)
    # variants exist; the jit cache holds the ones actually visited.
    @functools.lru_cache(maxsize=None)
    def _specialized(digits: tuple):
        def local_step(params, step_no, key, idx_b, val_b, mask_b):
            idx_b, val_b, mask_b = idx_b[0], val_b[0], mask_b[0]
            rot = [rotate_shard(params.factors[n], digits[n], M, axis)
                   for n in range(N)]
            new_rot, core_grads = stratum_row_update(
                cfg, plan, axis, digits, rot, params.core_factors,
                idx_b, val_b, mask_b, step_no, key)
            back = tuple(
                rotate_shard(new_rot[n], -digits[n], M, axis)
                for n in range(N)
            )
            core_f, _ = core_update(cfg, axis, M, params.core_factors,
                                    core_grads, (), step_no, compress=False)
            return FastTuckerParams(back, core_f)

        sharded = shard_map(
            local_step,
            mesh=mesh,
            in_specs=(
                FastTuckerParams(
                    tuple(P(axis, None) for _ in range(N)),
                    tuple(P() for _ in range(N)),
                ),
                P(), P(),
                P(axis), P(axis), P(axis),
            ),
            out_specs=FastTuckerParams(
                tuple(P(axis, None) for _ in range(N)),
                tuple(P() for _ in range(N)),
            ),
            check_vma=False,
        )
        return jax.jit(sharded)

    def step(params, step_no, key, stratum: int):
        digits = tuple(int(d) for d in plan.stratum_digits(int(stratum)))
        b = plan.buckets
        idx_s = b["indices"][stratum]     # (M, L, N)
        val_s = b["values"][stratum]
        msk_s = b["mask"][stratum]
        return _specialized(digits)(params, step_no, key, idx_s, val_s,
                                    msk_s)

    return step


# ---------------------------------------------------------------------------
# strategy
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StrataRunPlan:
    cfg: FastTuckerConfig
    mesh: Mesh
    layout: StrataLayout
    schedule: np.ndarray   # (S,) stratum ids — LHC epoch cover, fixed per run
    digits: np.ndarray     # (S, N) matching digits
    compress: bool
    axis: str = "data"
    store: "NonzeroStore | None" = None   # out-of-core chunk source
    prefetch_depth: int = 2               # device blocks issued ahead


def _prepare_run_plan(tensor, cfg, mesh, compress, seed, axis="data",
                      store=None, prefetch_depth=2):
    from repro.core.sampling import latin_hypercube_schedule, stratum_digits

    if store is not None:
        if store.num_workers != mesh.devices.size:
            raise ValueError(
                f"store was sharded for {store.num_workers} workers but "
                f"the mesh has {mesh.devices.size} devices — rebuild it "
                f"with NonzeroStore.build(tensor, {mesh.devices.size})")
        layout = StrataLayout.from_store(store)
    else:
        layout = StrataLayout.build(tensor, mesh.devices.size)
    M = layout.num_workers
    schedule = np.asarray(latin_hypercube_schedule(
        jax.random.PRNGKey(seed), M, cfg.order))
    digits = np.asarray(stratum_digits(schedule, M, cfg.order))
    return StrataRunPlan(cfg, mesh, layout, schedule, digits, compress,
                         axis, store, prefetch_depth)


def _block_sharding(plan: StrataRunPlan):
    """Devices-major placement for (M, …) schedule blocks: each device
    receives its own bucket slice during the prefetch, not at step time."""
    from jax.sharding import NamedSharding

    return NamedSharding(plan.mesh, P(plan.axis))


def make_stratum_prefetcher(plan: StrataRunPlan):
    """Prefetcher over the LHC schedule, one stratum per step.

    ``take(pos)`` yields the (idx, val, msk) device blocks for schedule
    position ``pos`` — loaded from the store and ``device_put`` on the
    prefetch thread ``plan.prefetch_depth`` strata ahead of consumption.
    """
    from repro.data.pipeline import StratumPrefetcher

    store, S = plan.store, len(plan.schedule)
    sharding = _block_sharding(plan)
    return StratumPrefetcher(
        lambda pos: store.stratum(int(plan.schedule[pos % S])),
        lambda pos: (pos + 1) % S,
        depth=plan.prefetch_depth,
        place_fn=lambda blocks: jax.device_put(blocks, sharding),
    )


def _init_strata_state(plan, state: TrainState, key) -> DistState:
    params = pad_factors_for_strata(state.params, plan.layout)
    M = plan.layout.num_workers
    acc = jnp.dtype(plan.cfg.accum_dtype)  # EF lives in grad dtype
    ef = (tuple(
        jnp.zeros((M,) + b.shape, acc)
        for b in state.params.core_factors)
        if plan.compress else ())
    return DistState(params, jnp.asarray(state.step, jnp.int32), key, ef)


def _build_strata_specializer(plan: StrataRunPlan):
    from jax import shard_map

    cfg, layout, axis = plan.cfg, plan.layout, plan.axis
    M, N = layout.num_workers, cfg.order
    spec = strata_state_spec(cfg, axis, plan.compress)

    @functools.lru_cache(maxsize=None)
    def specialized(digits: tuple):
        def local_step(dstate: DistState, idx_b, val_b, msk_b) -> DistState:
            idx_b, val_b, msk_b = idx_b[0], val_b[0], msk_b[0]
            skey = jax.random.fold_in(dstate.key, dstate.step)
            rot = [rotate_shard(dstate.params.factors[n], digits[n], M, axis)
                   for n in range(N)]
            new_rot, core_grads = stratum_row_update(
                cfg, layout, axis, digits, rot, dstate.params.core_factors,
                idx_b, val_b, msk_b, dstate.step, skey)
            # issue the home rotation before the core psum/update: the two
            # have no data dependence, so the permutes can overlap it
            back = tuple(
                rotate_shard(new_rot[n], -digits[n], M, axis)
                for n in range(N)
            )
            ef = tuple(e[0] for e in dstate.ef)
            core_f, ef = core_update(
                cfg, axis, M, dstate.params.core_factors, core_grads, ef,
                dstate.step, plan.compress)
            ef = tuple(e[None] for e in ef)
            return DistState(FastTuckerParams(back, core_f),
                             dstate.step + 1, dstate.key, ef)

        sharded = shard_map(
            local_step,
            mesh=plan.mesh,
            in_specs=(spec, P(axis), P(axis), P(axis)),
            out_specs=spec,
            check_vma=False,
        )
        return jax.jit(sharded, donate_argnums=step_donation())

    return specialized


class StrataStrategy(DistStrategy):
    name = "strata"

    def prepare(self, tensor: SparseTensor, cfg: FastTuckerConfig, mesh,
                *, compress: bool = False, seed: int = 0,
                store=None, prefetch_depth: int = 2) -> StrataRunPlan:
        return _prepare_run_plan(tensor, cfg, mesh, compress, seed,
                                 store=store, prefetch_depth=prefetch_depth)

    def init(self, plan: StrataRunPlan, state: TrainState,
             key: jax.Array) -> DistState:
        return _init_strata_state(plan, state, key)

    def nnz_per_step(self, plan: StrataRunPlan) -> int:
        # every device draws |Ψ| nonzeros from its stratum bucket
        return plan.cfg.batch_size * plan.layout.num_workers

    def make_step(self, plan: StrataRunPlan
                  ) -> Callable[[DistState], DistState]:
        specialized = _build_strata_specializer(plan)
        S = len(plan.schedule)

        if plan.store is not None:
            # out-of-core: consume device blocks from the prefetcher —
            # stratum pos+depth is in flight while pos computes. The
            # blocks are bit-identical to the resident bucket slices
            # (the store writer mirrors partition_for_workers), so the
            # trajectory is too.
            fetch = make_stratum_prefetcher(plan)

            def step(dstate: DistState) -> DistState:
                pos = int(dstate.step) % S
                digits = tuple(int(d) for d in plan.digits[pos])
                idx_s, val_s, msk_s = fetch.take(pos)
                return specialized(digits)(dstate, idx_s, val_s, msk_s)

            step.prefetcher = fetch  # tests/benchmarks can close() it
            return step

        b = plan.layout.buckets

        @functools.lru_cache(maxsize=None)
        def bucket_for(s: int):
            # memoize the per-stratum device slices: the same S strata
            # repeat every epoch, no need to re-slice on the hot loop
            return b["indices"][s], b["values"][s], b["mask"][s]

        def step(dstate: DistState) -> DistState:
            pos = int(dstate.step) % S
            digits = tuple(int(d) for d in plan.digits[pos])
            idx_s, val_s, msk_s = bucket_for(int(plan.schedule[pos]))
            return specialized(digits)(dstate, idx_s, val_s, msk_s)

        return step

    def eval_params(self, plan: StrataRunPlan,
                    dstate: DistState) -> FastTuckerParams:
        return FastTuckerParams(
            tuple(f[: plan.cfg.dims[n]]
                  for n, f in enumerate(dstate.params.factors)),
            dstate.params.core_factors,
        )

    def _lift_eval_params(self, plan: StrataRunPlan, dstate: DistState,
                          state: TrainState) -> DistState:
        # re-pad the refreshed global-layout factors to the device-multiple
        # row counts the strata shard_map steps expect at rest (the next
        # step's in_specs re-place them on the mesh, as init does)
        return DistState(
            pad_factors_for_strata(state.params, plan.layout),
            jnp.asarray(state.step, jnp.int32), dstate.key, dstate.ef)

    def lower_step(self, plan: StrataRunPlan, dstate: DistState):
        specialized = _build_strata_specializer(plan)
        s = int(plan.schedule[0])
        digits = tuple(int(d) for d in plan.digits[0])
        if plan.store is not None:
            idx_s, val_s, msk_s = plan.store.stratum(s)
        else:
            b = plan.layout.buckets
            idx_s, val_s, msk_s = (b["indices"][s], b["values"][s],
                                   b["mask"][s])
        return specialized(digits).lower(dstate, idx_s, val_s, msk_s)
