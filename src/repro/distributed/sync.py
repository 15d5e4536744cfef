"""``sync`` strategy — synchronous data-parallel minibatch STD.

TPU-native adaptation of the paper's multi-GPU scheme: every device samples
from its local shard of Ω, computes dense factor/core gradients, ``psum``
over the data axis, identical update everywhere. Exact, stateless, composes
with int8 error-feedback gradient compression (the EF residuals live
per-device, stacked on a leading device axis and sharded over the mesh).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.fasttucker import (
    FastTuckerConfig, FastTuckerParams, TrainState, _sgd_update,
    batch_layout, dynamic_lr, scatter_row_grads, step_gradients,
)
from repro.core.sampling import sample_batch_arrays
from repro.core.sptensor import SparseTensor

from .base import DistState, DistStrategy, compressed_reduce, step_donation


def shard_nonzeros(tensor: SparseTensor, num_shards: int):
    """Pad + split Ω round-robin into (num_shards, L, ·) arrays.

    Padding TILES Ω (index arithmetic mod nnz), so ``nnz < num_shards``
    — where the old ``indices[:pad]`` slice came up short and broke the
    reshape — pads correctly by wrapping around.
    """
    nnz = tensor.nnz
    L = -(-nnz // num_shards)
    sel = jnp.arange(L * num_shards) % nnz
    return (tensor.indices[sel].reshape(num_shards, L, -1),
            tensor.values[sel].reshape(num_shards, L))


def init_error_feedback(params: FastTuckerParams):
    """Zero EF residuals, factor-shaped (legacy replicated layout)."""
    return tuple(jnp.zeros_like(f) for f in params.factors)


def _sync_local_update(cfg: FastTuckerConfig, axis: str, compress: bool,
                       params, step_no, key, idx_shard, val_shard, ef):
    """Per-device body shared by the legacy step and the strategy step.

    ``ef`` is a tuple of per-device factor-shaped residuals (already
    unstacked); returns (new_params, new_ef).
    """
    me = jax.lax.axis_index(axis)
    key = jax.random.fold_in(key, me)
    idx, val = sample_batch_arrays(key, idx_shard, val_shard, cfg.batch_size)
    layout = batch_layout(idx, cfg)  # per-device mode-sorted view
    grads = step_gradients(params, idx, val, cfg, layout=layout)
    dense = scatter_row_grads(params.factors, idx, grads.row_grads,
                              backend=cfg.backend, layout=layout)
    if compress:
        dense, ef = compressed_reduce(dense, ef, axis)
    else:
        dense = jax.lax.psum(dense, axis)
    core = jax.lax.psum(grads.core_grads, axis)
    nshards = jax.lax.psum(1, axis)
    lr_a = dynamic_lr(cfg.alpha_a, cfg.beta_a, step_no)
    lr_b = dynamic_lr(cfg.alpha_b, cfg.beta_b, step_no)
    factors = tuple(
        _sgd_update(f, lr_a / nshards, g)
        for f, g in zip(params.factors, dense))
    core_f = tuple(
        _sgd_update(b, lr_b / nshards, g)
        for b, g in zip(params.core_factors, core))
    return FastTuckerParams(factors, core_f), ef


def make_sync_step(cfg: FastTuckerConfig, mesh: Mesh, axis: str = "data",
                   compress: bool = False):
    """Legacy entry point: jit'd step(params, step_no, key, idx, val, ef).

    Kept for existing call sites; new code should drive ``SyncStrategy``
    through the registry (its EF residuals are properly device-sharded
    instead of replicated-with-divergence).
    """
    from jax import shard_map

    def local_step(params, step_no, key, idx_shard, val_shard, ef):
        # shard_map blocks keep a size-1 leading dim — drop it
        new_params, new_ef = _sync_local_update(
            cfg, axis, compress,
            params, step_no, key, idx_shard[0], val_shard[0], ef)
        return new_params, new_ef

    sharded = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(axis), P(axis), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(sharded)


# ---------------------------------------------------------------------------
# strategy
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SyncPlan:
    cfg: FastTuckerConfig
    mesh: Mesh
    idx_shards: jax.Array   # (M, L, N)
    val_shards: jax.Array   # (M, L)
    compress: bool
    axis: str = "data"

    @property
    def num_devices(self) -> int:
        return self.idx_shards.shape[0]


def _build_jitted(plan: SyncPlan):
    from jax import shard_map

    cfg, axis = plan.cfg, plan.axis

    def local_step(dstate: DistState, idx_shard, val_shard) -> DistState:
        step_key = jax.random.fold_in(dstate.key, dstate.step)
        # EF residuals arrive stacked (1, I_n, J_n) per device
        ef = tuple(e[0] for e in dstate.ef)
        new_params, new_ef = _sync_local_update(
            cfg, axis, plan.compress, dstate.params, dstate.step, step_key,
            idx_shard[0], val_shard[0], ef)
        new_ef = tuple(e[None] for e in new_ef)
        return DistState(new_params, dstate.step + 1, dstate.key, new_ef)

    ef_spec = tuple(P(axis) for _ in range(len(plan.cfg.dims))) \
        if plan.compress else ()
    state_spec = DistState(
        params=FastTuckerParams(
            tuple(P() for _ in plan.cfg.dims),
            tuple(P() for _ in plan.cfg.dims),
        ),
        step=P(), key=P(), ef=ef_spec,
    )
    sharded = shard_map(
        local_step,
        mesh=plan.mesh,
        in_specs=(state_spec, P(plan.axis), P(plan.axis)),
        out_specs=state_spec,
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=step_donation())


class SyncStrategy(DistStrategy):
    name = "sync"

    def prepare(self, tensor: SparseTensor, cfg: FastTuckerConfig, mesh,
                *, compress: bool = False, seed: int = 0) -> SyncPlan:
        idx_sh, val_sh = shard_nonzeros(tensor, mesh.devices.size)
        return SyncPlan(cfg, mesh, idx_sh, val_sh, compress)

    def init(self, plan: SyncPlan, state: TrainState,
             key: jax.Array) -> DistState:
        M = plan.num_devices
        acc = jnp.dtype(plan.cfg.accum_dtype)  # EF lives in grad dtype
        ef = (tuple(
            jnp.zeros((M,) + f.shape, acc) for f in state.params.factors)
            if plan.compress else ())
        return DistState(state.params, jnp.asarray(state.step, jnp.int32),
                         key, ef)

    def nnz_per_step(self, plan: SyncPlan) -> int:
        # every device samples its own |Ψ| from its Ω shard
        return plan.cfg.batch_size * plan.num_devices

    def make_step(self, plan: SyncPlan
                  ) -> Callable[[DistState], DistState]:
        jitted = _build_jitted(plan)
        return lambda dstate: jitted(dstate, plan.idx_shards,
                                     plan.val_shards)

    def lower_step(self, plan: SyncPlan, dstate: DistState):
        return _build_jitted(plan).lower(dstate, plan.idx_shards,
                                         plan.val_shards)
