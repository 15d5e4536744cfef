"""Named distributed-strategy registry — §5.3 data division behind one API.

The same pattern as the kernel-backend registry (``repro.kernels.dispatch``)
one layer up: every multi-device training scheme is a ``DistStrategy``
registered under a name:

    ``"local"``          single-device SGD (reference trajectory)
    ``"sync"``           synchronous data-parallel minibatch (psum'd grads)
    ``"strata"``         the paper's Fig.-2 stratified rotation, one stratum
                         per step over a pre-sampled Latin-hypercube epoch
                         schedule
    ``"strata_overlap"`` same schedule, fused over a chunk of strata with
                         the shard rotations double-buffered so stratum
                         s+1's ``ppermute`` is issued alongside stratum s's
                         remaining compute (communication hiding,
                         cuFasterTucker-style)

Uniform contract (the launcher drives every strategy through this):

    plan    = strategy.prepare(tensor, cfg, mesh, compress=..., seed=...)
    dstate  = strategy.init(plan, train_state, key)
    step_fn = strategy.make_step(plan)
    dstate  = step_fn(dstate)                   # advances steps_per_call
    params  = strategy.eval_params(plan, dstate)  # strata row-trim included
    strategy.save(plan, ckpt, dstate) / strategy.restore(plan, ckpt, dstate)

``DistState`` is one pytree — parameters, step counter, base PRNG key, and
error-feedback residuals — so checkpoint save/restore is identical across
strategies, and int8 error-feedback compression (``--compress``) works
under every strategy, not just ``sync``.

New strategies (hierarchical meshes, async parameter servers, …) register
via ``register_strategy`` without touching any call site.
"""
from __future__ import annotations

import abc
import os
import warnings
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.fasttucker import FastTuckerConfig, FastTuckerParams, TrainState

ENV_VAR = "REPRO_DIST_STRATEGY"
DEFAULT_STRATEGY = "local"


class DistState(NamedTuple):
    """Uniform distributed training state (one checkpointable pytree).

    ``ef`` holds the int8 error-feedback residuals when compression is on
    (strategy-specific shapes: factor-shaped for local/sync, per-device
    core-factor-shaped for the strata flavors) and is ``()`` otherwise.
    """

    params: FastTuckerParams
    step: jax.Array            # int32 global update counter (strata count)
    key: jax.Array             # base PRNG key; per-step keys are fold_in'd
    ef: tuple = ()


class DistStrategy(abc.ABC):
    """Interface every distributed training scheme implements."""

    name: str = "?"
    needs_mesh: bool = True

    # -- lifecycle -----------------------------------------------------------

    @abc.abstractmethod
    def prepare(self, tensor, cfg: FastTuckerConfig, mesh, *,
                compress: bool = False, seed: int = 0) -> Any:
        """Host-side data layout + schedule; returns an opaque plan."""

    @abc.abstractmethod
    def init(self, plan, state: TrainState, key: jax.Array) -> DistState:
        """Lift a fresh single-device ``TrainState`` into strategy state."""

    @abc.abstractmethod
    def make_step(self, plan) -> Callable[[DistState], DistState]:
        """Build the update function (advances ``steps_per_call`` steps)."""

    def steps_per_call(self, plan) -> int:
        return 1

    def nnz_per_step(self, plan) -> int:
        """Nonzeros consumed per update step (throughput accounting).

        Default: one |Ψ| draw.  Strategies whose devices each draw their
        own |Ψ| (sync, the strata flavors) override with M·|Ψ|.
        """
        return plan.cfg.batch_size

    # -- evaluation ----------------------------------------------------------

    def eval_params(self, plan, dstate: DistState) -> FastTuckerParams:
        """Parameters in the global (unpadded, unrotated) layout.

        The strata flavors override this to trim padded factor rows — the
        trimming previously inlined at every eval site in ``std_train``.
        """
        return dstate.params

    # -- online refresh ------------------------------------------------------

    def _lift_eval_params(self, plan, dstate: DistState,
                          state: TrainState) -> DistState:
        """Lift refreshed global-layout params back into strategy state.

        The inverse of ``eval_params``'s view: the base (local/sync)
        layout IS the global layout, so only the step counter moves; the
        strata flavors override this to re-pad factor rows to the device
        multiple.  ``key``/``ef`` carry over unchanged — the refresh is
        factor-phase only, so core-factor EF residuals stay meaningful.
        """
        return DistState(state.params, jnp.asarray(state.step, jnp.int32),
                         dstate.key, dstate.ef)

    def refresh_steps(self, plan, dstate: DistState, indices, values,
                      num_steps: int) -> tuple[DistState, tuple]:
        """K bounded factor-phase SGD steps over a recent-nonzero window.

        The strategy-uniform face of ``core.fasttucker.refresh_steps``:
        evaluate to the global layout, catch the factors up on the window
        (core frozen — the step cost stays O(batch) and the dirty set
        stays row-bounded), and lift the result back into this strategy's
        at-rest layout.  Per-step keys fold the current step count into
        ``dstate.key``, so successive refresh windows draw fresh samples
        and a full-epoch retrain is never implied.

        Returns ``(dstate', dirty)`` — ``dirty[n]`` the sorted int32 row
        ids of mode ``n`` touched by the window, sized for
        ``TuckerServer.update_rows(n, dirty[n], factors[n][dirty[n]])``.
        """
        from repro.core.fasttucker import refresh_steps as _core_refresh

        params = self.eval_params(plan, dstate)
        state = TrainState(params, jnp.asarray(dstate.step, jnp.int32))
        key = jax.random.fold_in(dstate.key, int(dstate.step))
        state, dirty = _core_refresh(state, key, indices, values,
                                     plan.cfg, num_steps)
        return self._lift_eval_params(plan, dstate, state), dirty

    # -- introspection (benchmarks / tests) ----------------------------------

    def lower_step(self, plan, dstate: DistState):
        """``jax.stages.Lowered`` for one representative compiled step.

        Benchmarks analyze its HLO for per-step collective bytes and
        communication/compute overlap evidence.
        """
        raise NotImplementedError(f"{self.name} has no lowerable step")

    # -- checkpointing (uniform across strategies) ---------------------------

    def save(self, plan, ckpt, dstate: DistState,
             blocking: bool = True) -> None:
        ckpt.save(int(dstate.step), dstate, blocking=blocking)

    def restore(self, plan, ckpt, like: DistState,
                step: int | None = None) -> DistState:
        restored, _ = ckpt.restore(like, step)
        return DistState(
            params=restored.params,
            step=jnp.asarray(restored.step, jnp.int32),
            key=restored.key,
            ef=restored.ef,
        )


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, DistStrategy] = {}


def register_strategy(strategy: DistStrategy, *,
                      overwrite: bool = False) -> None:
    if strategy.name in _REGISTRY and not overwrite:
        raise ValueError(f"strategy {strategy.name!r} already registered")
    _REGISTRY[strategy.name] = strategy


def available_strategies() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def resolve_strategy_name(name: str | None = None,
                          mode: str | None = None) -> str:
    """explicit ``name`` > deprecated ``mode`` > $REPRO_DIST_STRATEGY > local.

    ``mode`` is the pre-registry ``--mode`` flag; passing it warns.
    """
    if name:
        return name
    if mode:
        warnings.warn(
            "--mode is deprecated; use --strategy "
            f"{'/'.join(available_strategies())}",
            DeprecationWarning, stacklevel=2,
        )
        return mode
    return os.environ.get(ENV_VAR) or DEFAULT_STRATEGY


def get_strategy(name: str | None = None,
                 mode: str | None = None) -> DistStrategy:
    resolved = resolve_strategy_name(name, mode)
    try:
        return _REGISTRY[resolved]
    except KeyError:
        raise KeyError(
            f"unknown distributed strategy {resolved!r}; "
            f"available: {available_strategies()}"
        ) from None


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

DONATE_ENV_VAR = "REPRO_DONATE_STEP"


def step_donation() -> tuple[int, ...]:
    """``donate_argnums`` for the per-step jits (the DistState argument).

    Every strategy's compiled step is state → state with matching
    shapes/shardings, so donating the input state lets XLA reuse (alias)
    the parameter and EF buffers instead of allocating a fresh copy per
    step.  ``$REPRO_DONATE_STEP`` = ``on`` / ``off`` forces it; the
    default (``auto``) donates only off-CPU — CPU XLA cannot donate and
    would warn on every call.  Callers must rebind (``dstate =
    step(dstate)``), which the launcher and strategies already do.
    """
    mode = os.environ.get(DONATE_ENV_VAR, "auto").lower()
    if mode == "on":
        return (0,)
    if mode == "off":
        return ()
    return (0,) if jax.default_backend() != "cpu" else ()


def compressed_reduce(dense, ef, axis: str | None):
    """int8 error-feedback quantize → (psum over ``axis``) → dequantize.

    ``dense``/``ef`` are matching tuples of arrays. With ``axis=None`` the
    reduction is skipped (single-device: the quantization round-trip and
    residual carry still apply, so ``local --compress`` is the numerics
    reference for the distributed compressed paths).
    """
    from repro.optim.compression import compress_ef, decompress

    out, new_ef = [], []
    for g, e in zip(dense, ef):
        q, scale, ne = compress_ef(g, e)
        deq = decompress(q, scale)
        if axis is not None:
            deq = jax.lax.psum(deq, axis)
        out.append(deq)
        new_ef.append(ne)
    return tuple(out), tuple(new_ef)


__all__ = [
    "ENV_VAR",
    "DEFAULT_STRATEGY",
    "DONATE_ENV_VAR",
    "DistState",
    "DistStrategy",
    "register_strategy",
    "available_strategies",
    "resolve_strategy_name",
    "get_strategy",
    "compressed_reduce",
    "step_donation",
]
