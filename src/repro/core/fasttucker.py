"""FastTucker: Kruskal-core sparse Tucker decomposition with SGD (the paper).

Model state:
    factors      : tuple of A^(n) ∈ R^{I_n × J_n}      (feature matrices)
    core_factors : tuple of B^(n) ∈ R^{J_n × R_core}   (Kruskal core, Eq. 9)

Per sampled nonzero (i_1..i_N, x):
    c_r^(n)  = ⟨a_{i_n}, b_{:,r}^(n)⟩                       (Theorem 1)
    x̂        = Σ_r Π_n c_r^(n)
    err      = x̂ − x
    ∂/∂a_{i_n} = err · (Pexc^(n) B^(n)ᵀ) + λ_a a_{i_n}       (Eq. 13 factored)
    ∂/∂B^(n)   = a_{i_n}ᵀ (err ⊙ Pexc^(n)) + λ_b B^(n)       (Eq. 17 factored)
with Pexc^(n)[r] = Π_{k≠n} c_r^(k) (division-free exclusive products).

The factored forms reduce the paper's exponential ``O(Π J_k)`` coefficient
construction to linear ``O(R Σ J_k)`` — Theorems 1 & 2.

Kernel selection goes through the named-backend registry
(``repro.kernels.dispatch``): ``FastTuckerConfig(backend="xla")`` is the
pure-jnp reference path, ``"pallas"`` / ``"pallas_interpret"`` route the
ENTIRE hot path — contraction, Eq.13/17 gradients, and the factor-row
scatter — through the fused Pallas kernels, identical numerics.

Phase-split step (cuFasterTucker's invariant-intermediate caching): the
update decomposes into a *factor phase* (Eq. 13, B^(n) frozen) and a
*core phase* (Eq. 17, gathered rows frozen).  Both need the same mode
products ``c^(n) = a_rows^(n) B^(n)`` — the ``StepIntermediates`` cache
computes them once in the factor phase and hands them to the core phase
instead of re-running all N mode dots.  ``FastTuckerConfig(
phase_split=True)`` routes ``sgd_step`` (and every distributed strategy,
via ``step_gradients``) through the cached two-phase path; results are
bitwise identical to the joint step in f32 — only the op schedule
changes.  ``factor_phase_step`` / ``core_phase_step`` expose the phases
as separately compiled programs (the paper's two-kernel structure);
there the cache is a real ≥25 % dot-FLOP saving per step, because XLA
cannot CSE across program boundaries (and a ``pallas_call`` body is
opaque to CSE/DCE even within one program — on the Pallas backends the
gauss_seidel phase-split drops from 3N(N+1) to 4N in-kernel dots).

Mixed precision: ``FastTuckerConfig(dtype="bfloat16",
accum_dtype="float32")`` stores factors/core factors in bf16 while every
MXU dot, the residual, and the revisited core-gradient accumulator stay
in f32 (``preferred_element_type`` end to end); parameter updates are
applied in f32 and rounded back to the storage dtype.  The f32 default
is bit-for-bit the original trajectory.

Mode-sorted batches: ``FastTuckerConfig(sorted_batches=True)`` lays every
sampled batch out in the order the kernels consume it
(``core.sampling.sorted_batch_layout``) — cuFasterTucker's pre-sorted
per-mode slices / P-Tucker's CSF row grouping.  Each unique factor row is
gathered ONCE per mode and expanded through the inverse index, and the
row-gradient scatter goes through the ``segment_reduce`` registry op (a
sorted ``segment_sum`` on "xla", the O(B) segmented walk kernel on the
Pallas backends) instead of the unsorted ``scatter_accum`` fallback.
On "xla" the sorted path is bitwise-identical to the unsorted one in f32
(stable sort ⇒ per-row duplicate order preserved); on the Pallas backends
it is bitwise-identical to the jnp *reference* scatter — stronger than
the one-hot ``scatter_accum``, whose in-tile dot tree-reduction is only
tolerance-equal to that same reference.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import dispatch
from .sampling import (
    SortedBatchLayout, sample_batch_arrays, sorted_batch_layout,
)
from .sptensor import SparseTensor


class FastTuckerParams(NamedTuple):
    factors: tuple[jax.Array, ...]       # A^(n): (I_n, J_n)
    core_factors: tuple[jax.Array, ...]  # B^(n): (J_n, R_core)


@dataclasses.dataclass(frozen=True)
class FastTuckerConfig:
    dims: tuple[int, ...]
    ranks: tuple[int, ...]          # J_n per mode
    core_rank: int                  # R_core
    lambda_a: float = 0.01
    lambda_b: float = 0.01
    alpha_a: float = 0.006          # initial lr, factors (paper Table 7)
    beta_a: float = 0.05
    alpha_b: float = 0.0045         # initial lr, core factors
    beta_b: float = 0.1
    batch_size: int = 4096          # |Ψ|
    init_scale: float | None = None
    update_order: str = "jacobi"    # "jacobi" | "gauss_seidel"
    backend: str = "xla"            # kernel backend (repro.kernels.dispatch)
    phase_split: bool = False       # cached two-phase step (StepIntermediates)
    sorted_batches: bool = False    # mode-sorted layout: dedup gather +
                                    # segment_reduce scatter (f32-bitwise
                                    # on "xla"; reference-bitwise on Pallas)
    dtype: str = "float32"          # parameter STORAGE dtype (+"bfloat16")
    accum_dtype: str = "float32"    # MXU dot / gradient accumulation dtype
    init: str = "random"            # "random" | "sketched" (core.sketch
                                    # randomized warm start; needs nonzeros)
    sketch_passes: int = 2          # sample passes feeding the range finder
    sketch_oversample: int = 4      # sketch width = max(ranks) + oversample
    sketch_batch: int = 0           # samples per pass (0 → batch_size)
    sketch_core_sweeps: int = 2     # Gauss-Seidel LS sweeps for B^(n)
    sketch_refine_passes: int = 4   # alternating ALS/core-LS polish passes
    sketch_refine_batch: int = 0    # factor-solve sample cap (0 → all nnz)
    warm_step_offset: int = 0       # start the decaying LR schedule here
                                    # (warm init replaces the cold ramp-in;
                                    # raise if SGD diverges from a warm start)

    def __post_init__(self) -> None:
        if self.dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"dtype must be 'float32' or 'bfloat16', got {self.dtype!r}")
        if self.accum_dtype != "float32":
            raise ValueError(
                "accum_dtype must be 'float32' (bf16 storage still "
                f"accumulates in f32), got {self.accum_dtype!r}")
        if self.init not in ("random", "sketched"):
            raise ValueError(
                f"init must be 'random' or 'sketched', got {self.init!r}")

    @property
    def order(self) -> int:
        return len(self.dims)

    @property
    def param_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def sketch_batch_size(self) -> int:
        return self.sketch_batch or self.batch_size


def init_scale(cfg: FastTuckerConfig) -> float:
    """The cold-init uniform half-range s (see ``init_params``)."""
    if cfg.init_scale is not None:
        return cfg.init_scale
    meanJ = sum(cfg.ranks) / cfg.order
    return float(
        (1.0 / cfg.core_rank) ** (0.5 / cfg.order) / jnp.sqrt(meanJ))


def init_params(
    key: jax.Array,
    cfg: FastTuckerConfig,
    indices: jax.Array | None = None,
    values: jax.Array | None = None,
) -> FastTuckerParams:
    """Initialize so that E[x̂] has unit-ish scale.

    x̂ sums R terms, each a product of N dot products of J-vectors; with
    entries ~ U(0, s) the magnitude is ≈ R (s²J)^N, so pick
    s = (1/(R)^{1/N} / J)^{1/2} scaled — matching SGD_Tucker-style init.

    With ``cfg.init == "sketched"`` the randomized warm start
    (``core.sketch``) runs instead: it needs the training nonzeros, so
    ``indices``/``values`` become required.  The random path ignores them
    and is bit-for-bit the original initialization.
    """
    if cfg.init == "sketched":
        if indices is None or values is None:
            raise ValueError(
                "init='sketched' needs the training nonzeros: pass "
                "indices/values to init_params/init_state")
        from .sketch import sketched_init_params

        return sketched_init_params(key, cfg, indices, values)
    N = cfg.order
    keys = jax.random.split(key, 2 * N)
    scale = init_scale(cfg)
    # draw in f32 regardless of storage dtype (same random stream), then
    # round down — bf16 params are the rounded f32 initialization
    factors = tuple(
        jax.random.uniform(keys[n], (cfg.dims[n], cfg.ranks[n]), minval=0.0,
                           maxval=2 * scale).astype(cfg.param_dtype)
        for n in range(N)
    )
    core_factors = tuple(
        jax.random.uniform(keys[N + n], (cfg.ranks[n], cfg.core_rank),
                           minval=0.0, maxval=2 * scale
                           ).astype(cfg.param_dtype)
        for n in range(N)
    )
    return FastTuckerParams(factors, core_factors)


def dynamic_lr(alpha: float, beta: float, t: jax.Array) -> jax.Array:
    """NOMAD-style decaying rate γ_t = α / (1 + β·t^1.5)   [paper §6.1]."""
    return alpha / (1.0 + beta * jnp.power(t.astype(jnp.float32), 1.5))


# ---------------------------------------------------------------------------
# Forward / gradients (batched over the sampling set Ψ)
# ---------------------------------------------------------------------------

def _gather_mode(
    f: jax.Array,
    idx: jax.Array,
    n: int,
    layout: SortedBatchLayout | None,
) -> jax.Array:
    """Mode n's factor rows, (B, J_n) — plain or dedup form."""
    if layout is None:
        return f[idx[:, n]]
    return f[layout.uniq[n]][layout.inv[n]]


def gather_rows(
    factors: Sequence[jax.Array],
    idx: jax.Array,
    layout: SortedBatchLayout | None = None,
) -> tuple[jax.Array, ...]:
    """A^(n)[idx[:, n]] for each mode → tuple of (B, J_n).

    With a mode-sorted ``layout`` each UNIQUE row is fetched from the
    (large, HBM-resident) factor table once and expanded to batch order
    through the inverse index — a second gather, but from the small
    (B, J_n) buffer that is already on-chip.  Bitwise-identical either
    way: gathers move bits, they do no arithmetic.
    """
    return tuple(_gather_mode(f, idx, n, layout)
                 for n, f in enumerate(factors))


def _predict_from_rows(
    rows: Sequence[jax.Array],
    core_factors: Sequence[jax.Array],
    backend: str,
) -> jax.Array:
    """Theorem-1 x̂ from already-gathered rows (shared by predict /
    sampled_loss so the rows are gathered exactly once)."""
    if backend == "xla":
        # natively differentiable; skip the custom_vjp on the reference path
        pred, _ = dispatch.get_backend("xla").kruskal_contract(
            rows, core_factors)
        return pred
    return dispatch.kruskal_predict(backend, tuple(rows), tuple(core_factors))


def predict(
    params: FastTuckerParams, idx: jax.Array, backend: str | None = None
) -> jax.Array:
    """x̂ for a batch of indices (B, N) → (B,).

    Differentiable on every backend: the Pallas flavors go through
    ``dispatch.kruskal_predict`` (a ``jax.custom_vjp`` whose backward pass
    is the fused gradient kernel), so ``jax.grad`` of any loss built on
    this stays kernel-resident.
    """
    backend = dispatch.resolve_backend_name(backend)
    rows = gather_rows(params.factors, idx)
    return _predict_from_rows(rows, params.core_factors, backend)


def sampled_loss(
    params: FastTuckerParams,
    idx: jax.Array,
    val: jax.Array,
    lambda_a: float,
    lambda_b: float,
    row_mean: bool = False,
    backend: str | None = None,
) -> jax.Array:
    """Sampled objective whose exact gradient the hand-derived forms compute.

    ``row_mean=False`` (paper M=1 semantics): 0.5·Σ_b err² + 0.5·λ_a·Σ_b
    Σ_n‖a_rows‖² + B·0.5·λ_b·Σ_n‖B^(n)‖² — i.e. each sample is its own SGD
    update for the rows it touches; collisions sum.
    ``row_mean=True``: everything averaged over the batch (minibatch SGD).
    Verified against ``jax.grad`` in tests.
    """
    backend = dispatch.resolve_backend_name(backend)
    # gather ONCE: the prediction and the row regularizer share these rows
    rows = gather_rows(params.factors, idx)
    pred = _predict_from_rows(rows, params.core_factors, backend)
    err = pred - val
    B = idx.shape[0]
    red = jnp.mean if row_mean else jnp.sum
    data = 0.5 * red(err**2)
    reg_a = 0.5 * lambda_a * sum(red(jnp.sum(r**2, -1)) for r in rows)
    scale_b = 1.0 if row_mean else float(B)
    reg_b = scale_b * 0.5 * lambda_b * sum(
        jnp.sum(b**2) for b in params.core_factors
    )
    return data + reg_a + reg_b


class BatchGrads(NamedTuple):
    row_grads: tuple[jax.Array, ...]   # per-mode (B, J_n) — pre-scatter
    core_grads: tuple[jax.Array, ...]  # per-mode (J_n, R)
    err: jax.Array                     # (B,)
    pred: jax.Array                    # (B,)


class StepIntermediates(NamedTuple):
    """Invariant intermediates shared by the two phases of one step.

    ``B^(n)`` is frozen during the factor phase and the gathered rows are
    frozen during the core phase (jacobi semantics), so the mode products
    ``c^(n)`` — the expensive MXU dots — are identical in both; the
    factor phase emits them once and the core phase consumes them instead
    of re-running all N mode dots (cuFasterTucker's caching).
    """
    rows: tuple[jax.Array, ...]   # per-mode (B, J_n), storage dtype
    c: tuple[jax.Array, ...]      # per-mode (B, R) mode products, accum dtype
    pred: jax.Array               # (B,) accum dtype
    err: jax.Array                # (B,) masked residual, accum dtype


def batch_gradients(
    params: FastTuckerParams,
    idx: jax.Array,
    val: jax.Array,
    lambda_a: float,
    lambda_b: float,
    mask: jax.Array | None = None,
    row_mean: bool = False,
    backend: str | None = None,
    accum_dtype=None,
    layout: SortedBatchLayout | None = None,
) -> BatchGrads:
    """Fused Eq.13 + Eq.17 gradients for the sampled set (the JOINT pass).

    ``mask`` (B,) zeroes contributions of padding entries (distributed path).
    ``row_mean=False`` keeps the paper's per-sample (M=1) row-update
    semantics; the core-factor gradient is always batch-averaged (M=|Ψ|).

    The whole computation dispatches to ``backend`` (see
    ``repro.kernels.dispatch``): on the Pallas flavors the contraction AND
    both gradient stages run inside a single ``pallas_call``
    (``repro.kernels.kruskal_grad``).  See
    ``factor_phase_gradients`` / ``core_phase_gradients`` for the
    phase-split flavor with cached intermediates.
    """
    backend = dispatch.resolve_backend_name(backend)
    with jax.named_scope("repro.step.grad"):
        rows = gather_rows(params.factors, idx, layout)
        kg = dispatch.get_backend(backend).kruskal_grad(
            rows, params.core_factors, val,
            mask=mask, lambda_a=lambda_a, lambda_b=lambda_b,
            row_mean=row_mean, accum_dtype=accum_dtype,
        )
    return BatchGrads(kg.row_grads, kg.core_grads, kg.err, kg.pred)


def factor_phase_gradients(
    params: FastTuckerParams,
    idx: jax.Array,
    val: jax.Array,
    lambda_a: float,
    lambda_b: float,
    mask: jax.Array | None = None,
    row_mean: bool = False,
    backend: str | None = None,
    accum_dtype=None,
    layout: SortedBatchLayout | None = None,
) -> tuple[BatchGrads, StepIntermediates]:
    """Factor phase: Eq.-13 row gradients + the emitted intermediates.

    One fused kernel pass computing the mode products ``c^(n)``, the
    residual, and the row gradients — the Eq.-17 core stage is skipped
    entirely (``want_core=False``).  Returns the gradients (with
    ``core_grads=()``) and the ``StepIntermediates`` the matching
    ``core_phase_gradients`` call consumes.
    """
    backend = dispatch.resolve_backend_name(backend)
    with jax.named_scope("repro.step.grad"):
        rows = gather_rows(params.factors, idx, layout)
        kg = dispatch.get_backend(backend).kruskal_grad(
            rows, params.core_factors, val,
            mask=mask, lambda_a=lambda_a, lambda_b=lambda_b,
            row_mean=row_mean, want_core=False, emit_c=True,
            accum_dtype=accum_dtype,
        )
    inter = StepIntermediates(rows, kg.c, kg.pred, kg.err)
    return BatchGrads(kg.row_grads, (), kg.err, kg.pred), inter


def core_phase_gradients(
    params: FastTuckerParams,
    idx: jax.Array,
    val: jax.Array,
    lambda_a: float,
    lambda_b: float,
    mask: jax.Array | None = None,
    row_mean: bool = False,
    backend: str | None = None,
    accum_dtype=None,
    intermediates: StepIntermediates | None = None,
    layout: SortedBatchLayout | None = None,
) -> BatchGrads:
    """Core phase: Eq.-17 core-factor gradients (``row_grads=()``).

    With ``intermediates`` the cached rows and mode products are consumed
    — no gather and no mode dots, only the N core-gradient dots (this is
    the ≥25 % per-step dot-FLOP saving of the phase-split pipeline).
    Without, the phase is self-contained and recomputes both (the
    uncached baseline the HLO cost test measures against).
    """
    backend = dispatch.resolve_backend_name(backend)
    with jax.named_scope("repro.step.grad"):
        if intermediates is None:
            rows = gather_rows(params.factors, idx, layout)
            c = None
        else:
            rows, c = intermediates.rows, intermediates.c
        kg = dispatch.get_backend(backend).kruskal_grad(
            rows, params.core_factors, val,
            mask=mask, lambda_a=lambda_a, lambda_b=lambda_b,
            row_mean=row_mean, c=c, row_modes=(), want_core=True,
            accum_dtype=accum_dtype,
        )
    return BatchGrads((), kg.core_grads, kg.err, kg.pred)


def batch_layout(
    idx: jax.Array, cfg: "FastTuckerConfig"
) -> SortedBatchLayout | None:
    """The mode-sorted layout of a sampled batch, or ``None`` when the
    config keeps the unsorted fallback.  Computed device-side inside the
    jitted step (one stable int argsort per mode) so every caller —
    ``sgd_step`` and all distributed strategies — threads the layout with
    one line."""
    return sorted_batch_layout(idx) if cfg.sorted_batches else None


def _sample(key, indices, values, cfg: "FastTuckerConfig"):
    """Ψ drawn from ``key`` and its layout (``batch_layout``)."""
    with jax.named_scope("repro.step.sample"):
        idx, val = sample_batch_arrays(key, indices, values, cfg.batch_size)
        return idx, val, batch_layout(idx, cfg)


def step_gradients(
    params: FastTuckerParams,
    idx: jax.Array,
    val: jax.Array,
    cfg: "FastTuckerConfig",
    mask: jax.Array | None = None,
    layout: SortedBatchLayout | None = None,
) -> BatchGrads:
    """Config-routed gradients: joint, or the cached two-phase pipeline.

    The single entry point the distributed strategies call, so
    ``FastTuckerConfig(phase_split=True)`` reaches every strategy without
    per-strategy plumbing.  Bitwise identical either way (f32) — the
    phases consume the same ``StepIntermediates`` the joint kernel
    computes inline.  ``layout`` (from ``batch_layout``) switches the
    gather to the dedup form; pass the same layout to
    ``scatter_row_grads``.
    """
    if not cfg.phase_split:
        return batch_gradients(
            params, idx, val, cfg.lambda_a, cfg.lambda_b, mask=mask,
            backend=cfg.backend, accum_dtype=cfg.accum_dtype, layout=layout,
        )
    fg, inter = factor_phase_gradients(
        params, idx, val, cfg.lambda_a, cfg.lambda_b, mask=mask,
        backend=cfg.backend, accum_dtype=cfg.accum_dtype, layout=layout,
    )
    cg = core_phase_gradients(
        params, idx, val, cfg.lambda_a, cfg.lambda_b, mask=mask,
        backend=cfg.backend, accum_dtype=cfg.accum_dtype,
        intermediates=inter,
    )
    return BatchGrads(fg.row_grads, cg.core_grads, inter.err, inter.pred)


def _scatter_mode(
    bk,
    grads: jax.Array,
    idx: jax.Array,
    n: int,
    num_rows: int,
    layout: SortedBatchLayout | None,
) -> jax.Array:
    """One mode's dense row-gradient scatter, layout-routed.

    Sorted: permute the per-sample grads into mode-n sorted order and
    segment-reduce over the now-contiguous runs; unsorted: the
    ``scatter_accum`` fallback.
    """
    with jax.named_scope("repro.step.scatter"):
        if layout is None:
            return bk.scatter_accum(grads, idx[:, n], num_rows)
        return bk.segment_reduce(grads[layout.perm[n]],
                                 layout.sorted_rows[n], num_rows)


def scatter_row_grads(
    factors: Sequence[jax.Array],
    idx: jax.Array,
    row_grads: Sequence[jax.Array],
    backend: str | None = None,
    layout: SortedBatchLayout | None = None,
) -> tuple[jax.Array, ...]:
    """Σ_b contributions into dense (I_n, J_n) gradients (exact segment sum).

    Unsorted: the MXU one-hot ``scatter_accum`` kernel on the Pallas
    backends, ``jax.ops.segment_sum`` on "xla".  With a mode-sorted
    ``layout``: the ``segment_reduce`` op over the permuted grads —
    bitwise-identical on "xla", reference-bitwise on Pallas.
    """
    bk = dispatch.get_backend(backend)
    outs = []
    for n, f in enumerate(factors):
        outs.append(_scatter_mode(bk, row_grads[n], idx, n, f.shape[0],
                                  layout))
    return tuple(outs)


# ---------------------------------------------------------------------------
# SGD steps
# ---------------------------------------------------------------------------

class TrainState(NamedTuple):
    params: FastTuckerParams
    step: jax.Array  # int32 scalar


def init_state(
    key: jax.Array,
    cfg: FastTuckerConfig,
    indices: jax.Array | None = None,
    values: jax.Array | None = None,
) -> TrainState:
    """Fresh ``TrainState``.  A sketched warm start may begin the
    decaying LR schedule at ``cfg.warm_step_offset`` (the init replaces
    the cold ramp-in, so the schedule resumes where an equivalent cold
    run would be); the random path always starts at step 0."""
    step = cfg.warm_step_offset if cfg.init == "sketched" else 0
    return TrainState(init_params(key, cfg, indices, values),
                      jnp.asarray(step, jnp.int32))


def _sgd_update(p: jax.Array, lr: jax.Array, g: jax.Array) -> jax.Array:
    """p − lr·g applied in the gradient (accum) dtype, stored in p's dtype.

    For f32 params this is exactly the original update (the casts are
    no-ops); for bf16 storage the arithmetic happens in f32 and only the
    final write rounds down.
    """
    with jax.named_scope("repro.step.update"):
        return (p.astype(g.dtype) - lr * g).astype(p.dtype)


def _apply_updates(
    params: FastTuckerParams,
    idx: jax.Array,
    grads: BatchGrads,
    lr_a: jax.Array,
    lr_b: jax.Array,
    update_factors: bool = True,
    update_core: bool = True,
    backend: str | None = None,
    layout: SortedBatchLayout | None = None,
) -> FastTuckerParams:
    factors = params.factors
    core_factors = params.core_factors
    if update_factors:
        dense = scatter_row_grads(factors, idx, grads.row_grads,
                                  backend=backend, layout=layout)
        factors = tuple(
            _sgd_update(f, lr_a, g) for f, g in zip(factors, dense))
    if update_core:
        core_factors = tuple(
            _sgd_update(b, lr_b, g)
            for b, g in zip(core_factors, grads.core_grads)
        )
    return FastTuckerParams(factors, core_factors)


def _gauss_seidel_joint(params, idx, val, lr_a, lr_b, cfg,
                        update_factors, update_core, layout=None):
    """Original GS: one full joint gradient pass per mode (+ one for the
    core).  XLA CSE rescues the recomputed mode products on the "xla"
    backend, but a ``pallas_call`` is opaque — on the Pallas backends
    every pass really re-runs all 3N in-kernel dots."""
    bk = dispatch.get_backend(cfg.backend)
    if update_factors:
        for n in range(cfg.order):
            grads = batch_gradients(
                params, idx, val, cfg.lambda_a, cfg.lambda_b,
                backend=cfg.backend, accum_dtype=cfg.accum_dtype,
                layout=layout,
            )
            g_n = _scatter_mode(bk, grads.row_grads[n], idx, n,
                                params.factors[n].shape[0], layout)
            new_f = list(params.factors)
            new_f[n] = _sgd_update(params.factors[n], lr_a, g_n)
            params = FastTuckerParams(tuple(new_f), params.core_factors)
    if update_core:
        grads = batch_gradients(
            params, idx, val, cfg.lambda_a, cfg.lambda_b,
            backend=cfg.backend, accum_dtype=cfg.accum_dtype, layout=layout,
        )
        params = _apply_updates(
            params, idx, grads, lr_a, lr_b,
            update_factors=False, update_core=True,
            backend=cfg.backend, layout=layout,
        )
    return params


def _gauss_seidel_phase_split(params, idx, val, lr_a, lr_b, cfg,
                              update_factors, update_core, layout=None):
    """GS with invariant-intermediate caching (cuFasterTucker):

    Updating mode n leaves every other mode's product c^(k≠n) — and all
    of B^(n) — untouched, so the cache holds all N mode products and only
    mode n's entry is refreshed (ONE dot) after its row update.  Per
    step: N initial dots + per mode (1 Eq.-13 dot + 1 refresh dot) + N
    Eq.-17 dots = 4N, vs 3N(N+1) in-kernel dots for the joint form on
    the Pallas backends.  Bitwise identical to the joint GS step."""
    bk = dispatch.get_backend(cfg.backend)
    N = cfg.order
    # the scatters and updates inside carry their own scopes
    with jax.named_scope("repro.step.grad"):
        rows = list(gather_rows(params.factors, idx, layout))
        c = [bk.mode_dot(rows[n], params.core_factors[n],
                         accum_dtype=cfg.accum_dtype) for n in range(N)]
        if update_factors:
            for n in range(N):
                kg = bk.kruskal_grad(
                    tuple(rows), params.core_factors, val,
                    lambda_a=cfg.lambda_a, lambda_b=cfg.lambda_b,
                    c=tuple(c), row_modes=(n,), want_core=False,
                    accum_dtype=cfg.accum_dtype,
                )
                g_n = _scatter_mode(bk, kg.row_grads[0], idx, n,
                                    params.factors[n].shape[0], layout)
                new_f = list(params.factors)
                new_f[n] = _sgd_update(params.factors[n], lr_a, g_n)
                params = FastTuckerParams(tuple(new_f), params.core_factors)
                rows[n] = _gather_mode(params.factors[n], idx, n, layout)
                c[n] = bk.mode_dot(rows[n], params.core_factors[n],
                                   accum_dtype=cfg.accum_dtype)
        if update_core:
            kg = bk.kruskal_grad(
                tuple(rows), params.core_factors, val,
                lambda_a=cfg.lambda_a, lambda_b=cfg.lambda_b,
                c=tuple(c), row_modes=(), want_core=True,
                accum_dtype=cfg.accum_dtype,
            )
            core_factors = tuple(
                _sgd_update(b, lr_b, g)
                for b, g in zip(params.core_factors, kg.core_grads))
            params = FastTuckerParams(params.factors, core_factors)
    return params


@partial(jax.jit, static_argnames=("cfg", "update_factors", "update_core"))
def sgd_step(
    state: TrainState,
    key: jax.Array,
    indices: jax.Array,
    values: jax.Array,
    cfg: FastTuckerConfig,
    update_factors: bool = True,
    update_core: bool = True,
) -> TrainState:
    """One stochastic step: draw Ψ, factored gradients, dynamic-LR SGD.

    ``update_core=False`` reproduces the paper's "Factor"-only curves;
    both True is "Factor+Core".  ``cfg.phase_split`` reroutes through the
    ``StepIntermediates``-cached two-phase form — bitwise identical in
    f32, structurally cheaper on the Pallas backends (and under
    gauss_seidel: 4N vs 3N(N+1) in-kernel dots).
    """
    idx, val, layout = _sample(key, indices, values, cfg)
    lr_a = dynamic_lr(cfg.alpha_a, cfg.beta_a, state.step)
    lr_b = dynamic_lr(cfg.alpha_b, cfg.beta_b, state.step)

    if cfg.update_order == "gauss_seidel":
        gs = (_gauss_seidel_phase_split if cfg.phase_split
              else _gauss_seidel_joint)
        params = gs(state.params, idx, val, lr_a, lr_b, cfg,
                    update_factors, update_core, layout=layout)
    elif cfg.phase_split:
        # jacobi, phased: factor phase emits the intermediates, the core
        # phase consumes them (core grads use the PRE-update rows cached
        # in the intermediates — exactly the joint jacobi semantics)
        fg, inter = factor_phase_gradients(
            state.params, idx, val, cfg.lambda_a, cfg.lambda_b,
            backend=cfg.backend, accum_dtype=cfg.accum_dtype, layout=layout,
        )
        params = state.params
        if update_factors:
            params = _apply_updates(
                params, idx, fg, lr_a, lr_b,
                update_factors=True, update_core=False,
                backend=cfg.backend, layout=layout,
            )
        if update_core:
            cg = core_phase_gradients(
                state.params, idx, val, cfg.lambda_a, cfg.lambda_b,
                backend=cfg.backend, accum_dtype=cfg.accum_dtype,
                intermediates=inter,
            )
            params = _apply_updates(
                params, idx, cg, lr_a, lr_b,
                update_factors=False, update_core=True,
                backend=cfg.backend, layout=layout,
            )
    else:  # jacobi: one fused gradient pass, all variables step together
        grads = batch_gradients(
            state.params, idx, val, cfg.lambda_a, cfg.lambda_b,
            backend=cfg.backend, accum_dtype=cfg.accum_dtype, layout=layout,
        )
        params = _apply_updates(
            state.params, idx, grads, lr_a, lr_b,
            update_factors=update_factors, update_core=update_core,
            backend=cfg.backend, layout=layout,
        )
    return TrainState(params, state.step + 1)


# ---------------------------------------------------------------------------
# separately compiled phase programs (the paper's two-kernel structure)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg",))
def factor_phase_step(
    state: TrainState,
    key: jax.Array,
    indices: jax.Array,
    values: jax.Array,
    cfg: FastTuckerConfig,
) -> tuple[TrainState, jax.Array, jax.Array, StepIntermediates]:
    """Phase 1 as its own compiled program: sample Ψ, update the factor
    matrices, emit ``StepIntermediates``.

    Returns ``(state', idx, val, intermediates)`` — hand all three to
    ``core_phase_step`` to finish the step.  The step counter advances in
    the core phase (one "step" = both phases), so ``state'.step`` is
    unchanged here and both phases share the same dynamic LR epoch.
    """
    idx, val, layout = _sample(key, indices, values, cfg)
    lr_a = dynamic_lr(cfg.alpha_a, cfg.beta_a, state.step)
    fg, inter = factor_phase_gradients(
        state.params, idx, val, cfg.lambda_a, cfg.lambda_b,
        backend=cfg.backend, accum_dtype=cfg.accum_dtype, layout=layout,
    )
    params = _apply_updates(
        state.params, idx, fg, lr_a, jnp.asarray(0.0),
        update_factors=True, update_core=False, backend=cfg.backend,
        layout=layout,
    )
    return TrainState(params, state.step), idx, val, inter


@partial(jax.jit, static_argnames=("cfg",))
def core_phase_step(
    state: TrainState,
    idx: jax.Array,
    val: jax.Array,
    cfg: FastTuckerConfig,
    intermediates: StepIntermediates | None = None,
) -> TrainState:
    """Phase 2 as its own compiled program: update the core factors.

    With ``intermediates`` (from ``factor_phase_step``) the cached rows
    and mode products are consumed — the compiled program contains N
    fewer mode-product dots and no gather than the uncached form, a
    ≥25 % dot-FLOP reduction over the two-program step (XLA cannot CSE
    across program boundaries; ``launch.hlo_analysis`` verifies this in
    tests).  Without, the phase recomputes them from ``state.params`` —
    note the params must then still be PRE-factor-update to preserve
    joint jacobi semantics, so the uncached form is only exact when run
    before (or instead of) the factor phase, or as the deliberate
    recompute baseline.
    """
    lr_b = dynamic_lr(cfg.alpha_b, cfg.beta_b, state.step)
    layout = batch_layout(idx, cfg) if intermediates is None else None
    cg = core_phase_gradients(
        state.params, idx, val, cfg.lambda_a, cfg.lambda_b,
        backend=cfg.backend, accum_dtype=cfg.accum_dtype,
        intermediates=intermediates, layout=layout,
    )
    params = _apply_updates(
        state.params, idx, cg, jnp.asarray(0.0), lr_b,
        update_factors=False, update_core=True, backend=cfg.backend,
    )
    return TrainState(params, state.step + 1)


# ---------------------------------------------------------------------------
# online refresh (bounded factor-phase catch-up over recent nonzeros)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg",))
def _refresh_step(
    state: TrainState,
    key: jax.Array,
    indices: jax.Array,
    values: jax.Array,
    cfg: FastTuckerConfig,
    masks: tuple,
) -> tuple[TrainState, tuple]:
    """One factor-phase step + dirty-row mask accumulation (one compile,
    reused across the K refresh steps — the window arrays keep one shape)."""
    idx, val, layout = _sample(key, indices, values, cfg)
    lr_a = dynamic_lr(cfg.alpha_a, cfg.beta_a, state.step)
    fg, _ = factor_phase_gradients(
        state.params, idx, val, cfg.lambda_a, cfg.lambda_b,
        backend=cfg.backend, accum_dtype=cfg.accum_dtype, layout=layout,
    )
    params = _apply_updates(
        state.params, idx, fg, lr_a, jnp.asarray(0.0),
        update_factors=True, update_core=False, backend=cfg.backend,
        layout=layout,
    )
    masks = tuple(
        m.at[idx[:, n]].set(True) for n, m in enumerate(masks))
    return TrainState(params, state.step + 1), masks


def refresh_steps(
    state: TrainState,
    key: jax.Array,
    indices: jax.Array,
    values: jax.Array,
    cfg: FastTuckerConfig,
    num_steps: int,
) -> tuple[TrainState, tuple[np.ndarray, ...]]:
    """K bounded factor-phase SGD steps over a recent-nonzero window.

    The online-training primitive: the paper's one-step stochastic
    sampling touches only the gathered factor rows per step, so folding a
    window of NEW nonzeros into the model needs no epoch — K small
    factor-phase steps (core ``B^(n)`` frozen, exactly
    ``sgd_step(update_core=False)`` numerics) move only the rows the
    window samples.  Because the core is frozen, the serving tables
    C^(n) = A^(n)B^(n) change in exactly those rows, so the returned
    per-mode dirty-row sets — the union of sampled ``unique_ids`` across
    all K steps, collected device-side as boolean masks — are precisely
    the ids ``TuckerServer.update_rows`` must patch.

    Returns ``(state', dirty)`` where ``dirty[n]`` is a sorted int32
    ``np.ndarray`` of mode-``n`` row ids touched by the refresh.
    """
    if num_steps < 1:
        raise ValueError(f"num_steps must be ≥ 1, got {num_steps}")
    indices = jnp.asarray(indices)
    values = jnp.asarray(values)
    masks = tuple(
        jnp.zeros((f.shape[0],), jnp.bool_) for f in state.params.factors)
    for t in range(num_steps):
        sub = jax.random.fold_in(key, t)
        state, masks = _refresh_step(state, sub, indices, values, cfg, masks)
    dirty = tuple(
        np.nonzero(np.asarray(m))[0].astype(np.int32) for m in masks)
    return state, dirty


def train(
    key: jax.Array,
    tensor: SparseTensor,
    cfg: FastTuckerConfig,
    num_steps: int,
    eval_every: int = 0,
    test: SparseTensor | None = None,
    update_core: bool = True,
) -> tuple[TrainState, list[dict]]:
    """Simple single-host training loop (examples/benchmarks)."""
    from .metrics import rmse_mae

    key, init_key = jax.random.split(key)
    state = init_state(init_key, cfg, tensor.indices, tensor.values)
    history: list[dict] = []
    for step in range(num_steps):
        key, sub = jax.random.split(key)
        state = sgd_step(
            state, sub, tensor.indices, tensor.values, cfg,
            update_core=update_core,
        )
        if eval_every and ((step + 1) % eval_every == 0) and test is not None:
            r, m = rmse_mae(state.params, test, predict)
            history.append({"step": step + 1, "rmse": float(r), "mae": float(m)})
    return state, history
