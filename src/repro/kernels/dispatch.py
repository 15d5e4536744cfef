"""Named kernel-backend registry — one dispatch point for every hot-loop op.

Named backends:

    ``"xla"``              pure-jnp reference path (default; runs anywhere)
    ``"pallas"``           Pallas kernels compiled via Mosaic (TPU)
    ``"pallas_interpret"`` Pallas kernels in interpret mode (CPU-testable,
                           bit-for-bit the same kernel bodies as ``"pallas"``)

Interpret mode is reached only by naming ``"pallas_interpret"``: the
kernel entry points have no ``interpret`` default, and nothing maps a
generic "use the kernels" request onto the interpreter.

Resolution order for ``get_backend(name)``:

    explicit ``name`` argument  >  ``$REPRO_KERNEL_BACKEND``  >  ``"xla"``

All backends speak the core library's tuple-of-modes layout (per-mode
``(B, J_n)`` gathered rows and ``(J_n, R)`` Kruskal factors with possibly
distinct ``J_n``); the Pallas backends zero-pad to the stacked ``(N, B, J)``
kernel layout internally and unpad results — zero padding is exact for every
op here (dot products and gradients of padded columns are identically zero).

Ops per backend:

    ``kruskal_contract``  Theorem-1 forward: ``(pred, pexc)``
    ``kruskal_grad``      fused forward + Eq.13/17 gradients (cuFasterTucker
                          style single-pass; one ``pallas_call`` on the
                          Pallas backends)
    ``scatter_accum``     factor-row segment-sum scatter (unsorted batches;
                          O(rows×B) one-hot MXU sweep on Pallas)
    ``segment_reduce``    factor-row scatter for MODE-SORTED batches
                          (``core.sampling.sorted_batch_layout``): a sorted
                          ``segment_sum`` on "xla", the O(B) segmented
                          walk kernel (``kernels.segment_reduce``) on the
                          Pallas backends; ``scatter_accum`` stays the
                          unsorted fallback
    ``tucker_matmul``     Tucker-2 factorized dense layer

New accelerator targets (Triton, CUDA, …) register via
``register_backend`` without touching any call site.
"""
from __future__ import annotations

import os
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

ENV_VAR = "REPRO_KERNEL_BACKEND"
DEFAULT_BACKEND = "xla"


class KruskalGrads(NamedTuple):
    """Fused forward+gradient results in the tuple-of-modes layout.

    ``row_grads`` follows the requested ``row_modes`` order (all modes by
    default) and is ``()`` when the row stage was skipped; ``core_grads``
    is ``()`` when ``want_core=False``; ``c`` holds the emitted per-mode
    ``(B, R)`` mode products (the ``StepIntermediates`` cache) when
    ``emit_c=True`` and ``()`` otherwise.
    """
    pred: jax.Array                      # (B,)
    err: jax.Array                       # (B,) masked residual
    row_grads: tuple[jax.Array, ...]     # per requested mode (B, J_n)
    core_grads: tuple[jax.Array, ...]    # per-mode (J_n, R)
    c: tuple[jax.Array, ...] = ()        # per-mode (B, R) when emitted


DEFAULT_ACCUM = "float32"


def resolve_accum_dtype(accum_dtype=None) -> jnp.dtype:
    """Accumulation dtype for every MXU dot (bf16 storage still sums f32)."""
    return jnp.dtype(accum_dtype or DEFAULT_ACCUM)


def _mode_dot(rows_n: jax.Array, core_n: jax.Array,
              accum_dtype=None) -> jax.Array:
    """Single-mode Theorem-1 product c^(n) = a_rows^(n) B^(n) → (B, R).

    The Gauss-Seidel phase-split step refreshes exactly one cached mode
    product after each mode's row update through this op.  Shared by
    both backends: a lone (B, J)×(J, R) contraction is one MXU matmul,
    for which XLA's native dot IS the optimal kernel — no ``pallas_call``
    even on the Pallas backends.
    """
    return jnp.matmul(rows_n, core_n,
                      preferred_element_type=resolve_accum_dtype(accum_dtype))


def _denominators(
    batch: int,
    mask: jax.Array | None,
    row_mean: bool,
    core_mean: bool,
) -> tuple[jax.Array, jax.Array]:
    """(row_denom ρ, core_denom δ) matching the paper's M=1 semantics."""
    if core_mean:
        if mask is not None:
            core = jnp.maximum(jnp.sum(mask), 1.0).astype(jnp.float32)
        else:
            core = jnp.asarray(float(batch), jnp.float32)
    else:
        core = jnp.asarray(1.0, jnp.float32)
    row = core if row_mean else jnp.asarray(1.0, jnp.float32)
    return row, core


# ---------------------------------------------------------------------------
# "xla" — pure-jnp reference backend
# ---------------------------------------------------------------------------

class XlaBackend:
    """Pure-jnp ops; the numerics oracle every kernel backend must match."""

    name = "xla"
    interpret = None  # not a Pallas backend

    mode_dot = staticmethod(_mode_dot)

    def kruskal_contract(
        self,
        rows: Sequence[jax.Array],
        core_factors: Sequence[jax.Array],
        accum_dtype=None,
    ) -> tuple[jax.Array, jax.Array]:
        from repro.core.kruskal import exclusive_products, mode_dots

        c = mode_dots(rows, core_factors,
                      accum_dtype=resolve_accum_dtype(accum_dtype))
        full, pexc = exclusive_products(c)
        return jnp.sum(full, axis=-1), pexc

    def kruskal_grad(
        self,
        rows: Sequence[jax.Array],
        core_factors: Sequence[jax.Array],
        val: jax.Array,
        *,
        mask: jax.Array | None = None,
        lambda_a: float = 0.0,
        lambda_b: float = 0.0,
        row_mean: bool = False,
        core_mean: bool = True,
        err_override: jax.Array | None = None,
        c: Sequence[jax.Array] | None = None,
        row_modes: tuple[int, ...] | None = None,
        want_core: bool = True,
        emit_c: bool = False,
        accum_dtype=None,
    ) -> KruskalGrads:
        from repro.core.kruskal import exclusive_products

        acc_dt = resolve_accum_dtype(accum_dtype)
        N = len(rows)
        if row_modes is None:
            row_modes = tuple(range(N))
        if c is None:
            c_stack = None
            pred, pexc = self.kruskal_contract(rows, core_factors,
                                               accum_dtype=acc_dt)
        else:
            c_stack = jnp.stack(tuple(c), axis=0)       # (N, B, R)
            full, pexc = exclusive_products(c_stack)
            pred = jnp.sum(full, axis=-1)
        err = err_override if err_override is not None else pred - val
        if mask is not None:
            err = jnp.where(mask, err, 0.0)
        row_denom, core_denom = _denominators(
            val.shape[0], mask, row_mean, core_mean)
        w_row = err / row_denom
        w_core = err / core_denom
        row_grads = []
        for n in row_modes:
            pex_n = pexc[n]                             # (B, R)
            d_n = jnp.matmul(pex_n, core_factors[n].T,
                             preferred_element_type=acc_dt)  # (B, J_n)
            reg_rows = rows[n]
            if mask is not None:
                reg_rows = jnp.where(mask[:, None], reg_rows, 0.0)
            row_grads.append(
                w_row[:, None] * d_n + (lambda_a / row_denom) * reg_rows
            )
        core_grads = []
        if want_core:
            for n in range(N):
                core_grads.append(
                    jnp.matmul(rows[n].T, w_core[:, None] * pexc[n],
                               preferred_element_type=acc_dt)
                    + lambda_b * core_factors[n]
                )
        c_out = ()
        if emit_c:
            if c_stack is None:
                from repro.core.kruskal import mode_dots

                c_stack = mode_dots(rows, core_factors, accum_dtype=acc_dt)
            c_out = tuple(c_stack[n] for n in range(N))
        return KruskalGrads(pred, err, tuple(row_grads), tuple(core_grads),
                            c_out)

    def scatter_accum(
        self, grads: jax.Array, idx: jax.Array, num_rows: int
    ) -> jax.Array:
        return jax.ops.segment_sum(grads, idx, num_segments=num_rows)

    def segment_reduce(
        self, grads: jax.Array, idx: jax.Array, num_rows: int
    ) -> jax.Array:
        """Sorted-batch scatter: ``grads``/``idx`` are in mode-sorted order
        (duplicates adjacent, batch order preserved by the stable sort), so
        the segment sum accumulates contiguous runs — bitwise-identical to
        the unsorted ``scatter_accum`` in f32."""
        return jax.ops.segment_sum(grads, idx, num_segments=num_rows,
                                   indices_are_sorted=True)

    def tucker_matmul(self, x, u1, g, u2) -> jax.Array:
        return ((x @ u1) @ g) @ u2.T


# ---------------------------------------------------------------------------
# "pallas" / "pallas_interpret" — fused kernel backends
# ---------------------------------------------------------------------------

def _stack_padded_rows(rows: Sequence[jax.Array]) -> jax.Array:
    jmax = max(r.shape[-1] for r in rows)
    return jnp.stack(
        [jnp.pad(r, ((0, 0), (0, jmax - r.shape[-1]))) for r in rows], axis=0
    )


def _stack_padded_factors(core_factors: Sequence[jax.Array]) -> jax.Array:
    jmax = max(cf.shape[0] for cf in core_factors)
    return jnp.stack(
        [jnp.pad(cf, ((0, jmax - cf.shape[0]), (0, 0))) for cf in core_factors],
        axis=0,
    )


class PallasBackend:
    """Pallas kernels; ``interpret=True`` runs the same bodies on CPU.

    Tile sizes are the kernels' own (picked from the VMEM budget), so the
    backend carries only its name and whether it interprets.
    """

    def __init__(self, name: str, interpret: bool):
        self.name = name
        self.interpret = interpret

    mode_dot = staticmethod(_mode_dot)

    def kruskal_contract(
        self,
        rows: Sequence[jax.Array],
        core_factors: Sequence[jax.Array],
        accum_dtype=None,
    ) -> tuple[jax.Array, jax.Array]:
        from .kruskal_contract import kruskal_contract as kc

        a = _stack_padded_rows(rows)
        b = _stack_padded_factors(core_factors)
        return kc(a, b, interpret=self.interpret,
                  accum_dtype=str(resolve_accum_dtype(accum_dtype)))

    def kruskal_grad(
        self,
        rows: Sequence[jax.Array],
        core_factors: Sequence[jax.Array],
        val: jax.Array,
        *,
        mask: jax.Array | None = None,
        lambda_a: float = 0.0,
        lambda_b: float = 0.0,
        row_mean: bool = False,
        core_mean: bool = True,
        err_override: jax.Array | None = None,
        c: Sequence[jax.Array] | None = None,
        row_modes: tuple[int, ...] | None = None,
        want_core: bool = True,
        emit_c: bool = False,
        accum_dtype=None,
    ) -> KruskalGrads:
        from .kruskal_grad import kruskal_grad as kg

        acc_dt = resolve_accum_dtype(accum_dtype)
        a = _stack_padded_rows(rows)
        b = _stack_padded_factors(core_factors)
        row_denom, core_denom = _denominators(
            val.shape[0], mask, row_mean, core_mean)
        if mask is None:
            mask_f = jnp.ones_like(val, dtype=acc_dt)
        else:
            mask_f = mask.astype(acc_dt)
        if err_override is not None:
            # err = (0·pred − (−ḡ))·mask = ḡ exactly — NOT pred − (pred − ḡ),
            # which cancels catastrophically for |ḡ| < ulp(pred)
            val_in, pred_coef = -err_override, 0.0
        else:
            val_in, pred_coef = val, 1.0
        scal = jnp.stack([
            1.0 / row_denom,
            1.0 / core_denom,
            jnp.asarray(lambda_a, jnp.float32),
            jnp.asarray(lambda_b, jnp.float32),
            jnp.asarray(pred_coef, jnp.float32),
        ]).astype(acc_dt)
        c_stacked = (None if c is None
                     else jnp.stack(tuple(c), axis=0).astype(acc_dt))
        outs = kg(
            a, b, val_in.astype(acc_dt), mask_f, scal, c_stacked,
            row_modes=row_modes, want_core=want_core, emit_c=emit_c,
            interpret=self.interpret,
            accum_dtype=str(jnp.dtype(acc_dt)),
        )
        if row_modes is None:
            row_modes = tuple(range(len(rows)))
        row_grads = tuple(
            outs.row_grads[j, :, : rows[n].shape[-1]]
            for j, n in enumerate(row_modes)
        ) if row_modes else ()
        core_grads = tuple(
            outs.core_grads[n, : cf.shape[0]]
            for n, cf in enumerate(core_factors)
        ) if want_core else ()
        c_out = (tuple(outs.c[n] for n in range(len(rows)))
                 if emit_c else ())
        return KruskalGrads(outs.pred, outs.err, row_grads, core_grads,
                            c_out)

    def scatter_accum(
        self, grads: jax.Array, idx: jax.Array, num_rows: int
    ) -> jax.Array:
        from .scatter_accum import scatter_accum as sa

        return sa(grads, idx, num_rows, interpret=self.interpret)

    def segment_reduce(
        self, grads: jax.Array, idx: jax.Array, num_rows: int
    ) -> jax.Array:
        from .segment_reduce import segment_reduce as sr

        return sr(grads, idx, num_rows, interpret=self.interpret)

    def tucker_matmul(self, x, u1, g, u2) -> jax.Array:
        from .tucker_matmul import tucker_matmul as tm

        return tm(x, u1, g, u2, interpret=self.interpret)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, object] = {}


def register_backend(backend, *, overwrite: bool = False) -> None:
    """Register ``backend`` (any object with the op methods + ``name``)."""
    if backend.name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {backend.name!r} already registered")
    _REGISTRY[backend.name] = backend


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def resolve_backend_name(name: str | None = None) -> str:
    """explicit arg > $REPRO_KERNEL_BACKEND > "xla"."""
    if name:
        return name
    return os.environ.get(ENV_VAR) or DEFAULT_BACKEND


def get_backend(name: str | None = None):
    resolved = resolve_backend_name(name)
    try:
        return _REGISTRY[resolved]
    except KeyError:
        raise KeyError(
            f"unknown kernel backend {resolved!r}; "
            f"available: {available_backends()}"
        ) from None


# ---------------------------------------------------------------------------
# differentiable entry point
# ---------------------------------------------------------------------------

import functools as _functools


@_functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def kruskal_predict(
    backend_name: str,
    rows: tuple[jax.Array, ...],
    core_factors: tuple[jax.Array, ...],
) -> jax.Array:
    """Theorem-1 prediction with a kernel-resident custom VJP.

    ``jax.grad`` through this routes BOTH passes through the named backend:
    the forward contraction kernel, and the fused ``kruskal_grad`` kernel
    with the cotangent ḡ injected as the residual (``err_override``), unit
    denominators, and zero regularizers — which then yields exactly
    ``∂pred/∂rows·ḡ`` and ``∂pred/∂B·ḡ``.
    """
    pred, _ = get_backend(backend_name).kruskal_contract(rows, core_factors)
    return pred


def _kruskal_predict_fwd(backend_name, rows, core_factors):
    pred, _ = get_backend(backend_name).kruskal_contract(rows, core_factors)
    return pred, (rows, core_factors)


def _kruskal_predict_bwd(backend_name, residuals, g):
    rows, core_factors = residuals
    kg = get_backend(backend_name).kruskal_grad(
        rows, core_factors, jnp.zeros_like(g),
        mask=None, lambda_a=0.0, lambda_b=0.0,
        row_mean=False, core_mean=False, err_override=g,
    )
    # cotangent dtypes must match the primals (bf16 storage params get
    # bf16 cotangents even though the kernel accumulated them in f32)
    return (
        tuple(t.astype(r.dtype) for t, r in zip(kg.row_grads, rows)),
        tuple(t.astype(b.dtype) for t, b in zip(kg.core_grads,
                                                core_factors)),
    )


kruskal_predict.defvjp(_kruskal_predict_fwd, _kruskal_predict_bwd)


# ---------------------------------------------------------------------------
# introspection helpers
# ---------------------------------------------------------------------------

def count_pallas_calls(jaxpr) -> int:
    """Recursively count ``pallas_call`` equations in a (closed) jaxpr.

    Structural check used by tests/benchmarks that the fused path lowers
    to a single kernel launch.
    """
    total = 0
    eqns = jaxpr.jaxpr.eqns if hasattr(jaxpr, "jaxpr") else jaxpr.eqns
    for eqn in eqns:
        if eqn.primitive.name == "pallas_call":
            total += 1
        for v in eqn.params.values():
            # sub-jaxprs may sit directly in a param (pjit) or inside a
            # tuple/list of them (lax.cond/switch branches)
            items = v if isinstance(v, (tuple, list)) else (v,)
            for item in items:
                if hasattr(item, "eqns") or hasattr(item, "jaxpr"):
                    total += count_pallas_calls(item)
    return total


register_backend(XlaBackend())
register_backend(PallasBackend("pallas", interpret=False))
register_backend(PallasBackend("pallas_interpret", interpret=True))


__all__ = [
    "ENV_VAR",
    "DEFAULT_BACKEND",
    "DEFAULT_ACCUM",
    "KruskalGrads",
    "resolve_accum_dtype",
    "XlaBackend",
    "PallasBackend",
    "register_backend",
    "available_backends",
    "resolve_backend_name",
    "get_backend",
    "kruskal_predict",
    "count_pallas_calls",
]
