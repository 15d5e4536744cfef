"""Pallas TPU kernel: fused Theorem-1/2 forward + gradient pass, phase-aware.

One ``pallas_call`` tile pass computes, for a VMEM tile of BT sampled
nonzeros, the per-sample hot loop of the paper (Algorithm 1 lines 4–10
*and* the Eq. 13 / Eq. 17 gradient stage that the follow-up
cuFasterTucker fuses on-GPU):

    c[n]     = a_tile[n] @ B[n]                 # (BT,J)×(J,R) on the MXU
    pexc[n]  = Π_{k≠n} c[k]                     # division-free prefix/suffix
    pred     = Σ_r Π_n c[n]
    err      = (pred − x) ⊙ mask
    drow[n]  = (err/ρ)·(pexc[n] B^(n)ᵀ) + (λ_a/ρ)·mask·a_tile[n]   # Eq. 13
    dcore[n] += a_tile[n]ᵀ (err/δ ⊙ pexc[n])                        # Eq. 17

with ρ = row denominator, δ = core denominator (batch / valid-sample
mean), both precomputed on the host side of the trace and passed in as a
small scalar vector.  The Kruskal factors ``B^(n)`` stay fully
VMEM-resident across every grid step (the shared-memory trick of
``kruskal_contract.py``), and the (N, J, R) core-gradient accumulator
uses the revisiting-output trick: its block index is constant across the
1-D batch grid, so Pallas keeps it in VMEM and the kernel accumulates
partial sums across tiles, seeding tile 0 with the λ_b·B^(n) regularizer.

Phase-split extensions (cuFasterTucker's invariant-intermediate caching):

  * ``emit_c=True``   writes the per-tile mode products c[n] out as an
    extra ``(N, B, R)`` result — the ``StepIntermediates`` cache the core
    phase consumes later.  The tile never round-trips through HBM inside
    the pass: it is produced on the MXU, used for the chains, and only
    then stored.
  * ``c=...``         consumes a cached ``(N, B, R)`` tile instead of
    re-running the N mode dots — the dominant saving of the phase-split
    step: a ``pallas_call`` body is opaque to XLA, so unlike the jnp
    reference path there is no CSE/DCE to rescue redundant in-kernel
    dots; skipping them here is a *real* FLOP reduction.
  * ``row_modes``     emits Eq.-13 row gradients only for the selected
    modes (the Gauss-Seidel phase-split updates one mode per pass);
    ``()`` skips the row-gradient stage entirely.
  * ``want_core``     gates the Eq.-17 accumulator (the factor phase
    does not need it).

Mixed precision: inputs may be bf16 (storage dtype); every MXU dot uses
``preferred_element_type=accum_dtype`` (f32) and ALL results — pred, err,
row/core gradients, emitted c — are produced in ``accum_dtype``, so the
revisited core-gradient accumulator never accumulates in bf16.

Zero padding is exact end to end: padded J columns produce zero dot
products and zero gradient columns; padded batch rows carry mask 0 and
therefore contribute nothing to the core accumulator.

Layout: the per-sample vectors (val, mask, pred, err) are lane-dense
``(1, B)`` rows and the five scalars sit in SMEM (``kernels.tiling``), so
the kernel lowers for TPU whatever tiling XLA gives a 1-D array.  Grid:
1-D over batch tiles; the tile is the largest multiple of 128 whose
lane-padded buffers fit the VMEM budget (about 1.4k samples at N=3,
J=R=32), and any batch is zero-padded up to whole tiles.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import tiling

# layout of the scalar vector input; PRED_COEF generalizes the residual to
# err = (pred_coef·pred − val)·mask — 1 for training (err = pred − x), 0 for
# the custom-VJP backward pass, which passes val = −ḡ so err = ḡ EXACTLY
# (computing pred − (pred − ḡ) instead would catastrophically cancel in f32
# whenever |ḡ| is below ulp(pred), silently zeroing gradients).
(SCAL_INV_ROW, SCAL_INV_CORE, SCAL_LAM_A, SCAL_LAM_B,
 SCAL_PRED_COEF) = range(5)
NUM_SCALARS = 5


class KernelOuts(NamedTuple):
    """Outputs of the phase-aware fused kernel (absent stages are None)."""
    pred: jax.Array                        # (B,) accum dtype
    err: jax.Array                         # (B,)
    row_grads: Optional[jax.Array] = None  # (len(row_modes), B, J)
    core_grads: Optional[jax.Array] = None  # (N, J, R)
    c: Optional[jax.Array] = None          # (N, B, R) emitted mode products


def _kernel(*refs, n_modes: int, row_modes: tuple, want_core: bool,
            emit_c: bool, consume_c: bool, accum_dtype: str):
    # ins:  scal (5,) SMEM; a (N, BT, J); b (N, J, R); val (1, BT);
    #       mask (1, BT); [c_in (N, BT, R) when consume_c]
    # outs: pred (1, BT); err (1, BT); [rg (len(row_modes), BT, J)];
    #       [cg (N, J, R) — revisited across the grid]; [c_out (N, BT, R)]
    acc_dt = jnp.dtype(accum_dtype)
    it = iter(refs)
    scal_ref, a_ref, b_ref, val_ref, mask_ref = (next(it) for _ in range(5))
    c_ref = next(it) if consume_c else None
    pred_ref, err_ref = next(it), next(it)
    rg_ref = next(it) if row_modes else None
    cg_ref = next(it) if want_core else None
    cout_ref = next(it) if emit_c else None

    if consume_c:
        # the invariant-intermediate cache: mode dots already on hand
        cs = [c_ref[n] for n in range(n_modes)]
    else:
        cs = [
            jax.lax.dot_general(
                a_ref[n], b_ref[n], (((1,), (0,)), ((), ())),
                preferred_element_type=acc_dt,
            )
            for n in range(n_modes)  # static unroll over modes (N ≤ 10)
        ]
    if emit_c:
        for n in range(n_modes):
            cout_ref[n] = cs[n].astype(cout_ref.dtype)

    prefix = [None] * n_modes
    suffix = [None] * n_modes
    acc = jnp.ones_like(cs[0])
    for n in range(n_modes):
        prefix[n] = acc
        acc = acc * cs[n]
    full = acc
    acc = jnp.ones_like(cs[0])
    for n in reversed(range(n_modes)):
        suffix[n] = acc
        acc = acc * cs[n]

    pred = jnp.sum(full, axis=-1, keepdims=True)        # (BT, 1) accum
    mask = tiling.row_to_col(mask_ref[...].astype(pred.dtype))
    val = tiling.row_to_col(val_ref[...].astype(pred.dtype))
    err = (scal_ref[SCAL_PRED_COEF] * pred - val) * mask
    pred_ref[...] = tiling.col_to_row(pred).astype(pred_ref.dtype)
    err_ref[...] = tiling.col_to_row(err).astype(err_ref.dtype)

    inv_row = scal_ref[SCAL_INV_ROW]
    inv_core = scal_ref[SCAL_INV_CORE]
    lam_a = scal_ref[SCAL_LAM_A]
    lam_b = scal_ref[SCAL_LAM_B]
    w_row = err * inv_row                               # (BT, 1)
    w_core = err * inv_core

    if want_core:
        @pl.when(pl.program_id(0) == 0)
        def _seed_core():                               # λ_b·B^(n) once
            cg_ref[...] = (lam_b * b_ref[...]).astype(cg_ref.dtype)

    for j, n in enumerate(row_modes):
        pexc_n = prefix[n] * suffix[n]                  # (BT, R)
        # Eq. 13: err·(pexc B^T) + λ_a·a (padding rows killed via mask)
        d_n = jax.lax.dot_general(
            pexc_n, b_ref[n], (((1,), (1,)), ((), ())),
            preferred_element_type=acc_dt,
        )                                               # (BT, J)
        rg_ref[j] = (
            w_row * d_n
            + (lam_a * inv_row) * mask * a_ref[n].astype(acc_dt)
        ).astype(rg_ref.dtype)
    if want_core:
        for n in range(n_modes):
            pexc_n = prefix[n] * suffix[n]
            # Eq. 17 partial: aᵀ (err ⊙ pexc), accumulated across batch tiles
            cg_ref[n] += jax.lax.dot_general(
                a_ref[n].astype(acc_dt), w_core * pexc_n,
                (((0,), (0,)), ((), ())),
                preferred_element_type=acc_dt,
            ).astype(cg_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "row_modes", "want_core", "emit_c", "block_b", "interpret",
    "accum_dtype"))
def kruskal_grad(
    a_rows: jax.Array,  # (N, B, J)  gathered factor rows (J zero-padded)
    b_fac: jax.Array,   # (N, J, R)  Kruskal core factors (zero-padded)
    val: jax.Array,     # (B,)       sampled tensor values
    mask: jax.Array,    # (B,)       1.0 valid / 0.0 padding
    scal: jax.Array,    # (5,)  [1/ρ_row, 1/δ_core, λ_a, λ_b, pred_coef]
    c: jax.Array | None = None,  # (N, B, R) cached mode products (consume)
    *,
    row_modes: tuple[int, ...] | None = None,  # None = all; () = none
    want_core: bool = True,
    emit_c: bool = False,
    block_b: int | None = None,
    interpret: bool,
    accum_dtype: str = "float32",
) -> KernelOuts:
    """Fused contraction + Eq.13/17 gradients in a single ``pallas_call``.

    Default flags reproduce the original fully fused joint pass; the
    phase-split step uses ``emit_c`` (factor phase: cache the mode
    products) and ``c=``/``row_modes``/``want_core`` (consume the cache,
    compute only the gradients this phase needs).  ``core_grads`` already
    includes the λ_b·B regularizer term.  ``block_b`` caps the batch tile
    (default: what fits the VMEM budget); ``interpret`` has no default —
    the backend states whether the kernel is compiled or interpreted.
    """
    N, B, J = a_rows.shape
    R = b_fac.shape[-1]
    acc_dt = jnp.dtype(accum_dtype)
    if row_modes is None:
        row_modes = tuple(range(N))
    nr = len(row_modes)
    acc_b = acc_dt.itemsize
    per_sample = (
        2 * N * tiling.lane_bytes(J, a_rows.dtype.itemsize)   # a, 2 buffers
        + 2 * nr * tiling.lane_bytes(J, acc_b)                # row grads
        + 2 * N * tiling.lane_bytes(R, acc_b) * ((c is not None) + emit_c)
        + (3 * N + 4) * tiling.lane_bytes(R, acc_b)           # c/chains/pexc
        + N * tiling.lane_bytes(J, acc_b)                     # a in accum
    )
    bt, Bp = tiling.batch_tile(B, per_sample, block_b)
    if Bp != B:
        pad = Bp - B
        a_rows = jnp.pad(a_rows, ((0, 0), (0, pad), (0, 0)))
        val = jnp.pad(val, (0, pad))
        mask = jnp.pad(mask, (0, pad))  # zeros: no core/err contribution
        if c is not None:
            c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    grid = (Bp // bt,)
    row_spec = pl.BlockSpec((1, bt), lambda i: (0, i))

    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((N, bt, J), lambda i: (0, i, 0)),
        pl.BlockSpec((N, J, R), lambda i: (0, 0, 0)),
        row_spec,
        row_spec,
    ]
    operands = [scal.astype(jnp.float32), a_rows, b_fac,
                val.reshape(1, Bp), mask.reshape(1, Bp)]
    if c is not None:
        in_specs.append(pl.BlockSpec((N, bt, R), lambda i: (0, i, 0)))
        operands.append(c)

    out_specs = [row_spec, row_spec]
    out_shape = [
        jax.ShapeDtypeStruct((1, Bp), acc_dt),
        jax.ShapeDtypeStruct((1, Bp), acc_dt),
    ]
    if nr:
        out_specs.append(pl.BlockSpec((nr, bt, J), lambda i: (0, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((nr, Bp, J), acc_dt))
    if want_core:
        out_specs.append(pl.BlockSpec((N, J, R), lambda i: (0, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((N, J, R), acc_dt))
    if emit_c:
        out_specs.append(pl.BlockSpec((N, bt, R), lambda i: (0, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((N, Bp, R), acc_dt))

    outs = pl.pallas_call(
        functools.partial(
            _kernel, n_modes=N, row_modes=row_modes, want_core=want_core,
            emit_c=emit_c, consume_c=c is not None,
            accum_dtype=accum_dtype),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        # the core-gradient block is revisited by every step
        compiler_params=tiling.compiler_params("arbitrary"),
        interpret=interpret,
    )(*operands)

    it = iter(outs)
    pred, err = next(it)[0, :B], next(it)[0, :B]
    rg = next(it)[:, :B] if nr else None
    cg = next(it) if want_core else None
    c_out = next(it)[:, :B] if emit_c else None
    return KernelOuts(pred, err, rg, cg, c_out)
