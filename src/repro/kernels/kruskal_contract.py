"""Pallas TPU kernel: fused Theorem-1 Kruskal contraction.

This is the paper's per-nonzero hot loop (Algorithm 1 lines 4–10 / 20–27:
``c_r^(n) = ⟨b_r^(n), a_{i_n}⟩`` dot products + products across modes),
adapted from warp-shuffle reductions to MXU batched matmuls:

  for a VMEM tile of BT sampled nonzeros:
      c[n]    = a_tile[n] @ B[n]          # (BT,J)×(J,R) on the MXU
      pexc[n] = Π_{k≠n} c[k]              # division-free prefix/suffix
      pred    = Σ_r c[0]·pexc[0]

Inputs are zero-padded to a common J across modes (zero rows/cols change
nothing: they add 0 to every dot product). The small Kruskal factors
``B^(n)`` (N·J·R ≤ 10·32·32 floats) are fully VMEM-resident in every grid
step — the TPU analogue of the paper keeping B^(n) in shared memory.

Grid: 1-D over batch tiles, sized from the VMEM budget after lane padding
(``kernels.tiling``); ``pred`` is written as a lane-dense ``(1, B)`` row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import tiling


def _kernel(a_ref, b_ref, pred_ref, pexc_ref, *, n_modes: int,
            accum_dtype: str):
    # a_ref: (N, BT, J); b_ref: (N, J, R); pred_ref: (1, BT);
    # pexc_ref: (N, BT, R)
    acc_dt = jnp.dtype(accum_dtype)
    cs = []
    for n in range(n_modes):  # static unroll over modes (N ≤ 10)
        a_n = a_ref[n]                       # (BT, J)
        b_n = b_ref[n]                       # (J, R)
        cs.append(
            jax.lax.dot_general(
                a_n, b_n, (((1,), (0,)), ((), ())),
                preferred_element_type=acc_dt,
            )
        )
    # exclusive products via static prefix/suffix chains
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
    pred = jnp.sum(full, axis=-1, keepdims=True)
    pred_ref[...] = tiling.col_to_row(pred).astype(pred_ref.dtype)
    for n in range(n_modes):
        pexc_ref[n] = (prefix[n] * suffix[n]).astype(pexc_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret",
                                              "accum_dtype"))
def kruskal_contract(
    a_rows: jax.Array,  # (N, B, J)
    b_fac: jax.Array,   # (N, J, R)
    *,
    block_b: int | None = None,
    interpret: bool,
    accum_dtype: str = "float32",
) -> tuple[jax.Array, jax.Array]:
    """Returns (pred (B,), pexc (N, B, R)).

    Results come back in ``accum_dtype`` even for bf16 storage inputs —
    the in-kernel dots already accumulate at that precision; don't round
    back down on write.
    """
    N, B, J = a_rows.shape
    R = b_fac.shape[-1]
    acc_dt = jnp.dtype(accum_dtype)
    per_sample = (
        2 * N * tiling.lane_bytes(J, a_rows.dtype.itemsize)
        + 2 * N * tiling.lane_bytes(R, acc_dt.itemsize)       # pexc out
        + (3 * N + 1) * tiling.lane_bytes(R, acc_dt.itemsize)
    )
    bt, Bp = tiling.batch_tile(B, per_sample, block_b)
    if Bp != B:
        a_rows = jnp.pad(a_rows, ((0, 0), (0, Bp - B), (0, 0)))
    grid = (Bp // bt,)
    pred, pexc = pl.pallas_call(
        functools.partial(_kernel, n_modes=N, accum_dtype=accum_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((N, bt, J), lambda i: (0, i, 0)),
            pl.BlockSpec((N, J, R), lambda i: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bt), lambda i: (0, i)),
            pl.BlockSpec((N, bt, R), lambda i: (0, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, Bp), acc_dt),
            jax.ShapeDtypeStruct((N, Bp, R), acc_dt),
        ],
        compiler_params=tiling.compiler_params("parallel"),
        interpret=interpret,
    )(a_rows, b_fac)
    return pred[0, :B], pexc[:, :B]
