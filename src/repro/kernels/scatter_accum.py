"""Pallas TPU kernel: MXU one-hot scatter-accumulate for factor-row grads.

The paper scatters per-nonzero gradients into factor rows with implicit
GPU write races. The TPU adaptation is race-free and systolic: for an output
row tile ``[i0, i0+IT)`` and a batch tile of BT samples,

    out[i0:i0+IT] += onehot(idx_tile − i0)ᵀ @ grads_tile      # (IT,BT)×(BT,J)

i.e. the scatter becomes a sequence of small matmuls on the MXU — exactly
how TPU embedding updates are lowered. Accumulation across batch tiles uses
the revisiting-output trick: the output block index depends only on the row
tile, so Pallas keeps the block resident in VMEM across the inner batch-tile
grid dimension.

Grid: (rows/IT, B/BT), output revisited along the second axis.  The row
ids arrive as a lane-dense ``(1, B)`` row, which is exactly the
orientation the ``(IT, BT)`` one-hot compare broadcasts along, so no
relayout is needed (``kernels.tiling``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import tiling


def _kernel(idx_ref, g_ref, out_ref, *, block_i: int):
    bi = pl.program_id(1)

    @pl.when(bi == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    i0 = pl.program_id(0) * block_i
    local = idx_ref[...] - i0               # (1, BT)
    g = g_ref[...]                          # (BT, J)
    bt = g.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_i, bt), 0)
    onehot = (rows == local).astype(g.dtype)            # (IT, BT)
    out_ref[...] += jax.lax.dot_general(
        onehot, g, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("num_rows", "block_i", "block_b", "interpret")
)
def scatter_accum(
    grads: jax.Array,  # (B, J)
    idx: jax.Array,    # (B,) int32
    num_rows: int,
    *,
    block_i: int = 256,
    block_b: int = 512,
    interpret: bool,
) -> jax.Array:
    """Segment-sum scatter -> (num_rows, J). Exact (duplicates summed).

    ``block_b`` caps the batch tile: every batch tile is one more f32
    accumulation into the revisited row block, so the cap fixes the
    association order of duplicate rows independently of the VMEM budget.
    """
    B, J = grads.shape
    it = min(block_i, tiling.round_up(num_rows, tiling.SUBLANES))
    per_sample = (2 * tiling.lane_bytes(J, grads.dtype.itemsize)
                  + 2 * it * 4)                      # ids + one-hot column
    bt, Bp = tiling.batch_tile(B, per_sample, block_b)
    if Bp != B:
        grads = jnp.pad(grads, ((0, Bp - B), (0, 0)))
        idx = jnp.pad(idx, (0, Bp - B), constant_values=-1)  # matches no row
    rows_p = tiling.round_up(num_rows, it)
    grid = (rows_p // it, Bp // bt)
    out = pl.pallas_call(
        functools.partial(_kernel, block_i=it),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt), lambda i, b: (0, b)),
            pl.BlockSpec((bt, J), lambda i, b: (b, 0)),
        ],
        out_specs=pl.BlockSpec((it, J), lambda i, b: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, J), grads.dtype),
        compiler_params=tiling.compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(idx.astype(jnp.int32).reshape(1, Bp), grads)
    return out[:num_rows]
