"""Shared TPU tiling rules for the batch-tiled Pallas kernels.

Mosaic checks every operand's layout against the one XLA assigned, and a
1-D operand's tiling is XLA's choice (``f32[4096]`` is ``T(1024)``), so a
1-D block only lowers when the block happens to match it.  The kernels
here therefore keep every per-sample vector 2-D and lane-dense: a
``(1, B)`` row whose ``(1, bt)`` block is legal for any ``bt`` that is a
multiple of the 128-lane width.  Inside a kernel the samples of a
``(bt, J)`` tile run down the sublanes, so a row vector is turned into a
column (and back) with one aligned 2-D transpose — exact, no arithmetic.

The batch tile is picked from a VMEM budget instead of a fixed constant:
lane padding makes a ``(bt, 32)`` f32 tile cost ``bt·128·4`` bytes, and
the caller states how many such lane-padded rows one sample costs.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
# Scoped VMEM the batch-tiled kernels ask Mosaic for, and the share of it
# their explicitly blocked buffers and live intermediates may plan on;
# the rest is headroom for Mosaic's own scratch.  v5e has 128 MiB of VMEM.
VMEM_LIMIT_BYTES = 48 * 2**20
VMEM_BUDGET_BYTES = 20 * 2**20
MAX_BATCH_TILE = 4096


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def lane_bytes(width: int, itemsize: int = 4) -> int:
    """VMEM bytes of one row of a ``(rows, width)`` tile after lane padding."""
    return round_up(width, LANES) * itemsize


def batch_tile(batch: int, bytes_per_sample: int,
               block_b: int | None = None) -> tuple[int, int]:
    """(tile, padded batch) for a 1-D grid over ``batch`` samples.

    ``block_b`` caps the tile (tests use small caps to force several grid
    steps); by default the cap is what fits ``VMEM_BUDGET_BYTES``.  The
    tile is a multiple of the lane width, and the grid is balanced so the
    padded batch exceeds ``batch`` by less than one lane width per tile.
    """
    cap = block_b or VMEM_BUDGET_BYTES // max(bytes_per_sample, 1)
    cap = max(LANES, min(cap, MAX_BATCH_TILE) // LANES * LANES)
    tiles = -(-batch // cap)
    bt = round_up(-(-batch // tiles), LANES)
    return bt, bt * tiles


def compiler_params(*dimension_semantics: str):
    return pltpu.CompilerParams(
        dimension_semantics=dimension_semantics or None,
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def row_to_col(row):
    """``(1, n)`` lane-dense row → ``(n, 1)`` column (n % 128 == 0)."""
    n = row.shape[1]
    return jnp.transpose(jnp.broadcast_to(row, (SUBLANES, n)))[:, :1]


def col_to_row(col):
    """``(n, 1)`` column → ``(1, n)`` lane-dense row (n % 128 == 0)."""
    n = col.shape[0]
    return jnp.transpose(jnp.broadcast_to(col, (n, LANES)))[:1, :]
