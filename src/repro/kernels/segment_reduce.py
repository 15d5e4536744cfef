"""Pallas TPU kernel: segmented-reduce scatter for mode-sorted row grads.

Counterpart of ``scatter_accum`` for batches in the mode-sorted layout
(``core.sampling.sorted_batch_layout``).  The one-hot kernel must sweep
every (row tile × batch tile) pair — O(rows × B) MXU work — because an
unsorted batch entry can target any row.  Sorted input makes each row's
contributions *contiguous*, so this kernel touches each entry once: O(B)
row adds, zero MXU work (the layout win cuFasterTucker gets from
per-mode-slice sorted nonzeros).

Accumulation order is ascending sorted position, which — because the sort
permutation is *stable* — is each row's original batch order, starting
from zero.  That makes the f32 result bitwise-identical to
``jax.ops.segment_sum`` over the unsorted batch (the jnp reference).

Layout.  The output table is tiled over rows: one ``(RT, J)`` block at a
time lives in VMEM, whatever the table's height (480,189 rows at the
Netflix shape).  Because the ids are sorted, the entries of row tile t
are one contiguous run ``[lo[t], lo[t+1])`` of the batch, which may span
several batch chunks.  The 1-D grid walks *work items* — the (row tile,
batch chunk) pairs whose ranges intersect, in row-tile order — so it has
at most ``tiles + chunks`` steps (megablox's group metadata, applied to a
scatter).  The item tables are scalar-prefetched into SMEM and drive the
block index maps; the chunk's ids ride along as an SMEM block, and each
entry is added into its row with a dynamic-sublane read-modify-write of
an f32 VMEM accumulator that is written to the output block on the
tile's last item.  A tile no entry touches still gets one item, which
writes its zeros.  Out-of-range ids (negative = strata padding, or past
``num_rows``) sort outside every tile's run and are dropped, exactly like
``segment_sum``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import tiling

_PAD_ID = jnp.iinfo(jnp.int32).max  # sorts last, lands in no tile


def _kernel(tile_ref, chunk_ref, p0_ref, p1_ref,    # scalar prefetch
            ids_ref, g_ref, out_ref, acc_ref, *, block_rows: int,
            block_b: int):
    w = pl.program_id(0)
    t = tile_ref[w]
    first = (w == 0) | (tile_ref[jnp.maximum(w - 1, 0)] != t)
    last = ((w == pl.num_programs(0) - 1)
            | (tile_ref[jnp.minimum(w + 1, pl.num_programs(0) - 1)] != t))

    @pl.when(first)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    base = chunk_ref[w] * block_b
    row0 = t * block_rows

    def body(p, carry):
        b = p - base
        r = ids_ref[b] - row0
        acc_ref[pl.ds(r, 1), :] += g_ref[pl.ds(b, 1), :]
        return carry

    jax.lax.fori_loop(p0_ref[w], p1_ref[w], body, 0)

    @pl.when(last)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _work_items(ids: jax.Array, num_rows: int, block_rows: int,
                block_b: int, num_chunks: int):
    """(tile, chunk, start, stop) per grid step, padded to a static count.

    Tile t owns sorted positions ``[lo[t], lo[t+1])``; each tile gets one
    item per batch chunk its run touches (at least one, so every tile is
    written).  Padding items repeat the last tile with an empty range.
    """
    num_tiles = -(-num_rows // block_rows)
    bounds = jnp.minimum(jnp.arange(num_tiles + 1) * block_rows, num_rows)
    lo = jnp.searchsorted(ids, bounds.astype(jnp.int32),
                          side="left").astype(jnp.int32)
    last_chunk = num_chunks - 1
    first_c = jnp.minimum(lo[:-1] // block_b, last_chunk)
    end_c = jnp.minimum((jnp.maximum(lo[1:], lo[:-1] + 1) - 1) // block_b,
                        last_chunk)
    count = end_c - first_c + 1
    ends = jnp.cumsum(count)
    starts = ends - count
    steps = num_tiles + num_chunks
    w = jnp.arange(steps, dtype=jnp.int32)
    tile = jnp.minimum(jnp.searchsorted(ends, w, side="right"),
                       num_tiles - 1).astype(jnp.int32)
    chunk = jnp.minimum(first_c[tile] + w - starts[tile], last_chunk)
    active = w < ends[-1]
    p0 = jnp.maximum(lo[tile], chunk * block_b)
    p1 = jnp.minimum(lo[tile + 1], (chunk + 1) * block_b)
    p0 = jnp.where(active, p0, 0)
    p1 = jnp.where(active, jnp.maximum(p1, p0), 0)
    return (tile, chunk.astype(jnp.int32), p0.astype(jnp.int32),
            p1.astype(jnp.int32)), num_tiles, steps


@functools.partial(
    jax.jit,
    static_argnames=("num_rows", "block_rows", "block_b", "interpret"))
def segment_reduce(
    grads: jax.Array,  # (B, J) row grads PERMUTED to sorted order
    idx: jax.Array,    # (B,) int32 sorted row ids (layout.sorted_rows[n])
    num_rows: int,
    *,
    block_rows: int = 2048,
    block_b: int | None = None,
    interpret: bool,
) -> jax.Array:
    """Sorted segment-sum scatter -> (num_rows, J).

    Exact (duplicates summed in sorted — i.e. original batch — order);
    bitwise-identical to ``jax.ops.segment_sum`` of the unpermuted grads
    in f32.  ``block_rows`` is the output row tile, ``block_b`` caps the
    batch chunk (default: the VMEM budget).
    """
    B, J = grads.shape
    out_dtype = grads.dtype
    # the per-entry add reads single rows at dynamic sublane offsets,
    # which Mosaic supports for 32-bit rows only; accumulation is f32
    # anyway, so widen narrower grads up front
    grads = grads.astype(jnp.float32)
    bc, Bp = tiling.batch_tile(B, 2 * tiling.lane_bytes(J), block_b)
    idx = idx.astype(jnp.int32)
    if Bp != B:
        grads = jnp.pad(grads, ((0, Bp - B), (0, 0)))
        idx = jnp.pad(idx, (0, Bp - B), constant_values=_PAD_ID)
    rt = min(block_rows, tiling.round_up(num_rows, 2 * tiling.SUBLANES))
    items, num_tiles, steps = _work_items(idx, num_rows, rt, bc, Bp // bc)

    def by_tile(w, tile, chunk, p0, p1):
        return tile[w], 0

    def by_chunk(w, tile, chunk, p0, p1):
        return chunk[w], 0

    out = pl.pallas_call(
        functools.partial(_kernel, block_rows=rt, block_b=bc),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(steps,),
            in_specs=[
                pl.BlockSpec((bc,), lambda w, tile, chunk, p0, p1:
                             (chunk[w],), memory_space=pltpu.SMEM),
                pl.BlockSpec((bc, J), by_chunk),
            ],
            out_specs=pl.BlockSpec((rt, J), by_tile),
            scratch_shapes=[pltpu.VMEM((rt, J), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((num_tiles * rt, J), out_dtype),
        compiler_params=tiling.compiler_params("arbitrary"),
        interpret=interpret,
    )(*items, idx, grads)
    return out[:num_rows]
