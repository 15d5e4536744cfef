"""RMSE / MAE over a held-out set Γ (paper §6.1), chunked to bound memory.

The whole held-out pass is one compiled program: it packs each factor
table once into a lane-dense copy (``LaneDenseTable``) and walks the
held-out nonzeros in fixed-size chunks inside a ``lax.fori_loop``.  A
TPU keeps a narrow table (I, 32) in the transposed layout ``{0,1}``,
where one row spreads over four (8,128) tiles, one lane in each; a row
of the packed copy is a quarter of one contiguous 128-lane line.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from .sptensor import SparseTensor

LANES = 128


def _padded_width(width: int) -> int:
    """The least width ≥ ``width`` that tiles a 128-lane line exactly:
    a divisor of 128 below it, a multiple of 128 above it."""
    if width >= LANES:
        return -(-width // LANES) * LANES
    return 1 << (width - 1).bit_length()


@jax.tree_util.register_pytree_node_class
class LaneDenseTable:
    """A factor table A (I, J) kept as ``lines`` (⌈I/p⌉, p·J'): p = 128 // J'
    rows to a 128-lane line, J' = ``_padded_width(J)`` (zero columns pad
    J up to it).  ``table[ids]`` is ``A[ids]`` bit for bit, for ids in
    [0, I): row i is slot i % p of line i // p, picked by selects."""

    def __init__(self, lines: jax.Array, width: int):
        self.lines = lines
        self.width = width

    @classmethod
    def pack(cls, table: jax.Array) -> "LaneDenseTable":
        rows, width = table.shape
        wide = _padded_width(width)
        per_line = max(1, LANES // wide)
        lines = -(-rows // per_line)
        padded = jnp.pad(table, ((0, lines * per_line - rows),
                                 (0, wide - width)))
        return cls(padded.reshape(lines, per_line * wide), width)

    def __getitem__(self, ids: jax.Array) -> jax.Array:
        wide = _padded_width(self.width)
        p = self.lines.shape[-1] // wide
        view = self.lines[ids // p].reshape(*ids.shape, p, wide)
        slot = (ids % p)[..., None]
        out = view[..., 0, :]
        for k in range(1, p):
            out = jnp.where(slot == k, view[..., k, :], out)
        return out[..., : self.width]

    def tree_flatten(self):
        return (self.lines,), self.width

    @classmethod
    def tree_unflatten(cls, width, children):
        return cls(children[0], width)


def _pack_factors(params):
    """``params`` with each factor table A^(n) packed lane-dense, for
    parameters that carry their tables as ``factors``; others as given."""
    factors = getattr(params, "factors", None)
    if factors is None or not hasattr(params, "_replace"):
        return params
    return params._replace(
        factors=tuple(LaneDenseTable.pack(f) for f in factors))


@partial(jax.jit, static_argnames=("predict_fn", "chunk"))
def _held_out_err(params, indices, values, predict_fn, chunk):
    """(Σ err², Σ |err|) over every held-out nonzero, ``chunk`` at a time;
    the last chunk ends at the last nonzero and leaves out what the one
    before it counted."""
    nnz = values.shape[0]
    with jax.named_scope("repro.eval.chunk"):
        with jax.named_scope("lane_pack"):
            packed = _pack_factors(params)

        def body(k, sums):
            start = jnp.minimum(k * chunk, nnz - chunk)
            idx = lax.dynamic_slice_in_dim(indices, start, chunk)
            val = lax.dynamic_slice_in_dim(values, start, chunk)
            err = predict_fn(packed, idx) - val
            fresh = start + jnp.arange(chunk) >= k * chunk
            err = jnp.where(fresh, err, 0.0)
            return sums[0] + jnp.sum(err**2), sums[1] + jnp.sum(jnp.abs(err))

        zero = jnp.zeros((), values.dtype)
        return lax.fori_loop(0, -(-nnz // chunk), body, (zero, zero))


def rmse_mae(
    params,
    test: SparseTensor,
    predict_fn: Callable,
    chunk: int = 262144,
) -> tuple[jax.Array, jax.Array]:
    """√(Σ(v−ṽ)²/|Γ|),  Σ|v−ṽ|/|Γ| — streamed in chunks."""
    nnz = test.nnz
    se, ae = _held_out_err(params, test.indices, test.values, predict_fn,
                           min(chunk, nnz))
    return jnp.sqrt(se / nnz), ae / nnz
