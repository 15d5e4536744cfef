"""RMSE / MAE over a held-out set Γ (paper §6.1), chunked to bound memory."""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from .sptensor import SparseTensor


@partial(jax.jit, static_argnames=("predict_fn",))
def _chunk_err(params, idx, val, predict_fn):
    with jax.named_scope("repro.eval.chunk"):
        pred = predict_fn(params, idx)
        err = pred - val
        return jnp.sum(err**2), jnp.sum(jnp.abs(err))


def rmse_mae(
    params,
    test: SparseTensor,
    predict_fn: Callable,
    chunk: int = 262144,
) -> tuple[jax.Array, jax.Array]:
    """√(Σ(v−ṽ)²/|Γ|),  Σ|v−ṽ|/|Γ| — streamed in chunks."""
    nnz = test.nnz
    se = jnp.asarray(0.0)
    ae = jnp.asarray(0.0)
    for start in range(0, nnz, chunk):
        idx = test.indices[start : start + chunk]
        val = test.values[start : start + chunk]
        s, a = _chunk_err(params, idx, val, predict_fn)
        se = se + s
        ae = ae + a
    return jnp.sqrt(se / nnz), ae / nnz
