"""Synthetic HOHDST generators (paper Tables 4 & 5 analogues).

``planted_tensor`` draws ground-truth Tucker factors and emits noisy
observations at distinct uniformly random indices — used for
convergence/accuracy benchmarks (the RMSE floor is the noise level).  A
tensor whose flat index fits in int64 draws flat indices; a larger one
(the paper's synthesis tensors from order 5 up) draws each mode's
coordinate on its own.

``ratings_tensor`` mimics the real recommender datasets: values in
[min_value, max_value], heavy-tailed mode sizes.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core.sptensor import SparseTensor


_MIX = np.uint64(0x9E3779B97F4A7C15)


def _row_keys(idx):
    """A 64-bit hash of each index tuple: equal tuples, equal keys."""
    keys = np.zeros(len(idx), dtype=np.uint64)
    for n in range(idx.shape[1]):
        keys = (keys ^ idx[:, n].astype(np.uint64)) * _MIX
        keys ^= keys >> np.uint64(29)
    return keys


def _unique_rows_per_mode(rng, dims, nnz):
    """nnz distinct index tuples, each mode's coordinate drawn on its own.

    For tensors whose flat index overflows int64.  A row is kept only if
    its key (``_row_keys``) is new, so the rows kept are distinct; a
    duplicate (or a key collision) is dropped and the shortfall redrawn.
    """
    idx = np.zeros((0, len(dims)), dtype=np.int32)
    keys = np.zeros(0, dtype=np.uint64)
    while len(idx) < nnz:
        extra = np.stack([rng.integers(0, d, size=nnz - len(idx))
                          for d in dims], axis=1).astype(np.int32)
        keys = np.concatenate([keys, _row_keys(extra)])
        idx = np.concatenate([idx, extra])
        _, first = np.unique(keys, return_index=True)
        first.sort()
        idx, keys = idx[first], keys[first]
    return idx


def _unique_indices(rng, dims, nnz):
    """nnz distinct random index tuples (rejection-free for sparse regime)."""
    dims = np.asarray(dims, dtype=np.int64)
    total = np.prod(dims.astype(object))
    if total > 2 ** 63:
        return _unique_rows_per_mode(rng, dims, nnz)
    flat = rng.integers(0, int(total), size=int(nnz * 1.2), dtype=np.int64)
    flat = np.unique(flat)[:nnz]
    while len(flat) < nnz:
        extra = rng.integers(0, int(total), size=nnz, dtype=np.int64)
        flat = np.unique(np.concatenate([flat, extra]))[:nnz]
    idx = np.zeros((nnz, len(dims)), dtype=np.int32)
    rem = flat
    for n in range(len(dims)):
        idx[:, n] = rem % dims[n]
        rem = rem // dims[n]
    return idx


def planted_tensor(
    dims: tuple[int, ...],
    nnz: int,
    rank: int = 4,
    core_rank: int = 4,
    noise: float = 0.05,
    seed: int = 0,
) -> SparseTensor:
    """Observations of a planted Kruskal-core Tucker model + Gaussian noise."""
    rng = np.random.default_rng(seed)
    N = len(dims)
    idx = _unique_indices(rng, dims, nnz)
    scale = (1.0 / core_rank) ** (0.5 / N) / np.sqrt(rank)
    A = [rng.uniform(0, 2 * scale, (dims[n], rank)).astype(np.float32)
         for n in range(N)]
    B = [rng.uniform(0, 2 * scale, (rank, core_rank)).astype(np.float32)
         for n in range(N)]
    # x̂ = Σ_r Π_n ⟨a_{i_n}, b_r^(n)⟩ — evaluate in chunks
    vals = np.zeros(nnz, dtype=np.float32)
    chunk = 1 << 18
    for s in range(0, nnz, chunk):
        sl = slice(s, min(s + chunk, nnz))
        c = None
        for n in range(N):
            cn = A[n][idx[sl, n]] @ B[n]  # (b, R)
            c = cn if c is None else c * cn
        vals[sl] = c.sum(-1)
    vals += rng.normal(0, noise, nnz).astype(np.float32)
    return SparseTensor(jnp.asarray(idx), jnp.asarray(vals), tuple(dims))


def ratings_tensor(
    dims: tuple[int, ...],
    nnz: int,
    min_value: float = 1.0,
    max_value: float = 5.0,
    rank: int = 8,
    seed: int = 0,
) -> SparseTensor:
    """Recommender-style tensor: planted low-rank signal squashed to range."""
    t = planted_tensor(dims, nnz, rank=rank, core_rank=rank, noise=0.1,
                       seed=seed)
    v = np.asarray(t.values)
    lo, hi = np.quantile(v, [0.01, 0.99])
    v = (v - lo) / max(hi - lo, 1e-6)
    v = np.clip(v, 0, 1) * (max_value - min_value) + min_value
    return SparseTensor(t.indices, jnp.asarray(v.astype(np.float32)), t.dims)


# Paper Table 5 synthesis set (scaled down by `scale` for CPU runs)
def synthesis_suite(scale: float = 1e-3, seed: int = 0) -> dict[str, SparseTensor]:
    spec = {
        "order3": ((10_000,) * 3, 1_000_000_000),
        "order4": ((10_000,) * 4, 800_000_000),
        "order5": ((10_000,) * 5, 600_000_000),
        **{f"order{k}": ((10_000,) * k, 100_000_000) for k in range(6, 11)},
    }
    out = {}
    for name, (dims, nnz) in spec.items():
        n = max(int(nnz * scale), 10_000)
        d = tuple(max(int(x * scale ** (1 / len(dims))), 64) for x in dims)
        out[name] = planted_tensor(d, n, seed=seed)
    return out
