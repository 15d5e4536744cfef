"""Plain reference of FastTucker SGD and of the serving queries.

Written from the paper's equations (Theorem 1, Eq. 13, Eq. 17, the
dynamic rate of §6.1) in ``jax.numpy`` at ``highest`` matmul precision,
so its float32 dots are float32 on the chip too.  It imports nothing of
the program and takes nothing the program made: it draws its own initial
factors and its own samples from the same seeds, and reads only the
benchmark's data.

Training semantics (the configuration's ``hyper`` block states them):

* Psi: ``batch`` nonzeros drawn uniformly with replacement, with key
  ``fold_in(loop_key, t)`` at step t;
* c_n = a_{i_n} B_n, Pexc_n = prod_{k != n} c_k, pred = sum_r prod_n c_n,
  err = pred - x;
* row gradient err · Pexc_n B_n^T + lambda_a a_{i_n}, summed per row
  (each sample is its own update of the rows it touches);
* core gradient mean_b a_{i_n}^T (err · Pexc_n) + lambda_b B_n;
* rate gamma_t = alpha / (1 + beta t^1.5), applied to both at once.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"


def _dot(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def init_scale(ranks, core_rank) -> float:
    """Half-range s of the cold uniform init: (1/R)^(1/2N) / sqrt(mean J)."""
    N = len(ranks)
    mean_j = sum(ranks) / N
    return float((1.0 / core_rank) ** (0.5 / N) / np.sqrt(mean_j))


@partial(jax.jit, static_argnames=("dims", "ranks", "core_rank", "dtype"))
def init_params(key, *, dims, ranks, core_rank, dtype="float32"):
    """Cold init: every entry uniform on [0, 2s), drawn in float32 from
    ``split(key, 2N)`` (factors first, then core factors), then stored in
    ``dtype``."""
    N = len(dims)
    keys = jax.random.split(key, 2 * N)
    s = init_scale(ranks, core_rank)
    dt = jnp.dtype(dtype)
    factors = tuple(
        jax.random.uniform(keys[n], (dims[n], ranks[n]), jnp.float32,
                           0.0, 2 * s).astype(dt) for n in range(N))
    core = tuple(
        jax.random.uniform(keys[N + n], (ranks[n], core_rank), jnp.float32,
                           0.0, 2 * s).astype(dt) for n in range(N))
    return factors, core


def sample(loop_key, t: int, indices, values, batch: int):
    key = jax.random.fold_in(loop_key, t)
    pick = jax.random.randint(key, (batch,), 0, values.shape[0])
    return indices[pick], values[pick]


def _coefficients(rows, core):
    return [_dot(r, b) for r, b in zip(rows, core)]


def _exclusive(c, n):
    out = None
    for k, ck in enumerate(c):
        if k != n:
            out = ck if out is None else out * ck
    return out


@partial(jax.jit, static_argnames=("batch", "hyper"))
def sgd_step(params, loop_key, t, indices, values, *, batch, hyper):
    """One reference step from ``params`` = (factors, core factors).

    ``hyper`` is a tuple of (name, value) pairs: lambda_a, lambda_b,
    alpha_a, beta_a, alpha_b, beta_b.
    """
    h = dict(hyper)
    factors, core = params
    idx, val = sample(loop_key, t, indices, values, batch)
    B = val.shape[0]
    f32 = [f.astype(jnp.float32) for f in factors]
    c32 = [b.astype(jnp.float32) for b in core]
    rows = [f[idx[:, n]] for n, f in enumerate(f32)]
    c = _coefficients(rows, c32)
    pred = jnp.sum(c[0] * _exclusive(c, 0), axis=-1)
    err = pred - val
    tf = jnp.asarray(t, jnp.float32)
    lr_a = h["alpha_a"] / (1.0 + h["beta_a"] * tf ** 1.5)
    lr_b = h["alpha_b"] / (1.0 + h["beta_b"] * tf ** 1.5)
    new_f, new_c = [], []
    for n in range(len(f32)):
        pex = _exclusive(c, n)
        g_rows = err[:, None] * _dot(pex, c32[n].T) + h["lambda_a"] * rows[n]
        dense = jnp.zeros_like(f32[n]).at[idx[:, n]].add(g_rows)
        g_core = _dot(rows[n].T, (err / B)[:, None] * pex) \
            + h["lambda_b"] * c32[n]
        new_f.append((f32[n] - lr_a * dense).astype(factors[n].dtype))
        new_c.append((c32[n] - lr_b * g_core).astype(core[n].dtype))
    return tuple(new_f), tuple(new_c)


@jax.jit
def _sq_err(params, idx, val):
    factors, core = params
    rows = [f.astype(jnp.float32)[idx[:, n]] for n, f in enumerate(factors)]
    c = _coefficients(rows, [b.astype(jnp.float32) for b in core])
    pred = jnp.sum(c[0] * _exclusive(c, 0), axis=-1)
    return jnp.sum((pred - val) ** 2)


def rmse(params, indices, values, chunk: int = 1 << 18) -> float:
    """Held-out RMSE, chunk sums added in float64 on the host."""
    nnz = int(values.shape[0])
    total = 0.0
    for s in range(0, nnz, chunk):
        e = min(s + chunk, nnz)
        total += float(_sq_err(params, indices[s:e], values[s:e]))
    return float(np.sqrt(total / nnz))


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

@jax.jit
def tables(factors, core):
    """C^(n) = A^(n) B^(n) and their column sums, float32 at highest."""
    ts = tuple(_dot(a, b) for a, b in zip(factors, core))
    return ts, tuple(t.sum(axis=0) for t in ts)


def quantize(x, dtype: str):
    """Round to ``dtype`` and back to float32 (a lower-precision table).
    float8 tables take one scale per table so that they use the format's
    range; the scale is a power of two, which rounds nothing itself."""
    dt = jnp.dtype(dtype)
    if dt == jnp.float32:
        return x
    if jnp.issubdtype(dt, jnp.floating) and jnp.finfo(dt).bits == 8:
        amax = jnp.max(jnp.abs(x))
        scale = 2.0 ** jnp.floor(jnp.log2(float(jnp.finfo(dt).max) / amax))
        return (x * scale).astype(dt).astype(jnp.float32) / scale
    return x.astype(dt).astype(jnp.float32)


@partial(jax.jit, static_argnames=("mode", "target", "dtype"))
def topk_scores(tabs, colsums, ids, *, mode, target, dtype="float32"):
    """Reference scores (b, I_target): C^(mode)[ids] weighted by the other
    modes' column sums, against every row of C^(target)."""
    tq = [quantize(t, dtype) for t in tabs]
    w = tq[mode][ids]
    for n, cs in enumerate(colsums):
        if n not in (mode, target):
            w = w * quantize(cs, dtype)[None, :]
    return _dot(w, tq[target].T)


@partial(jax.jit, static_argnames=("dtype",))
def predict(tabs, idx, *, dtype="float32"):
    """Reference x-hat for index tuples (b, N): sum_r prod_n C^(n)[i_n, r]."""
    prod = None
    for n, t in enumerate(tabs):
        c = quantize(t, dtype)[idx[:, n]]
        prod = c if prod is None else prod * c
    return prod.sum(-1)
