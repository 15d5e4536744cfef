"""Seeded data for the benchmark, made on the device in one jitted call.

``ratings`` follows ``repro.data.synthetic.ratings_tensor``: a planted
Kruskal-core Tucker signal (factors and core factors uniform on [0, 2s),
rank ``planted_rank``) plus Gaussian noise, squashed to
[min_value, max_value] by the 1% and 99% quantiles.  Three things differ,
all stated in each configuration's ``assumed``:

* the tensor is drawn from the configuration's ``data.seed``, not from
  the run's seed: the held-out RMSE after a given number of steps moves
  with the tensor (the test set alone carries an error of about
  RMSE / sqrt(2 n_test)) far more than with the order of the samples,
  and on the flat part of the curve that moved the time to a target by
  a third from seed to seed (PERF.md §6);

* indices are drawn uniformly per mode (32 random bits mod I_n) and
  duplicates are kept (the expected count is nnz^2 / 2S for S cells),
  where the host generator drew distinct cells with ``np.unique``;
* the quantiles are read from the first 2^20 training values (already in
  random order), not from all of them, and the test set uses the
  training set's quantiles.

``serving_factors`` draws signed factors whose Theorem-1 coefficients
``c = a . b`` have unit variance, so top-k scores spread out instead of
crowding into near-ties.

Nothing here imports the program: the plain reference reads this data
too, and it must not take anything the program has made.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

QUANTILE_SAMPLE = 1 << 20
SIGNAL_CHUNK = 1 << 21


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed (more than 32 bits too)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def planted_scale(rank: int, order: int) -> float:
    """Half-range of the planted uniform draws (``planted_tensor``'s s)."""
    return (1.0 / rank) ** (0.5 / order) / rank ** 0.5


def _signal(factors, core, idx):
    """x(i) = sum_r prod_n (A_n[i_n] B_n)_r for a chunk of index tuples."""
    prod = None
    for n, (a, b) in enumerate(zip(factors, core)):
        c = jnp.matmul(a[idx[:, n]], b, precision="highest")
        prod = c if prod is None else prod * c
    return prod.sum(-1)


def _signal_all(factors, core, idx):
    """The planted signal for every row of ``idx``, chunk by chunk.

    The last chunk is clamped to end at the last row; rows it shares with
    the chunk before are computed twice, to the same values.
    """
    nnz = idx.shape[0]
    size = min(SIGNAL_CHUNK, nnz)
    steps = -(-nnz // size)

    def body(i, out):
        start = jnp.minimum(i * size, nnz - size)
        chunk = jax.lax.dynamic_slice_in_dim(idx, start, size)
        return jax.lax.dynamic_update_slice_in_dim(
            out, _signal(factors, core, chunk), start, 0)

    return jax.lax.fori_loop(0, steps, body, jnp.zeros((nnz,), jnp.float32))


def quantile(v, q: float, iters: int = 48):
    """The empirical q-quantile of ``v`` (least x with at least q·n values
    <= x), found by bisection on the value: a sort of this size takes the
    TPU compiler minutes, the bisection a fraction of a second."""
    want = q * v.shape[0]

    def body(_, bounds):
        lo, hi = bounds
        mid = 0.5 * (lo + hi)
        enough = jnp.sum(v <= mid) >= want
        return jnp.where(enough, lo, mid), jnp.where(enough, mid, hi)

    return jax.lax.fori_loop(0, iters, body, (jnp.min(v), jnp.max(v)))[1]


@partial(jax.jit, static_argnames=("dims", "train_nnz", "test_nnz", "rank",
                                   "noise", "min_value", "max_value"))
def _ratings(key, *, dims, train_nnz, test_nnz, rank, noise, min_value,
             max_value):
    N = len(dims)
    k_fac, k_core, k_train, k_test, k_ntrain, k_ntest = jax.random.split(
        key, 6)
    s = planted_scale(rank, N)
    factors = tuple(
        jax.random.uniform(k, (d, rank), jnp.float32, 0.0, 2 * s)
        for k, d in zip(jax.random.split(k_fac, N), dims))
    core = tuple(
        jax.random.uniform(k, (rank, rank), jnp.float32, 0.0, 2 * s)
        for k in jax.random.split(k_core, N))

    def draw(k_idx, k_noise, nnz):
        # uniform ids as 32 random bits mod I_n (bias I_n / 2^32 < 3e-4)
        idx = jnp.stack(
            [(jax.random.bits(k, (nnz,), jnp.uint32) % jnp.uint32(d)
              ).astype(jnp.int32)
             for k, d in zip(jax.random.split(k_idx, N), dims)], axis=1)
        raw = _signal_all(factors, core, idx)
        return idx, raw + noise * jax.random.normal(k_noise, (nnz,))

    train_idx, train_raw = draw(k_train, k_ntrain, train_nnz)
    test_idx, test_raw = draw(k_test, k_ntest, test_nnz)
    sample = train_raw[:QUANTILE_SAMPLE]
    lo, hi = quantile(sample, 0.01), quantile(sample, 0.99)
    span = jnp.maximum(hi - lo, 1e-6)

    def squash(v):
        return (jnp.clip((v - lo) / span, 0.0, 1.0) * (max_value - min_value)
                + min_value)

    return train_idx, squash(train_raw), test_idx, squash(test_raw)


def ratings(cfg: dict):
    """(train_indices, train_values, test_indices, test_values) on device.

    ``cfg`` is a configuration file's dict: ``dims``, ``train_nnz``,
    ``test_nnz`` and its ``data`` block (``seed``, ``planted_rank``,
    ``noise``, ``min_value``, ``max_value``).  The tensor comes from the
    configuration's ``data.seed`` alone: every run of a configuration
    trains on and is evaluated against the same tensor.
    """
    data = cfg["data"]
    return _ratings(
        seed_key(int(data["seed"])), dims=tuple(cfg["dims"]), train_nnz=int(cfg["train_nnz"]),
        test_nnz=int(cfg["test_nnz"]), rank=int(data["planted_rank"]),
        noise=float(data["noise"]), min_value=float(data["min_value"]),
        max_value=float(data["max_value"]))


@partial(jax.jit, static_argnames=("dims", "ranks", "core_rank"))
def _serving_factors(key, *, dims, ranks, core_rank):
    N = len(dims)
    keys = jax.random.split(key, 2 * N)
    factors = tuple(
        jax.random.normal(keys[n], (dims[n], ranks[n]), jnp.float32)
        * ranks[n] ** -0.25 for n in range(N))
    core = tuple(
        jax.random.normal(keys[N + n], (ranks[n], core_rank), jnp.float32)
        * ranks[n] ** -0.25 for n in range(N))
    return factors, core


def serving_factors(key: jax.Array, cfg: dict):
    """(factors A^(n) (I_n, J_n), core factors B^(n) (J_n, R)) on device."""
    return _serving_factors(key, dims=tuple(cfg["dims"]),
                            ranks=tuple(cfg["ranks"]),
                            core_rank=int(cfg["core_rank"]))
