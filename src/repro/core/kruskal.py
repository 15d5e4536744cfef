"""Kruskal (CP) core-tensor machinery + Theorem 1/2 contractions.

The paper approximates the Tucker core ``G ∈ R^{J_1×…×J_N}`` by a rank-R_core
Kruskal product of ``B^(n) ∈ R^{J_n × R_core}`` (Eq. 9). Theorems 1 and 2 let
every Kronecker-structured contraction factor into mode-wise small matmuls.

All functions take ``core_factors`` as a tuple of ``(J_n, R)`` arrays and
per-sample gathered factor rows as a tuple of ``(B, J_n)`` arrays (modes may
have different J_n — we keep tuples, not stacked arrays, at this reference
level; the Pallas kernel uses a padded stacked layout).
"""
from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp


def kruskal_to_core(core_factors: Sequence[jax.Array]) -> jax.Array:
    """Materialize Ĝ = Σ_r b_r^(1) ∘ … ∘ b_r^(N)  (tests / tiny shapes)."""
    N = len(core_factors)
    R = core_factors[0].shape[1]
    letters = "abcdefghijklmnop"[:N]
    operands = []
    subs = []
    for n, b in enumerate(core_factors):
        operands.append(b)
        subs.append(f"{letters[n]}r")
    expr = ",".join(subs) + "->" + letters
    return jnp.einsum(expr, *operands)


def mode_dots(
    rows: Sequence[jax.Array], core_factors: Sequence[jax.Array],
    accum_dtype=None,
) -> jax.Array:
    """c_r^(n) = ⟨a_{i_n}, b_{:,r}^(n)⟩ for a batch.  -> (N, B, R).

    This is the paper's line-6/23 hot loop (warp-shuffle dot products),
    expressed as N batched matmuls (B,J_n)·(J_n,R).  ``accum_dtype``
    sets ``preferred_element_type`` so bf16 storage rows/factors still
    contract with f32 MXU accumulation (a no-op for f32 inputs).
    """
    pref = None if accum_dtype is None else jnp.dtype(accum_dtype)
    return jnp.stack(
        [jnp.matmul(r, b, preferred_element_type=pref)
         for r, b in zip(rows, core_factors)], axis=0)


@jax.named_scope("exclusive_products")
def exclusive_products(c: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Given c: (N, B, R), return (full_prod (B,R), excl (N,B,R)).

    excl[n] = Π_{k≠n} c[k], computed division-free with prefix/suffix
    products (stable when some c ≈ 0).

    Its ops carry the scope ``exclusive_products`` inside the caller's
    (``repro.step.grad``, ``repro.eval.chunk``); the name has no
    ``repro.`` prefix, so the caller's scope still owns the device time.
    """
    N = c.shape[0]
    ones = jnp.ones_like(c[0])
    # prefix[n] = Π_{k<n} c[k]; suffix[n] = Π_{k>n} c[k]
    prefix = jnp.concatenate(
        [ones[None], jnp.cumprod(c[:-1], axis=0)], axis=0
    )
    suffix = jnp.concatenate(
        [jnp.cumprod(c[:0:-1], axis=0)[::-1], ones[None]], axis=0
    )
    excl = prefix * suffix
    full = excl[0] * c[0]
    return full, excl


def predict_from_rows(
    rows: Sequence[jax.Array], core_factors: Sequence[jax.Array]
) -> jax.Array:
    """x̂ = Σ_r Π_n c_r^(n)   (Theorem-1 factored prediction).  -> (B,)"""
    c = mode_dots(rows, core_factors)
    full, _ = exclusive_products(c)
    return jnp.sum(full, axis=-1)


def mode_products(
    factors: Sequence[jax.Array], core_factors: Sequence[jax.Array],
    accum_dtype=None,
) -> tuple[jax.Array, ...]:
    """C^(n) = A^(n) B^(n) ∈ R^{I_n × R} — ALL mode dots, precomputed.

    ``C^(n)[i, r]`` is exactly the Theorem-1 coefficient ``c_r^(n)`` for row
    ``i``, so ``x̂(i_1..i_N) = Σ_r Π_n C^(n)[i_n, r]`` — the cheap per-query
    path the serving engine caches (``repro.serve``): one gather + product
    per query instead of J_n-length dot products.  ``accum_dtype`` keeps
    the contraction in f32 even for bf16-stored factors.
    """
    pref = None if accum_dtype is None else jnp.dtype(accum_dtype)
    return tuple(
        jnp.matmul(a, b, preferred_element_type=pref)
        for a, b in zip(factors, core_factors))


def dense_reconstruct(
    factors: Sequence[jax.Array], core_factors: Sequence[jax.Array]
) -> jax.Array:
    """X̂ = Ĝ ×_1 A^(1) … ×_N A^(N) materialized (tiny tensors / tests only).

    The O(Π I_n) oracle the factored serving path is checked against;
    deliberately routed through the MATERIALIZED core ``kruskal_to_core``
    (not ``mode_products``) so the test oracle shares no code with the
    engine's cached path.
    """
    G = kruskal_to_core(core_factors)                # (J_1, …, J_N)
    N = len(factors)
    core_l = "abcdefghijklmnop"[:N]
    out_l = "ABCDEFGHIJKLMNOP"[:N]
    expr = (core_l + ","
            + ",".join(f"{out_l[n]}{core_l[n]}" for n in range(N))
            + "->" + out_l)
    return jnp.einsum(expr, G, *factors)


# ---------------------------------------------------------------------------
# Theorem 1 / Theorem 2 reference forms (used by property tests)
# ---------------------------------------------------------------------------

def kron_vec(vectors: Sequence[jax.Array]) -> jax.Array:
    """x^(N) ⊗ … ⊗ x^(1) for a list ordered [x^(1), …, x^(N)] (paper order)."""
    out = vectors[-1]
    for v in reversed(vectors[:-1]):
        out = jnp.kron(out, v)
    return out


def kron_mat(mats: Sequence[jax.Array]) -> jax.Array:
    """Y^(N) ⊗ … ⊗ Y^(1) for a list ordered [Y^(1), …, Y^(N)]."""
    out = mats[-1]
    for m in reversed(mats[:-1]):
        out = jnp.kron(out, m)
    return out


def theorem1_lhs(xs: Sequence[jax.Array], ys: Sequence[jax.Array]) -> jax.Array:
    """(⊗ x)(⊗ y)^T — the exponential-cost form."""
    return kron_vec(xs) @ kron_vec(ys)


def theorem1_rhs(xs: Sequence[jax.Array], ys: Sequence[jax.Array]) -> jax.Array:
    """Π_n x^(n) y^(n)T — the linear-cost form."""
    out = jnp.asarray(1.0, dtype=xs[0].dtype)
    for x, y in zip(xs, ys):
        out = out * (x @ y)
    return out


def theorem2_lhs(xs: Sequence[jax.Array], Ys: Sequence[jax.Array]) -> jax.Array:
    """(⊗ x)(⊗ Y)^T — exponential form. Ys[n]: (J_n, I_n)."""
    return kron_vec(xs) @ kron_mat(Ys).T


def theorem2_rhs(xs: Sequence[jax.Array], Ys: Sequence[jax.Array]) -> jax.Array:
    """⊗_n (x^(n) Y^(n)T) — linear form (ordered to match kron_vec)."""
    return kron_vec([x @ Y.T for x, Y in zip(xs, Ys)])
