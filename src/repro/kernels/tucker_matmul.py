"""Pallas TPU kernel: fused Tucker-2 factorized linear  y = ((x U1) G) U2ᵀ.

The paper's stated future application is DNN weight compression; our LM
integration replaces a dense (K, Nout) weight with U1 (K,R1), G (R1,R2),
U2 (Nout,R2). Computing through the factorization costs
``M·R1·(K + R2) + M·R2·Nout`` FLOPs vs ``M·K·Nout`` dense — a win whenever
R/K is below ~0.5.

Fusion rationale: the intermediates (x U1) and ((x U1) G) are (M, R) with
R ≤ 512 — they live entirely in VMEM across the K-reduction, so the kernel
streams x and U2 tiles from HBM exactly once (single-pass, no HBM round-trip
for intermediates — the thing XLA cannot always guarantee across three dots).

Grid: (M/MT, N/NT, K/KT); K innermost so the (MT,R2) accumulator is revisited.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, u1_ref, g_ref, u2_ref, y_ref, acc_ref, *, k_steps: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # accumulate t = x U1 over K tiles, kept in f32 VMEM scratch
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], u1_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == k_steps - 1)
    def _finish():
        t = jax.lax.dot_general(
            acc_ref[...], g_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        y_ref[...] = jax.lax.dot_general(
            t, u2_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(y_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret"),
)
def tucker_matmul(
    x: jax.Array,   # (M, K)
    u1: jax.Array,  # (K, R1)
    g: jax.Array,   # (R1, R2)
    u2: jax.Array,  # (N, R2)
    *,
    block_m: int = 256,
    block_n: int = 512,
    block_k: int = 512,
    interpret: bool,
) -> jax.Array:
    M, K = x.shape
    R1 = u1.shape[1]
    R2 = g.shape[1]
    N = u2.shape[0]

    mt, nt, kt = min(block_m, M), min(block_n, N), min(block_k, K)

    def pad_to(a, axis, mult):
        size = a.shape[axis]
        rem = size % mult
        if rem:
            widths = [(0, 0)] * a.ndim
            widths[axis] = (0, mult - rem)
            a = jnp.pad(a, widths)
        return a

    xp = pad_to(pad_to(x, 0, mt), 1, kt)
    u1p = pad_to(u1, 0, kt)
    u2p = pad_to(u2, 0, nt)
    Mp, Kp = xp.shape
    Np = u2p.shape[0]
    k_steps = Kp // kt
    grid = (Mp // mt, Np // nt, k_steps)

    y = pl.pallas_call(
        functools.partial(_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((mt, kt), lambda m, n, k: (m, k)),
            pl.BlockSpec((kt, R1), lambda m, n, k: (k, 0)),
            pl.BlockSpec((R1, R2), lambda m, n, k: (0, 0)),
            pl.BlockSpec((nt, R2), lambda m, n, k: (n, 0)),
        ],
        out_specs=pl.BlockSpec((mt, nt), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((mt, R1), jnp.float32)],
        interpret=interpret,
    )(xp, u1p, g, u2p)
    return y[:M, :N]
