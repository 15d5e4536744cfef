"""Pallas TPU kernel: flash-attention forward (online softmax in VMEM).

The LM stack's hot spot (used by every assigned attention architecture).
Grid: (batch·heads, Sq/bq, Sk/bk) with the KV dimension innermost — the
(bq, D) accumulator plus (bq,) running max/denominator live in VMEM
scratch and are revisited across KV steps, so the (Sq, Sk) score matrix
never exists. Causality is an additive position-difference bias (no
`pred` mask broadcasts, cf. EXPERIMENTS §Perf iteration 4).

VMEM per step ≈ bq·D + bk·D + bq·bk floats: for bq=bk=512, D=128 that is
~0.6 MB — far under budget, so tiles can grow until the MXU is saturated.
The pure-jnp oracle is `ref.flash_attention_ref`; the train-path custom-VJP
wrapper lives in `repro.models.flash` (this kernel is the TPU lowering of
its forward pass).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
            *, causal: bool, block_q: int, block_k: int, k_steps: int,
            scale: float, kv_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]                                   # (bq, D)
    k = k_ref[0]                                   # (bk, D)
    v = v_ref[0]                                   # (bk, D)
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                      # (bq, bk)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    # padded-key guard (k_pos ≥ kv_len ⇒ −inf), additive — no pred masks
    logits = logits + jnp.minimum(
        (kv_len - 1 - k_pos).astype(jnp.float32), 0.0) * 1e12
    if causal:
        logits = logits + jnp.minimum(
            (q_pos - k_pos).astype(jnp.float32), 0.0) * 1e12

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, logits.max(-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == k_steps - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention_fwd(
    q: jax.Array,   # (BH, Sq, D) — batch·heads flattened
    k: jax.Array,   # (BH, Sk, D)
    v: jax.Array,   # (BH, Sk, D)
    *,
    causal: bool = True,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool,
) -> jax.Array:
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    bq, bk = min(block_q, Sq), min(block_k, Sk)

    def pad(x, blk):
        r = x.shape[1] % blk
        if r:
            x = jnp.pad(x, ((0, 0), (0, blk - r), (0, 0)))
        return x

    qp, kp, vp = pad(q, bq), pad(k, bk), pad(v, bk)
    k_steps = kp.shape[1] // bk
    grid = (BH, qp.shape[1] // bq, k_steps)
    out = pl.pallas_call(
        functools.partial(
            _kernel, causal=causal, block_q=bq, block_k=bk,
            k_steps=k_steps, scale=1.0 / (D ** 0.5), kv_len=Sk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :Sq]
