"""Layer library: boxed params with logical sharding axes + core NN ops.

Parameters are nested dicts whose leaves are ``Boxed(value, axes)`` — the
``axes`` tuple names one *logical* axis per array dim (MaxText/T5X style).
``unbox``/``axes_tree`` split a boxed tree into (params, PartitionSpec-ready
axes). Logical→mesh mapping lives in ``repro.distributed.sharding``.

Everything is functional: ``init_*`` builds params, ``apply``-style functions
consume them. All inits are tracer-safe (usable under ``jax.eval_shape`` for
the multi-pod dry-run: no real allocation for the full-size configs).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Boxed:
    """A parameter leaf + its logical axis names (aux data, not traced)."""

    value: Any
    axes: tuple[str | None, ...]

    def tree_flatten(self):
        return (self.value,), self.axes

    @classmethod
    def tree_unflatten(cls, axes, children):
        return cls(children[0], axes)


def _is_boxed(x) -> bool:
    return isinstance(x, Boxed)


def unbox(tree):
    """Boxed tree -> plain value tree."""
    return jax.tree.map(lambda b: b.value, tree, is_leaf=_is_boxed)


def axes_tree(tree):
    """Boxed tree -> tree of logical-axes tuples (same structure)."""
    return jax.tree.map(lambda b: b.axes, tree, is_leaf=_is_boxed)


def boxlike(axes, values):
    """Re-box a value tree using an axes tree (inverse of unbox)."""
    return jax.tree.map(
        lambda a, v: Boxed(v, a), axes, values,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x
        ),
    )


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(
    key, shape: Sequence[int], axes: Sequence[str | None],
    scale: float | None = None, dtype=jnp.float32,
) -> Boxed:
    """Truncated-normal fan-in init (LeCun) with logical axes."""
    fan_in = shape[0] if len(shape) > 1 else shape[-1]
    s = scale if scale is not None else 1.0 / jnp.sqrt(fan_in)
    v = jax.random.truncated_normal(key, -2.0, 2.0, tuple(shape), dtype) * s
    return Boxed(v, tuple(axes))


def zeros_init(shape, axes, dtype=jnp.float32) -> Boxed:
    return Boxed(jnp.zeros(tuple(shape), dtype), tuple(axes))


def ones_init(shape, axes, dtype=jnp.float32) -> Boxed:
    return Boxed(jnp.ones(tuple(shape), dtype), tuple(axes))


# ---------------------------------------------------------------------------
# norms / activations
# ---------------------------------------------------------------------------

def init_rmsnorm(dim: int) -> dict:
    return {"scale": ones_init((dim,), ("embed",))}


def rmsnorm(params: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps).astype(x.dtype)
    return y * params["scale"].astype(x.dtype)


def init_layernorm(dim: int) -> dict:
    return {
        "scale": ones_init((dim,), ("embed",)),
        "bias": zeros_init((dim,), ("embed",)),
    }


def layernorm(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (
        y.astype(x.dtype) * params["scale"].astype(x.dtype)
        + params["bias"].astype(x.dtype)
    )


def make_norm(norm_type: str):
    if norm_type == "layernorm":
        return init_layernorm, layernorm
    return init_rmsnorm, rmsnorm


def activation(name: str, x: jax.Array) -> jax.Array:
    if name == "gelu":
        return jax.nn.gelu(x)
    return jax.nn.silu(x)


# ---------------------------------------------------------------------------
# MLP (gated or plain)
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, gated: bool = True,
             axes_ff: str = "mlp") -> dict:
    ks = jax.random.split(key, 3)
    p = {
        "wi": dense_init(ks[0], (d_model, d_ff), ("embed", axes_ff)),
        "wo": dense_init(ks[1], (d_ff, d_model), (axes_ff, "embed")),
    }
    if gated:
        p["wg"] = dense_init(ks[2], (d_model, d_ff), ("embed", axes_ff))
    return p


def mlp(params: dict, x: jax.Array, act: str = "silu") -> jax.Array:
    h = x @ params["wi"]
    if "wg" in params:
        h = activation(act, x @ params["wg"]) * h
    else:
        h = activation(act, h)
    return h @ params["wo"]


# ---------------------------------------------------------------------------
# Tucker-compressed linear (the paper's technique applied to LM weights)
# ---------------------------------------------------------------------------

def init_tucker_linear(key, d_in: int, d_out: int, rank: int,
                       in_axis="embed", out_axis="mlp") -> dict:
    """W ≈ U1 G U2ᵀ with G (rank,rank) — Tucker-2 matrix factorization.

    The Kruskal-core special case of the paper (diagonal G) is recovered by
    ``kruskal=True`` in apply; rank plays the role of R_core.
    """
    ks = jax.random.split(key, 3)
    return {
        "u1": dense_init(ks[0], (d_in, rank), (in_axis, None)),
        "g": dense_init(ks[1], (rank, rank), (None, None),
                        scale=1.0 / jnp.sqrt(rank)),
        "u2": dense_init(ks[2], (d_out, rank), (out_axis, None)),
    }


def tucker_linear(params: dict, x: jax.Array,
                  backend: str | None = None) -> jax.Array:
    """Tucker-2 factorized dense layer, routed through the kernel registry.

    ``backend=None`` means "xla" — deliberately NOT resolved from
    ``$REPRO_KERNEL_BACKEND``: the Pallas ``tucker_matmul`` has no custom
    VJP, so an env-var set for the FastTucker workload must not silently
    reroute (and break ``jax.grad`` of) the LM forward.  Pallas flavors
    are explicit opt-in here.
    """
    from repro.kernels import dispatch

    bk = dispatch.get_backend(backend or "xla")
    shape = x.shape
    y = bk.tucker_matmul(
        x.reshape(-1, shape[-1]), params["u1"], params["g"], params["u2"]
    )
    return y.reshape(*shape[:-1], -1)


# ---------------------------------------------------------------------------
# embeddings / rotary
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d_model: int) -> dict:
    return {
        "embedding": dense_init(key, (vocab, d_model), ("vocab", "embed"),
                                scale=1.0),
    }


def embed(params: dict, tokens: jax.Array) -> jax.Array:
    return params["embedding"][tokens]


def rope_freqs(head_dim: int, theta: float = 10000.0) -> jax.Array:
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0
               ) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                        # (D/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (...,S,D/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)
