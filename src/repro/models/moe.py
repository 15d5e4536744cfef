"""Mixture-of-Experts: shared + routed experts, capacity-based dispatch.

Dispatch is the position-in-expert/cumsum scheme (GShard/Switch family) with
gather/scatter index matrices instead of the (T, E, C) one-hot einsum — the
one-hot dispatch tensor for qwen3-moe (T=32k, E=128, C=2.5k) would be 10^10
elements; the index-matrix form is (E, C) int32.

Expert weights carry the "experts" logical axis → sharded over the `model`
mesh axis (expert parallelism). Router runs in fp32 for stability.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import activation, dense_init, init_mlp, mlp


def init_moe(key, cfg) -> dict:
    d = cfg.d_model
    E, dff = cfg.num_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, E), ("embed", None), scale=0.02),
        "wi": dense_init(ks[1], (E, d, dff), ("experts", "embed", "mlp")),
        "wg": dense_init(ks[2], (E, d, dff), ("experts", "embed", "mlp")),
        "wo": dense_init(ks[3], (E, dff, d), ("experts", "mlp", "embed")),
    }
    if cfg.num_shared_experts:
        p["shared"] = init_mlp(
            ks[4], d, cfg.moe_d_ff * cfg.num_shared_experts, gated=True
        )
    return p


def _dispatch_indices(expert_ids: jax.Array, num_experts: int, capacity: int):
    """expert_ids: (T, k) → (index_mat (E,C) int32 into T*k, keep (T,k) bool,
    slot (T,k) int32). Position-in-expert via running per-expert counters."""
    T, K = expert_ids.shape
    flat = expert_ids.reshape(-1)                          # (T*k,) in arrival order
    onehot = jax.nn.one_hot(flat, num_experts, dtype=jnp.int32)  # (T*k, E)
    pos = jnp.cumsum(onehot, axis=0) - 1                   # occurrence rank
    slot = jnp.take_along_axis(pos, flat[:, None], axis=1)[:, 0]  # (T*k,)
    keep = slot < capacity
    # scatter arrival index into (E, C)
    index_mat = jnp.full((num_experts, capacity), T * K, jnp.int32)
    index_mat = index_mat.at[
        jnp.where(keep, flat, num_experts - 1),
        jnp.where(keep, slot, capacity - 1),
    ].max(jnp.where(keep, jnp.arange(T * K, dtype=jnp.int32), -1))
    index_mat = jnp.where(index_mat < 0, T * K, index_mat)
    return index_mat, keep.reshape(T, K), slot.reshape(T, K)


def moe_ffn(params: dict, cfg, x: jax.Array) -> jax.Array:
    """x: (B, S, d) → (B, S, d)."""
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    E, K = cfg.num_experts, cfg.top_k
    capacity = int(T * K / E * cfg.capacity_factor) + 1

    logits = (xt.astype(jnp.float32) @ params["router"].astype(jnp.float32))
    if cfg.router_softmax_then_topk:
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, K)
    else:
        top_logits, expert_ids = jax.lax.top_k(logits, K)
        gate_vals = jax.nn.softmax(top_logits, axis=-1)
    if cfg.norm_topk_prob:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)

    index_mat, keep, _ = _dispatch_indices(expert_ids, E, capacity)

    # gather tokens into expert buffers: (E, C, d); out-of-range → zeros
    xt_pad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], 0)
    token_of = index_mat // K                              # (E, C) token ids
    token_of = jnp.where(index_mat >= T * K, T, token_of)
    expert_in = xt_pad[token_of]                           # (E, C, d)

    h = jnp.einsum("ecd,edf->ecf", expert_in, params["wi"])
    g = jnp.einsum("ecd,edf->ecf", expert_in, params["wg"])
    h = activation(cfg.activation, g) * h
    expert_out = jnp.einsum("ecf,efd->ecd", h, params["wo"])  # (E, C, d)

    # combine: scatter expert outputs back, weighted by gates
    flat_out = jnp.zeros((T * K + 1, d), expert_out.dtype)
    flat_out = flat_out.at[index_mat.reshape(-1)].set(
        expert_out.reshape(-1, d)
    )[: T * K]
    flat_out = flat_out.reshape(T, K, d)
    gates = (gate_vals * keep).astype(flat_out.dtype)      # dropped → 0
    y = jnp.einsum("tkd,tk->td", flat_out, gates)

    if "shared" in params:
        y = y + mlp(params["shared"], xt, cfg.activation)
    return y.reshape(B, S, d)


# ---------------------------------------------------------------------------
# expert-parallel shard_map island (perf variant)
# ---------------------------------------------------------------------------

def moe_ffn_sharded(params: dict, cfg, x: jax.Array, mesh) -> jax.Array:
    """Expert-parallel MoE with LOCAL dispatch + one psum (beyond-paper).

    Under pure GSPMD the index-based dispatch's gather/scatter across the
    sharded token dim lowers to full-size all-reduces (~1.3 TB wire/step
    for deepseek-v2-lite train). Manual layout kills that:

      tokens sharded over (pod, data) · experts sharded over `model`.
      Device (d, m): routes ITS tokens to ITS experts entirely locally
      (per-shard capacity ⇒ local cumsum, local gather, local scatter),
      then ONE psum over `model` combines expert contributions — the only
      collective, of activation size.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.top_k
    baxes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    bspec = baxes if len(baxes) > 1 else baxes[0]

    def body(router, wi, wg, wo, shared, xb):
        # xb: (B_loc, S, d); wi/wg/wo: (E_loc, ...)
        me = jax.lax.axis_index("model")
        E_loc = wi.shape[0]
        Bl = xb.shape[0]
        T = Bl * S
        xt = xb.reshape(T, d)
        cap = int(T * K / E * cfg.capacity_factor) + 1

        logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)
        if cfg.router_softmax_then_topk:
            probs = jax.nn.softmax(logits, axis=-1)
            gate_vals, expert_ids = jax.lax.top_k(probs, K)
        else:
            top_logits, expert_ids = jax.lax.top_k(logits, K)
            gate_vals = jax.nn.softmax(top_logits, axis=-1)
        if cfg.norm_topk_prob:
            gate_vals = gate_vals / jnp.maximum(
                gate_vals.sum(-1, keepdims=True), 1e-9)

        # local ids for MY experts; others → E_loc (dropped)
        flat = expert_ids.reshape(-1)
        local = flat - me * E_loc
        mine = (local >= 0) & (local < E_loc)
        local = jnp.where(mine, local, E_loc)
        onehot = jax.nn.one_hot(local, E_loc + 1, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - 1
        slot = jnp.take_along_axis(pos, local[:, None], axis=1)[:, 0]
        keep = mine & (slot < cap)
        index_mat = jnp.full((E_loc + 1, cap), T * K, jnp.int32)
        index_mat = index_mat.at[
            jnp.where(keep, local, E_loc),
            jnp.where(keep, slot, cap - 1),
        ].max(jnp.where(keep, jnp.arange(T * K, dtype=jnp.int32), -1))
        index_mat = jnp.where(index_mat < 0, T * K, index_mat)
        index_mat = index_mat[:E_loc]

        xt_pad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], 0)
        token_of = jnp.where(index_mat >= T * K, T, index_mat // K)
        expert_in = xt_pad[token_of]                       # (E_loc, cap, d)

        h = jnp.einsum("ecd,edf->ecf", expert_in, wi)
        g = jnp.einsum("ecd,edf->ecf", expert_in, wg)
        h = activation(cfg.activation, g) * h
        expert_out = jnp.einsum("ecf,efd->ecd", h, wo)

        flat_out = jnp.zeros((T * K + 1, d), expert_out.dtype)
        flat_out = flat_out.at[index_mat.reshape(-1)].set(
            expert_out.reshape(-1, d))[: T * K].reshape(T, K, d)
        gates = (gate_vals * keep.reshape(T, K)).astype(flat_out.dtype)
        y = jnp.einsum("tkd,tk->td", flat_out, gates)

        if shared is not None:
            # shared expert FFN hidden sharded over model → same psum
            hs = xt @ shared["wi"]
            gs = activation(cfg.activation, xt @ shared["wg"])
            y = y + (gs * hs) @ shared["wo"]
        y = jax.lax.psum(y, "model")
        return y.reshape(Bl, S, d)

    shared = params.get("shared")
    shared_specs = None
    if shared is not None:
        shared_specs = {"wi": P(None, "model"), "wg": P(None, "model"),
                        "wo": P("model", None)}
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(), P("model", None, None), P("model", None, None),
            P("model", None, None), shared_specs,
            P(bspec, None, None),
        ),
        out_specs=P(bspec, None, None),
        check_vma=False,
    )
    return fn(params["router"], params["wi"], params["wg"], params["wo"],
              shared, x)


def load_balance_loss(logits: jax.Array, expert_ids: jax.Array, E: int):
    """Aux loss (Switch): E · Σ_e f_e · p_e  (not used by default configs)."""
    probs = jax.nn.softmax(logits, -1)
    f = jnp.mean(
        jax.nn.one_hot(expert_ids[..., 0], E, dtype=probs.dtype), axis=0
    )
    p = jnp.mean(probs, axis=0)
    return E * jnp.sum(f * p)
