"""Distributed STD strategies + sharding rules (multi-device via subprocess)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from helpers import run_with_devices
from repro.distributed.sharding import (
    CACHE_AXES, RULES_FSDP_TP, RULES_TP, cache_axes_tree, spec_for,
)


class FakeMesh:
    axis_names = ("data", "model")
    devices = np.zeros((4, 2))


def test_spec_for_divisibility():
    mesh = FakeMesh()
    # mlp divisible by model(2) → sharded
    assert spec_for(("embed", "mlp"), (64, 128), mesh, RULES_TP) \
        == P(None, "model")
    # kv_heads=3 not divisible by 2 → replicated
    assert spec_for(("embed", "kv_heads", None), (64, 3, 16), mesh,
                    RULES_TP) == P()
    # batch uses data axis
    assert spec_for(("batch", None), (8, 5), mesh, RULES_TP) == P("data")


def test_spec_for_axis_uniqueness():
    mesh = FakeMesh()
    # both dims want "model": only the first gets it
    sp = spec_for(("mlp", "vocab"), (128, 128), mesh, RULES_TP)
    assert sp == P("model")  # second entry trimmed (None tail)


def test_spec_for_fsdp_adds_embed_sharding():
    mesh = FakeMesh()
    sp = spec_for(("embed", "mlp"), (64, 128), mesh, RULES_FSDP_TP)
    assert sp == P("data", "model")


def test_cache_axes_tree_structure():
    cache = [
        {"attn": {"k": jnp.zeros((2, 8, 4, 16)),
                  "v": jnp.zeros((2, 8, 4, 16))}},
        {"ssm": {"conv": jnp.zeros((2, 3, 32)),
                 "ssm": jnp.zeros((2, 4, 8, 16))}},
    ]
    axes = cache_axes_tree(cache)
    assert axes[0]["attn"]["k"] == CACHE_AXES["k"]
    assert axes[1]["ssm"]["conv"] == CACHE_AXES["conv"]
    # stacked (scanned) caches get a leading None
    stacked = [{"attn": {"k": jnp.zeros((5, 2, 8, 4, 16))}}]
    axes2 = cache_axes_tree(stacked)
    assert axes2[0]["attn"]["k"] == (None,) + CACHE_AXES["k"]


@pytest.mark.slow
def test_sync_mode_matches_single_device():
    """4-device sync step == single-device step on the union batch."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import FastTuckerConfig, init_state
        from repro.core import fasttucker as ft
        from repro.data.synthetic import planted_tensor
        from repro.distributed import strategy
        from repro.launch.mesh import make_host_mesh

        dims = (64, 48, 32)
        t = planted_tensor(dims, 20000, seed=0)
        cfg = FastTuckerConfig(dims=dims, ranks=(4,4,4), core_rank=4,
                               batch_size=128)
        mesh = make_host_mesh()
        n = mesh.devices.size
        assert n == 4
        idx_sh, val_sh = strategy.shard_nonzeros(t, n)
        step = strategy.make_sync_step(cfg, mesh)
        state = init_state(jax.random.PRNGKey(0), cfg)
        params = state.params
        ef = strategy.init_error_feedback(params)
        with mesh:
            p1, _ = step(params, jnp.asarray(0), jax.random.PRNGKey(1),
                         idx_sh, val_sh, ef)

        # reference: same per-device samples, averaged grads, same lr
        ref_fac = [np.asarray(f, np.float64) for f in params.factors]
        ref_core = [np.asarray(b, np.float64) for b in params.core_factors]
        dense_sum = [np.zeros_like(f) for f in ref_fac]
        core_sum = [np.zeros_like(b) for b in ref_core]
        for d in range(n):
            key = jax.random.fold_in(jax.random.PRNGKey(1), d)
            pick = jax.random.randint(key, (cfg.batch_size,), 0,
                                      val_sh.shape[1])
            idx = idx_sh[d][pick]; val = val_sh[d][pick]
            g = ft.batch_gradients(params, idx, val, cfg.lambda_a,
                                   cfg.lambda_b)
            dd = ft.scatter_row_grads(params.factors, idx, g.row_grads)
            for i in range(3):
                dense_sum[i] += np.asarray(dd[i], np.float64)
                core_sum[i] += np.asarray(g.core_grads[i], np.float64)
        lr_a = float(ft.dynamic_lr(cfg.alpha_a, cfg.beta_a, jnp.asarray(0)))
        lr_b = float(ft.dynamic_lr(cfg.alpha_b, cfg.beta_b, jnp.asarray(0)))
        for i in range(3):
            want = ref_fac[i] - (lr_a / n) * dense_sum[i]
            np.testing.assert_allclose(np.asarray(p1.factors[i]), want,
                                       rtol=2e-4, atol=1e-6)
            wantc = ref_core[i] - (lr_b / n) * core_sum[i]
            np.testing.assert_allclose(np.asarray(p1.core_factors[i]),
                                       wantc, rtol=2e-4, atol=1e-6)
        print("sync == reference")
    """)


@pytest.mark.slow
def test_strata_mode_converges_multidevice():
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import FastTuckerConfig, init_state, rmse_mae
        from repro.core import fasttucker as ft
        from repro.data.synthetic import planted_tensor
        from repro.distributed import strategy
        from repro.launch.mesh import make_host_mesh

        dims = (120, 100, 80)
        t = planted_tensor(dims, 40000, noise=0.05, seed=1)
        train_t, test_t = t.split(0.1)
        cfg = FastTuckerConfig(dims=dims, ranks=(4,4,4), core_rank=4,
                               batch_size=512)
        mesh = make_host_mesh()
        plan = strategy.StrataPlan.build(train_t, mesh.devices.size)
        state = init_state(jax.random.PRNGKey(0), cfg)
        params = strategy.pad_factors_for_strata(state.params, plan)
        step = strategy.make_strata_step(cfg, mesh, plan)
        n_strata = plan.buckets["indices"].shape[0]
        rng = np.random.default_rng(0)
        key = jax.random.PRNGKey(2)
        r0 = None
        with mesh:
            for i in range(120):
                key, sub = jax.random.split(key)
                s = int(rng.integers(n_strata))
                params = step(params, jnp.asarray(i), sub, s)
            trimmed = ft.FastTuckerParams(
                tuple(f[: dims[n]] for n, f in enumerate(params.factors)),
                params.core_factors)
            r, m = rmse_mae(trimmed, test_t, ft.predict)
        print("strata rmse", float(r))
        assert float(r) < 0.5
    """)


@pytest.mark.slow
def test_compressed_sync_converges():
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import FastTuckerConfig, init_state, rmse_mae
        from repro.core import fasttucker as ft
        from repro.data.synthetic import planted_tensor
        from repro.distributed import strategy
        from repro.launch.mesh import make_host_mesh

        dims = (120, 100, 80)
        t = planted_tensor(dims, 40000, noise=0.05, seed=2)
        train_t, test_t = t.split(0.1)
        cfg = FastTuckerConfig(dims=dims, ranks=(4,4,4), core_rank=4,
                               batch_size=512)
        mesh = make_host_mesh()
        idx_sh, val_sh = strategy.shard_nonzeros(train_t, mesh.devices.size)
        step = strategy.make_sync_step(cfg, mesh, compress=True)
        state = init_state(jax.random.PRNGKey(0), cfg)
        params, ef = state.params, strategy.init_error_feedback(
            state.params)
        key = jax.random.PRNGKey(3)
        with mesh:
            for i in range(150):
                key, sub = jax.random.split(key)
                params, ef = step(params, jnp.asarray(i), sub, idx_sh,
                                  val_sh, ef)
            r, m = rmse_mae(params, test_t, ft.predict)
        print("compressed-sync rmse", float(r))
        assert float(r) < 0.6
    """)


@pytest.mark.slow
def test_sharded_moe_matches_dense_dispatch():
    """Expert-parallel shard_map MoE == single-device dispatch (high cap)."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import get_config
        from repro.models import moe as moe_mod
        from repro.models.layers import unbox
        from repro.models.moe import init_moe

        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        for arch in ("qwen3_moe_30b_a3b", "deepseek_v2_lite_16b"):
            cfg = dataclasses.replace(get_config(arch, reduced=True),
                                      capacity_factor=8.0)
            p = unbox(init_moe(jax.random.PRNGKey(0), cfg))
            x = jax.random.normal(jax.random.PRNGKey(1),
                                  (4, 16, cfg.d_model)) * 0.5
            with mesh:
                y_ref = moe_mod.moe_ffn(p, cfg, x)
                y_sh = jax.jit(lambda p, x: moe_mod.moe_ffn_sharded(
                    p, cfg, x, mesh))(p, x)
            np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_sh),
                                       rtol=2e-4, atol=2e-4)
            print(arch, "ok")
    """, num_devices=8)
