"""FastTucker core: gradients vs autodiff, convergence, baselines."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    FastTuckerConfig, init_params, init_state, rmse_mae, sgd_step, train,
)
from repro.core import als, ccd, cutucker as cu, fasttucker as ft
from repro.data.synthetic import planted_tensor

DIMS = (60, 50, 40)


@pytest.fixture(scope="module")
def tensor():
    return planted_tensor(DIMS, 8000, rank=4, core_rank=4, noise=0.02,
                          seed=7)


@pytest.fixture(scope="module")
def cfg():
    return FastTuckerConfig(dims=DIMS, ranks=(4, 4, 4), core_rank=4,
                            batch_size=256)


@pytest.mark.parametrize("row_mean", [True, False])
def test_grads_match_autodiff(tensor, cfg, row_mean):
    params = init_params(jax.random.PRNGKey(0), cfg)
    idx, val = tensor.indices[:256], tensor.values[:256]
    B = 256
    loss = lambda p: ft.sampled_loss(p, idx, val, 0.01, 0.02,
                                     row_mean=row_mean)
    g_auto = jax.grad(loss)(params)
    g_hand = ft.batch_gradients(params, idx, val, 0.01, 0.02,
                                row_mean=row_mean)
    dense = ft.scatter_row_grads(params.factors, idx, g_hand.row_grads)
    core_scale = 1.0 if row_mean else B  # see sampled_loss docstring
    for n in range(3):
        np.testing.assert_allclose(
            np.asarray(g_auto.factors[n]), np.asarray(dense[n]),
            rtol=3e-4, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(g_auto.core_factors[n]),
            np.asarray(g_hand.core_grads[n]) * core_scale,
            rtol=3e-4, atol=1e-5)


def test_masked_gradients_ignore_padding(tensor, cfg):
    params = init_params(jax.random.PRNGKey(1), cfg)
    idx, val = tensor.indices[:128], tensor.values[:128]
    # duplicate batch with garbage rows masked out
    idx2 = jnp.concatenate([idx, idx[:32] * 0], 0)
    val2 = jnp.concatenate([val, val[:32] * 0 + 99.0], 0)
    mask = jnp.concatenate([jnp.ones(128, bool), jnp.zeros(32, bool)])
    g_ref = ft.batch_gradients(params, idx, val, 0.01, 0.02)
    g_msk = ft.batch_gradients(params, idx2, val2, 0.01, 0.02, mask=mask)
    d_ref = ft.scatter_row_grads(params.factors, idx, g_ref.row_grads)
    d_msk = ft.scatter_row_grads(params.factors, idx2, g_msk.row_grads)
    for n in range(3):
        np.testing.assert_allclose(np.asarray(d_ref[n]),
                                   np.asarray(d_msk[n]),
                                   rtol=1e-5, atol=1e-6)
        # core grads normalize by valid count — identical here
        np.testing.assert_allclose(np.asarray(g_ref.core_grads[n]),
                                   np.asarray(g_msk.core_grads[n]),
                                   rtol=2e-5, atol=1e-6)


def test_kernel_path_identical(tensor, cfg):
    params = init_params(jax.random.PRNGKey(2), cfg)
    idx, val = tensor.indices[:128], tensor.values[:128]
    g1 = ft.batch_gradients(params, idx, val, 0.01, 0.01, backend="xla")
    g2 = ft.batch_gradients(params, idx, val, 0.01, 0.01,
                            backend="pallas_interpret")
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_training_converges(tensor, cfg):
    train_t, test_t = tensor.split(0.1, seed=3)
    state, hist = train(jax.random.PRNGKey(4), train_t, cfg,
                        num_steps=400, eval_every=200, test=test_t)
    assert hist[-1]["rmse"] < 0.35, hist


def test_factor_only_mode_converges(tensor, cfg):
    """Paper's 'Factor' curves: core factors frozen, RMSE still improves."""
    from repro.core.metrics import rmse_mae as _rm
    train_t, test_t = tensor.split(0.1, seed=3)
    # mirror train()'s internal key handling: it splits before init
    init_key = jax.random.split(jax.random.PRNGKey(5))[1]
    state0 = init_state(init_key, cfg)
    r0, _ = _rm(state0.params, test_t, ft.predict)
    state, hist = train(jax.random.PRNGKey(5), train_t, cfg,
                        num_steps=300, eval_every=300, test=test_t,
                        update_core=False)
    for b0, b1 in zip(state0.params.core_factors,
                      state.params.core_factors):
        np.testing.assert_array_equal(np.asarray(b0), np.asarray(b1))
    assert hist[-1]["rmse"] < 0.8 * float(r0)  # ≥20% improvement


def test_gauss_seidel_mode_runs(tensor):
    cfg_gs = FastTuckerConfig(dims=DIMS, ranks=(4, 4, 4), core_rank=4,
                              batch_size=128, update_order="gauss_seidel")
    state = init_state(jax.random.PRNGKey(6), cfg_gs)
    for i in range(5):
        state = sgd_step(state, jax.random.PRNGKey(i), tensor.indices,
                         tensor.values, cfg_gs)
    assert not np.any(np.isnan(np.asarray(state.params.factors[0])))


def test_dynamic_lr_schedule():
    t = jnp.asarray([0, 1, 10, 100], jnp.int32)
    lr = jax.vmap(lambda s: ft.dynamic_lr(0.01, 0.1, s))(t)
    assert float(lr[0]) == pytest.approx(0.01)
    assert np.all(np.diff(np.asarray(lr)) < 0)  # strictly decaying


# -- baselines --------------------------------------------------------------

def test_cutucker_grads_match_autodiff(tensor):
    ccfg = cu.CuTuckerConfig(dims=DIMS, ranks=(4, 4, 4), batch_size=128)
    params = cu.init_params(jax.random.PRNGKey(0), ccfg)
    idx, val = tensor.indices[:128], tensor.values[:128]
    loss = lambda p: cu.sampled_loss(p, idx, val, 0.01, 0.02,
                                     row_mean=True)
    g_auto = jax.grad(loss)(params)
    g_hand = cu.batch_gradients(params, idx, val, 0.01, 0.02,
                                row_mean=True)
    dense = ft.scatter_row_grads(params.factors, idx, g_hand.row_grads)
    for n in range(3):
        np.testing.assert_allclose(np.asarray(g_auto.factors[n]),
                                   np.asarray(dense[n]), rtol=3e-4,
                                   atol=1e-5)
    np.testing.assert_allclose(np.asarray(g_auto.core),
                               np.asarray(g_hand.core_grad), rtol=3e-4,
                               atol=1e-5)


def test_cutucker_kron_equals_einsum(tensor):
    """The literal Kronecker coefficient path == efficient contraction."""
    ccfg = cu.CuTuckerConfig(dims=DIMS, ranks=(3, 4, 5), batch_size=64)
    params = cu.init_params(jax.random.PRNGKey(1), ccfg)
    idx, val = tensor.indices[:64], tensor.values[:64]
    g1 = cu.batch_gradients(params, idx, val, 0.01, 0.01, "einsum")
    g2 = cu.batch_gradients(params, idx, val, 0.01, 0.01, "kron")
    np.testing.assert_allclose(np.asarray(g1.err), np.asarray(g2.err),
                               rtol=1e-4, atol=1e-5)
    for a, b in zip(g1.row_grads, g2.row_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_als_epoch_reduces_loss(tensor):
    acfg = als.ALSConfig(dims=DIMS, ranks=(4, 4, 4))
    ccfg = cu.CuTuckerConfig(dims=DIMS, ranks=(4, 4, 4))
    params = cu.init_params(jax.random.PRNGKey(2), ccfg)
    train_t, test_t = tensor.split(0.1, seed=1)
    r0, _ = rmse_mae(params, test_t, als.predict)
    for _ in range(3):
        params = als.als_epoch(params, train_t, acfg)
    r1, _ = rmse_mae(params, test_t, als.predict)
    assert float(r1) < float(r0)
    assert float(r1) < 0.2  # exact row solves converge fast


def test_ccd_epoch_reduces_loss(tensor):
    ccfg_c = ccd.CCDConfig(dims=DIMS, ranks=(4, 4, 4))
    ccfg = cu.CuTuckerConfig(dims=DIMS, ranks=(4, 4, 4))
    params = cu.init_params(jax.random.PRNGKey(3), ccfg)
    train_t, test_t = tensor.split(0.1, seed=1)
    r0, _ = rmse_mae(params, test_t, ccd.predict)
    for _ in range(3):
        params = ccd.ccd_epoch(params, train_t, ccfg_c)
    r1, _ = rmse_mae(params, test_t, ccd.predict)
    assert float(r1) < float(r0)


def test_fasttucker_representable_by_cutucker():
    """Kruskal core is a subspace of full cores: predictions must agree
    when the full core is the materialized Kruskal core."""
    from repro.core.kruskal import kruskal_to_core
    cfg = FastTuckerConfig(dims=DIMS, ranks=(3, 3, 3), core_rank=2,
                           batch_size=32)
    params = init_params(jax.random.PRNGKey(9), cfg)
    t = planted_tensor(DIMS, 500, seed=11)
    idx = t.indices[:100]
    pred_fast = ft.predict(params, idx)
    cu_params = cu.CuTuckerParams(
        params.factors, kruskal_to_core(params.core_factors))
    pred_full = cu.predict(cu_params, idx)
    np.testing.assert_allclose(np.asarray(pred_fast),
                               np.asarray(pred_full), rtol=1e-5,
                               atol=1e-6)
