"""Backend registry + fused gradient pipeline: cross-backend parity.

The contract under test: every op exposed by ``repro.kernels.dispatch``
produces identical numerics (atol ≤ 1e-5) on the ``"xla"`` reference
backend and the ``"pallas_interpret"`` kernel backend, for orders
N ∈ {3, 4}, unequal per-mode ranks J_n, and the masked/padded
distributed path — plus the structural guarantee that the fused path is
a single ``pallas_call``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FastTuckerConfig, init_params, init_state, sgd_step
from repro.core import fasttucker as ft
from repro.kernels import dispatch, ref

BACKENDS = ("xla", "pallas_interpret")


def _problem(N, seed=0, B=173):
    """Unequal per-mode ranks J_n; magnitudes O(1) like real factor inits."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 2 * N + 2)
    ranks = tuple(3 + 2 * n for n in range(N))          # 3,5,7,9 — ragged
    R = 4
    rows = tuple(
        jax.random.normal(ks[n], (B, ranks[n])) * 0.4 for n in range(N))
    cfs = tuple(
        jax.random.normal(ks[N + n], (ranks[n], R)) * 0.4 for n in range(N))
    val = jax.random.normal(ks[-1], (B,))
    return rows, cfs, val


def _assert_tree_close(a, b, atol=1e-5):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=1e-5, atol=atol)


@pytest.mark.parametrize("N", [3, 4])
@pytest.mark.parametrize("row_mean", [False, True])
def test_kruskal_grad_backend_parity(N, row_mean):
    rows, cfs, val = _problem(N)
    outs = [
        dispatch.get_backend(b).kruskal_grad(
            rows, cfs, val, lambda_a=0.01, lambda_b=0.02, row_mean=row_mean)
        for b in BACKENDS
    ]
    _assert_tree_close(outs[0], outs[1])


@pytest.mark.parametrize("N", [3, 4])
def test_kruskal_grad_masked_padded_parity(N):
    """The distributed path: padding entries masked out, B not a multiple
    of the kernel batch tile (exercises in-kernel zero padding too)."""
    rows, cfs, val = _problem(N, seed=3, B=173)
    mask = jnp.concatenate(
        [jnp.ones(131, bool), jnp.zeros(42, bool)])
    outs = [
        dispatch.get_backend(b).kruskal_grad(
            rows, cfs, val, mask=mask, lambda_a=0.01, lambda_b=0.02)
        for b in BACKENDS
    ]
    _assert_tree_close(outs[0], outs[1])
    # masked entries contribute nothing: err is exactly zero there
    np.testing.assert_array_equal(np.asarray(outs[1].err[131:]), 0.0)


@pytest.mark.parametrize("N", [3, 4])
def test_kruskal_contract_backend_parity(N):
    rows, cfs, val = _problem(N, seed=5)
    p1, e1 = dispatch.get_backend("xla").kruskal_contract(rows, cfs)
    p2, e2 = dispatch.get_backend("pallas_interpret").kruskal_contract(
        rows, cfs)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(e1), np.asarray(e2),
                               rtol=1e-5, atol=1e-5)


def test_fused_kernel_matches_ref_oracle():
    """Stacked-layout kernel vs the pure-jnp oracle in ref.py."""
    N, B, J, R = 3, 257, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    a = jax.random.normal(ks[0], (N, B, J)) * 0.4
    b = jax.random.normal(ks[1], (N, J, R)) * 0.4
    val = jax.random.normal(ks[2], (B,))
    mask = (jax.random.uniform(ks[3], (B,)) > 0.3).astype(jnp.float32)
    scal = jnp.asarray([1.0 / 3, 1.0 / 7, 0.01, 0.02, 1.0], jnp.float32)
    from repro.kernels.kruskal_grad import kruskal_grad

    outs = kruskal_grad(a, b, val, mask, scal, block_b=64, interpret=True)
    wants = ref.kruskal_grad_ref(a, b, val, mask, scal)
    for o, w in zip(outs, wants):
        if o is None or w is None:
            assert o is None and w is None  # same stage skipped
            continue
        np.testing.assert_allclose(np.asarray(o), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)
    # phase flags: consume cached c, single row mode, emitted c
    c = ref.kruskal_grad_ref(a, b, val, mask, scal, emit_c=True)[-1]
    o2 = kruskal_grad(a, b, val, mask, scal, c, row_modes=(1,),
                      want_core=False, emit_c=True, block_b=64,
                      interpret=True)
    w2 = ref.kruskal_grad_ref(a, b, val, mask, scal, c, row_modes=(1,),
                              want_core=False, emit_c=True)
    assert o2.core_grads is None and w2[3] is None
    np.testing.assert_allclose(np.asarray(o2.row_grads),
                               np.asarray(w2[2]), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(o2.c), np.asarray(w2[4]),
                               rtol=1e-5, atol=1e-5)


def test_batch_gradients_backend_parity_via_config():
    cfg = FastTuckerConfig(dims=(40, 30, 20, 25), ranks=(3, 5, 4, 6),
                           core_rank=4, batch_size=64)
    params = init_params(jax.random.PRNGKey(0), cfg)
    idx = jnp.stack(
        [jax.random.randint(jax.random.PRNGKey(i), (96,), 0, d)
         for i, d in enumerate(cfg.dims)], axis=1)
    val = jax.random.normal(jax.random.PRNGKey(9), (96,))
    g1 = ft.batch_gradients(params, idx, val, 0.01, 0.02, backend="xla")
    g2 = ft.batch_gradients(params, idx, val, 0.01, 0.02,
                            backend="pallas_interpret")
    _assert_tree_close(g1, g2)


def test_scatter_row_grads_backend_parity():
    cfg = FastTuckerConfig(dims=(50, 40, 30), ranks=(4, 4, 4), core_rank=4)
    params = init_params(jax.random.PRNGKey(1), cfg)
    idx = jnp.stack(
        [jax.random.randint(jax.random.PRNGKey(i), (130,), 0, d)
         for i, d in enumerate(cfg.dims)], axis=1)
    rg = tuple(jax.random.normal(jax.random.PRNGKey(20 + n), (130, 4))
               for n in range(3))
    d1 = ft.scatter_row_grads(params.factors, idx, rg, backend="xla")
    d2 = ft.scatter_row_grads(params.factors, idx, rg,
                              backend="pallas_interpret")
    _assert_tree_close(d1, d2, atol=1e-5)


def test_grad_of_sampled_loss_routes_through_kernels():
    """jax.grad(sampled_loss) on the kernel backend == xla autodiff."""
    cfg = FastTuckerConfig(dims=(30, 25, 20), ranks=(4, 5, 3), core_rank=4)
    params = init_params(jax.random.PRNGKey(2), cfg)
    idx = jnp.stack(
        [jax.random.randint(jax.random.PRNGKey(i), (64,), 0, d)
         for i, d in enumerate(cfg.dims)], axis=1)
    val = jax.random.normal(jax.random.PRNGKey(8), (64,))
    g_xla = jax.grad(
        lambda p: ft.sampled_loss(p, idx, val, 0.01, 0.02, backend="xla")
    )(params)
    g_pal = jax.grad(
        lambda p: ft.sampled_loss(p, idx, val, 0.01, 0.02,
                                  backend="pallas_interpret")
    )(params)
    _assert_tree_close(g_xla, g_pal, atol=1e-5)


def test_vjp_exact_for_tiny_cotangents_at_large_pred():
    """Regression: the custom-VJP backward must inject the cotangent
    exactly, not reconstruct it as pred − (pred − ḡ) — that cancels to 0
    in f32 whenever |ḡ| < ulp(pred) (e.g. near convergence on
    unnormalized data)."""
    N, B = 3, 32
    ks = jax.random.split(jax.random.PRNGKey(21), 2 * N)
    # large factors → |pred| ~ 1e4..1e5, far above ulp⁻¹ of a 1e-4 cotangent
    rows = tuple(jax.random.normal(ks[n], (B, 8)) * 10.0 for n in range(N))
    cfs = tuple(
        jax.random.normal(ks[N + n], (8, 4)) * 10.0 for n in range(N))
    g = jnp.full((B,), 1e-4)
    outs = {}
    for b in BACKENDS:
        _, vjp = jax.vjp(
            lambda r, c: dispatch.kruskal_predict(b, r, c)
            if b != "xla" else dispatch.get_backend("xla").kruskal_contract(
                r, c)[0],
            rows, cfs)
        outs[b] = vjp(g)
    leaves = jax.tree.leaves(outs["pallas_interpret"])
    assert max(float(jnp.abs(x).max()) for x in leaves) > 0.0
    _assert_tree_close(outs["xla"], outs["pallas_interpret"], atol=1e-5)


def test_trainstate_trajectory_parity():
    """Acceptance: identical TrainState trajectories (≤1e-5) across
    backends on a 3-order synthetic tensor."""
    from repro.data.synthetic import planted_tensor

    t = planted_tensor((40, 32, 24), 4000, rank=4, core_rank=4, seed=13)
    states = {}
    for b in BACKENDS:
        cfg = FastTuckerConfig(dims=t.dims, ranks=(4, 4, 4), core_rank=4,
                               batch_size=256, backend=b)
        state = init_state(jax.random.PRNGKey(0), cfg)
        for i in range(10):
            state = sgd_step(state, jax.random.PRNGKey(100 + i),
                             t.indices, t.values, cfg)
        states[b] = state
    _assert_tree_close(states["xla"].params, states["pallas_interpret"].params)


def test_fused_path_single_pallas_call():
    """Acceptance: batch_gradients on the fused backend lowers the whole
    contraction+gradient stage to exactly one pallas_call."""
    from repro.kernels.dispatch import count_pallas_calls

    cfg = FastTuckerConfig(dims=(32, 32, 32), ranks=(4, 4, 4), core_rank=4)
    params = init_params(jax.random.PRNGKey(3), cfg)
    idx = jax.random.randint(jax.random.PRNGKey(4), (64, 3), 0, 32)
    val = jax.random.normal(jax.random.PRNGKey(5), (64,))
    jaxpr = jax.make_jaxpr(
        lambda p, i, v: ft.batch_gradients(
            p, i, v, 0.01, 0.01, backend="pallas_interpret")
    )(params, idx, val)
    assert count_pallas_calls(jaxpr) == 1, jaxpr


# -- registry mechanics ------------------------------------------------------

def test_registry_resolution_order(monkeypatch):
    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
    assert dispatch.resolve_backend_name(None) == "xla"
    monkeypatch.setenv(dispatch.ENV_VAR, "pallas_interpret")
    assert dispatch.resolve_backend_name(None) == "pallas_interpret"
    assert dispatch.resolve_backend_name("pallas") == "pallas"  # arg wins


def test_unknown_backend_raises():
    with pytest.raises(KeyError, match="unknown kernel backend"):
        dispatch.get_backend("cuda_warp_shuffle")


def test_register_custom_backend():
    class Fake:
        name = "fake_test_backend"

    dispatch.register_backend(Fake())
    try:
        assert dispatch.get_backend("fake_test_backend").name == \
            "fake_test_backend"
        with pytest.raises(ValueError, match="already registered"):
            dispatch.register_backend(Fake())
    finally:
        dispatch._REGISTRY.pop("fake_test_backend", None)
