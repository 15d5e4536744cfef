"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.kernels import get_backend, ref
from repro.kernels.kruskal_contract import kruskal_contract
from repro.kernels.scatter_accum import scatter_accum
from repro.kernels.tucker_matmul import tucker_matmul


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "N,B,J,R", [(3, 257, 8, 4), (4, 512, 16, 8), (5, 64, 4, 4),
                (2, 1000, 32, 16), (6, 128, 8, 8)])
def test_kruskal_contract_sweep(N, B, J, R, dtype):
    key = jax.random.PRNGKey(N * 1000 + B)
    ks = jax.random.split(key, 2)
    a = jax.random.normal(ks[0], (N, B, J), dtype)
    b = jax.random.normal(ks[1], (N, J, R), dtype)
    p1, e1 = kruskal_contract(a, b, block_b=128, interpret=True)
    p2, e2 = ref.kruskal_contract_ref(a, b)
    # bf16: kernel accumulates in f32, ref rounds per-op — compare with a
    # tolerance scaled to the output magnitude.  f32 also needs a
    # magnitude-scaled atol: kernel and ref sum the R·Π_n products in
    # different association orders, so elements that nearly cancel carry
    # absolute error proportional to the summed-term magnitude (~1e-7·max).
    if dtype == jnp.float32:
        rtol = 1e-5
        atol_p = 1e-6 * float(np.abs(np.asarray(p2, np.float32)).max() + 1)
        atol_e = 1e-6 * float(np.abs(np.asarray(e2, np.float32)).max() + 1)
    else:
        rtol = 6e-2
        atol_p = 0.05 * float(np.abs(np.asarray(p2, np.float32)).max() + 1)
        atol_e = 0.05 * float(np.abs(np.asarray(e2, np.float32)).max() + 1)
    np.testing.assert_allclose(np.asarray(p1, np.float32),
                               np.asarray(p2, np.float32), rtol=rtol,
                               atol=atol_p)
    np.testing.assert_allclose(np.asarray(e1, np.float32),
                               np.asarray(e2, np.float32), rtol=rtol,
                               atol=atol_e)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,J,I", [(513, 8, 100), (1024, 16, 300), (64, 4, 1000), (100, 32, 64)])
def test_scatter_accum_sweep(B, J, I, dtype):
    g = jax.random.normal(jax.random.PRNGKey(B), (B, J), dtype)
    idx = jax.random.randint(jax.random.PRNGKey(J), (B,), 0, I)
    o1 = scatter_accum(g, idx, I, block_i=64, block_b=128, interpret=True)
    o2 = ref.scatter_accum_ref(g, idx, I)
    tol = 1e-4 if dtype == jnp.float32 else 1e-1
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "M,K,R1,R2,N", [(300, 512, 32, 32, 600), (128, 300, 16, 8, 200),
                    (65, 128, 8, 16, 127)])
def test_tucker_matmul_sweep(M, K, R1, R2, N, dtype):
    key = jax.random.PRNGKey(M)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (M, K), dtype)
    u1 = (jax.random.normal(ks[1], (K, R1), dtype) / np.sqrt(K)).astype(dtype)
    g = jax.random.normal(ks[2], (R1, R2), dtype)
    u2 = jax.random.normal(ks[3], (N, R2), dtype)
    y1 = tucker_matmul(x, u1, g, u2, block_m=64, block_n=128, block_k=128,
                       interpret=True)
    y2 = ref.tucker_matmul_ref(x, u1, g, u2)
    if dtype == jnp.float32:
        rtol, atol = 5e-4, 5e-4
    else:  # bf16 per-op rounding in the ref vs f32 kernel accumulation
        rtol = 8e-2
        atol = 0.05 * float(np.abs(np.asarray(y2, np.float32)).max() + 1)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), rtol=rtol,
                               atol=atol)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31), st.integers(1, 300), st.integers(1, 12),
       st.integers(2, 40))
def test_scatter_accum_property(seed, B, J, I):
    """Σ over rows is preserved (scatter is a permutation-sum)."""
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.normal(size=(B, J)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, I, size=B).astype(np.int32))
    out = scatter_accum(g, idx, I, block_i=16, block_b=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out.sum(0)),
                               np.asarray(g.sum(0)), rtol=1e-4, atol=1e-4)


def test_ragged_mode_dims_padding():
    """The Pallas backend handles per-mode J_n via zero padding."""
    rows = [jax.random.normal(jax.random.PRNGKey(n), (100, 3 + 2 * n))
            for n in range(4)]
    cfs = [jax.random.normal(jax.random.PRNGKey(10 + n), (3 + 2 * n, 5))
           for n in range(4)]
    pred, pexc = get_backend("pallas_interpret").kruskal_contract(rows, cfs)
    from repro.core.kruskal import exclusive_products, mode_dots
    c = mode_dots(rows, cfs)
    full, pexc_ref = exclusive_products(c)
    np.testing.assert_allclose(np.asarray(pred),
                               np.asarray(full.sum(-1)), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(pexc), np.asarray(pexc_ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("BH,S,D,bq,bk", [(4, 256, 32, 64, 64),
                                          (2, 300, 16, 128, 64),
                                          (1, 128, 64, 128, 128)])
def test_flash_attention_kernel(BH, S, D, bq, bk, causal):
    from repro.kernels.flash_attention import flash_attention_fwd
    ks = jax.random.split(jax.random.PRNGKey(S + D), 3)
    q = jax.random.normal(ks[0], (BH, S, D))
    k = jax.random.normal(ks[1], (BH, S, D))
    v = jax.random.normal(ks[2], (BH, S, D))
    out = flash_attention_fwd(q, k, v, causal=causal, block_q=bq,
                              block_k=bk, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
