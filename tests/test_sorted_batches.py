"""Mode-sorted batch layout: invariants, parity, and O(B) structure.

Contracts locked here:

  1. LAYOUT — ``sorted_batch_layout`` emits a stable per-mode sort
     permutation, sorted row ids, compacted unique ids, CSR segment
     offsets and the inverse index, all mutually consistent.
  2. PARITY — ``sorted_batches=True`` is bitwise-identical to the
     unsorted path in f32: the dedup gather moves the same bits, and the
     stable sort preserves each row's duplicate order so the segmented
     scatter adds the same values in the same order.  Locked for
     ``sgd_step`` (both backends × both update orders × phase_split),
     the two-program phase pipeline, and the local/sync strategies.  The
     strata flavors' stratum body is bitwise under plain jit; their full
     shard_map-compiled steps carry a pre-existing ~1-ulp wobble (XLA
     CPU FMA contraction differs per compiled program — the UNSORTED
     compiled step already differs from its own eager math by the same
     amount), so those assert a tight tolerance instead.
  3. KERNEL — the Pallas ``segment_reduce`` kernel is bitwise-identical
     to ``jax.ops.segment_sum`` (sequential in-order accumulation), a
     STRONGER contract than the unsorted one-hot ``scatter_accum``,
     whose in-tile dot tree-reduction is only tolerance-equal to that
     same reference.
  4. STRUCTURE — the sorted scatter is O(B): the ``segment_reduce``
     kernel contains ZERO dot_generals (vs the one-hot kernel's dense
     O(rows×B) MXU sweep), asserted on the jaxpr and via
     ``hlo_analysis.dot_flops`` on the compiled steps.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FastTuckerConfig, init_state, sgd_step
from repro.core import fasttucker as ft
from repro.core.sampling import sorted_batch_layout
from repro.data.synthetic import planted_tensor
from repro.kernels import dispatch, ref
from repro.kernels.scatter_accum import scatter_accum
from repro.kernels.segment_reduce import segment_reduce
from repro.launch.hlo_analysis import analyze

BACKENDS = ("xla", "pallas_interpret")
DIMS = (40, 32, 24)


@pytest.fixture(scope="module")
def tensor():
    return planted_tensor(DIMS, 4000, rank=4, core_rank=4, noise=0.05,
                          seed=13)


def _cfg(**kw):
    base = dict(dims=DIMS, ranks=(4, 4, 4), core_rank=4, batch_size=256)
    base.update(kw)
    return FastTuckerConfig(**base)


def _run(tensor, cfg, steps=5):
    state = init_state(jax.random.PRNGKey(0), cfg)
    for i in range(steps):
        state = sgd_step(state, jax.random.PRNGKey(100 + i),
                         tensor.indices, tensor.values, cfg)
    return state


def _assert_tree_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# 1. layout invariants
# ---------------------------------------------------------------------------

def test_layout_invariants():
    rng = np.random.default_rng(0)
    # includes negative ids (masked strata padding localizes below 0)
    idx = jnp.asarray(rng.integers(-2, 12, (64, 3)).astype(np.int32))
    lay = jax.jit(sorted_batch_layout)(idx)
    B, N = idx.shape
    for n in range(N):
        col = np.asarray(idx[:, n])
        p = np.asarray(lay.perm[n])
        sr = np.asarray(lay.sorted_rows[n])
        assert sorted(p.tolist()) == list(range(B))  # a permutation
        np.testing.assert_array_equal(sr, col[p])
        assert (np.diff(sr) >= 0).all()              # ascending
        for r in np.unique(col):                     # STABLE: batch order
            assert (np.diff(p[sr == r]) > 0).all()
        U = int(lay.num_uniq[n])
        assert U == len(np.unique(col))
        uq, iv = np.asarray(lay.uniq[n]), np.asarray(lay.inv[n])
        np.testing.assert_array_equal(uq[:U], np.unique(col))
        np.testing.assert_array_equal(uq[iv], col)   # exact reconstruction
        st = np.asarray(lay.seg_starts[n])
        for u in range(U):
            assert (sr[st[u]:st[u + 1]] == uq[u]).all()
            assert st[u + 1] - st[u] == (col == uq[u]).sum()
        assert (st[U:] == B).all()


def test_layout_shapes_and_sampler():
    from repro.core.sampling import sample_batch_arrays

    t = planted_tensor((10, 8, 6), 300, seed=1)
    idx, val = sample_batch_arrays(
        jax.random.PRNGKey(0), t.indices, t.values, 128)
    lay = sorted_batch_layout(idx)
    assert idx.shape == (128, 3) and val.shape == (128,)
    assert lay.perm.shape == lay.sorted_rows.shape == (3, 128)
    assert lay.uniq.shape == lay.inv.shape == (3, 128)
    assert lay.seg_starts.shape == (3, 129)
    assert lay.num_uniq.shape == (3,)


def test_dedup_gather_bitwise(tensor):
    for dtype in ("float32", "bfloat16"):
        cfg = _cfg(dtype=dtype)
        params = init_state(jax.random.PRNGKey(0), cfg).params
        idx = tensor.indices[:256]
        lay = sorted_batch_layout(idx)
        plain = ft.gather_rows(params.factors, idx)
        dedup = ft.gather_rows(params.factors, idx, lay)
        _assert_tree_equal(plain, dedup)


# ---------------------------------------------------------------------------
# 3. segment_reduce kernel vs the jnp reference (bitwise)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "B,J,I,bt", [(4096, 8, 1000, 512), (513, 8, 100, 128),
                 (64, 4, 1000, 512), (100, 32, 64, 64), (7, 3, 5, 4)])
def test_segment_reduce_bitwise_vs_reference(B, J, I, bt):
    """Sequential sorted accumulation == segment_sum of the unsorted
    batch, bitwise — including out-of-range ids (dropped) and ragged
    B % block_b tiles (padded with -1)."""
    rng = np.random.default_rng(B + J)
    idx = rng.integers(-2, I + 3, B).astype(np.int32)  # OOB on both sides
    order = np.argsort(idx, kind="stable")
    g = rng.normal(size=(B, J)).astype(np.float32)
    want = ref.scatter_accum_ref(jnp.asarray(g), jnp.asarray(idx), I)
    got = segment_reduce(jnp.asarray(g[order]), jnp.asarray(idx[order]), I,
                         block_b=bt, interpret=True)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    # and the sorted ref mirror agrees with the unsorted one
    got_ref = ref.segment_reduce_ref(jnp.asarray(g[order]),
                                     jnp.asarray(idx[order]), I)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got_ref))


@pytest.mark.parametrize(
    "B,J,I,bt,rt", [(513, 8, 3000, 128, 64), (4096, 4, 50_000, 1024, 512),
                    (100, 3, 10, 128, 16), (2048, 8, 700, 256, 16)])
def test_segment_reduce_row_tiles_bitwise(B, J, I, bt, rt):
    """Many output row tiles and batch chunks: runs that straddle chunk
    boundaries, tiles no entry touches, and out-of-range ids all reduce
    bitwise like segment_sum."""
    rng = np.random.default_rng(B + I)
    idx = rng.integers(-2, I + 3, B).astype(np.int32)
    idx[: B // 4] = rng.integers(0, 3, B // 4)        # long duplicate runs
    order = np.argsort(idx, kind="stable")
    g = rng.normal(size=(B, J)).astype(np.float32)
    want = ref.scatter_accum_ref(jnp.asarray(g), jnp.asarray(idx), I)
    got = segment_reduce(jnp.asarray(g[order]), jnp.asarray(idx[order]), I,
                         block_rows=rt, block_b=bt, interpret=True)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_xla_segment_reduce_bitwise_vs_scatter_accum():
    """On the xla backend the sorted scatter is bitwise == the unsorted
    one (the stable permutation preserves per-row duplicate order)."""
    bk = dispatch.get_backend("xla")
    rng = np.random.default_rng(3)
    idx = jnp.asarray(rng.integers(0, 50, 512).astype(np.int32))
    g = jnp.asarray(rng.normal(size=(512, 8)).astype(np.float32))
    order = jnp.argsort(idx, stable=True)
    u = bk.scatter_accum(g, idx, 50)
    s = bk.segment_reduce(g[order], idx[order], 50)
    np.testing.assert_array_equal(np.asarray(u), np.asarray(s))


def test_scatter_row_grads_layout_routing(tensor):
    """scatter_row_grads(layout=...) equals the unsorted scatter on both
    backends at this scale (and bitwise-equals the reference on Pallas,
    where the unsorted one-hot itself is only tolerance-exact)."""
    cfg = _cfg()
    params = init_state(jax.random.PRNGKey(1), cfg).params
    idx = tensor.indices[:256]
    lay = sorted_batch_layout(idx)
    g = ft.batch_gradients(params, idx, tensor.values[:256], 0.01, 0.02)
    for backend in BACKENDS:
        u = ft.scatter_row_grads(params.factors, idx, g.row_grads,
                                 backend=backend)
        s = ft.scatter_row_grads(params.factors, idx, g.row_grads,
                                 backend=backend, layout=lay)
        for n in range(cfg.order):
            want = ref.scatter_accum_ref(g.row_grads[n], idx[:, n],
                                         cfg.dims[n])
            # sorted path: bitwise vs the jnp reference on EVERY backend
            np.testing.assert_array_equal(np.asarray(want),
                                          np.asarray(s[n]))
            np.testing.assert_allclose(np.asarray(u[n]), np.asarray(s[n]),
                                       rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# 2. step-level parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("order", ["jacobi", "gauss_seidel"])
@pytest.mark.parametrize("phase_split", [False, True])
def test_sorted_step_bitwise_equals_unsorted(tensor, backend, order,
                                             phase_split):
    """f32: the mode-sorted step IS the unsorted step, bit for bit."""
    kw = dict(backend=backend, update_order=order, phase_split=phase_split)
    a = _run(tensor, _cfg(**kw))
    b = _run(tensor, _cfg(sorted_batches=True, **kw))
    _assert_tree_equal(a.params, b.params)


def test_sorted_phase_programs_bitwise(tensor):
    """The separately compiled factor/core phase programs honor the
    sorted layout and still reproduce the fused joint step."""
    cfg = _cfg(sorted_batches=True)
    state = init_state(jax.random.PRNGKey(0), cfg)
    key = jax.random.PRNGKey(7)
    joint = sgd_step(state, key, tensor.indices, tensor.values, _cfg())
    st1, idx, val, inter = ft.factor_phase_step(
        state, key, tensor.indices, tensor.values, cfg)
    split = ft.core_phase_step(st1, idx, val, cfg, inter)
    _assert_tree_equal(joint.params, split.params)


def test_sorted_bf16_matches_unsorted_bf16(tensor):
    """bf16 storage: gathers/scatters still move identical bits."""
    a = _run(tensor, _cfg(dtype="bfloat16"))
    b = _run(tensor, _cfg(dtype="bfloat16", sorted_batches=True))
    _assert_tree_equal(a.params, b.params)


def test_sorted_batches_default_off_guard():
    """Golden trajectories depend on the unsorted default staying put."""
    assert _cfg().sorted_batches is False


# ---------------------------------------------------------------------------
# strategy-level parity (single device; 4-device lives in test_strategies)
# ---------------------------------------------------------------------------

def _run_strategy(name, tensor, cfg, steps=8, compress=False):
    import contextlib

    from repro.distributed import get_strategy
    from repro.launch.mesh import make_host_mesh

    st = get_strategy(name)
    mesh = make_host_mesh() if st.needs_mesh else None
    plan = st.prepare(tensor, cfg, mesh, compress=compress, seed=0)
    ds = st.init(plan, init_state(jax.random.PRNGKey(0), cfg),
                 jax.random.PRNGKey(1))
    step = st.make_step(plan)
    with (mesh if mesh is not None else contextlib.nullcontext()):
        while int(ds.step) < steps:
            ds = step(ds)
    return st.eval_params(plan, ds)


@pytest.mark.parametrize("name", ["local", "sync"])
def test_local_sync_strategies_sorted_bitwise(name):
    t = planted_tensor((18, 15, 12), 2500, noise=0.05, seed=0)
    kw = dict(dims=(18, 15, 12), ranks=(3,) * 3, core_rank=3,
              batch_size=128)
    a = _run_strategy(name, t, FastTuckerConfig(**kw))
    b = _run_strategy(name, t, FastTuckerConfig(sorted_batches=True, **kw))
    _assert_tree_equal(a, b)


@pytest.mark.parametrize("name", ["strata", "strata_overlap"])
def test_strata_strategies_sorted_tight_tolerance(name):
    """The shard_map-compiled strata step carries a pre-existing ~1-ulp
    FMA-contraction wobble between compiled programs (the unsorted
    compiled step differs from its own eager math by the same amount —
    asserted below), so the sorted parity bound here is ulp-tight rather
    than bitwise."""
    t = planted_tensor((18, 15, 12), 2500, noise=0.05, seed=0)
    kw = dict(dims=(18, 15, 12), ranks=(3,) * 3, core_rank=3,
              batch_size=128)
    a = _run_strategy(name, t, FastTuckerConfig(**kw))
    b = _run_strategy(name, t, FastTuckerConfig(sorted_batches=True, **kw))
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-6, atol=1e-7)


def test_stratum_body_sorted_bitwise_eager():
    """The strata math itself (masked gradients, localized scatter) is
    bitwise op-for-op — the wobble in the test above comes from XLA
    fusing the two compiled programs differently (FMA contraction on the
    Eq.-13 `w·d + λ·reg` pattern), not from the layout."""
    from repro.core.fasttucker import (
        _sgd_update, batch_layout, dynamic_lr, scatter_row_grads,
        step_gradients,
    )
    from repro.distributed import get_strategy
    from repro.launch.mesh import make_host_mesh

    dims = (18, 15, 12)
    t = planted_tensor(dims, 2500, noise=0.05, seed=0)
    cfgs = {s: FastTuckerConfig(dims=dims, ranks=(3,) * 3, core_rank=3,
                                batch_size=128, sorted_batches=s)
            for s in (False, True)}
    st = get_strategy("strata")
    mesh = make_host_mesh()
    plan = st.prepare(t, cfgs[False], mesh, seed=0)
    ds = st.init(plan, init_state(jax.random.PRNGKey(0), cfgs[False]),
                 jax.random.PRNGKey(1))
    b = plan.layout.buckets
    s0 = int(plan.schedule[0])
    idx_b, val_b, msk_b = (b["indices"][s0][0], b["values"][s0][0],
                           b["mask"][s0][0])

    def body(params, step, key, sorted_):
        cfg = cfgs[sorted_]
        skey = jax.random.fold_in(jax.random.fold_in(key, step), 0)
        pick = jax.random.randint(skey, (128,), 0, idx_b.shape[0])
        lidx, val, msk = idx_b[pick], val_b[pick], msk_b[pick]
        lay = batch_layout(lidx, cfg)
        grads = step_gradients(params, lidx, val, cfg, mask=msk,
                               layout=lay)
        dense = scatter_row_grads(params.factors, lidx, grads.row_grads,
                                  backend=cfg.backend, layout=lay)
        lr_a = dynamic_lr(cfg.alpha_a, cfg.beta_a, step)
        return tuple(_sgd_update(f, lr_a, g)
                     for f, g in zip(params.factors, dense))

    f_u = body(ds.params, ds.step, ds.key, False)
    f_s = body(ds.params, ds.step, ds.key, True)
    _assert_tree_equal(f_u, f_s)


def test_local_compressed_sorted_bitwise():
    """int8 EF compression composes: quantization sees bit-identical
    dense gradients either way."""
    t = planted_tensor((18, 15, 12), 2500, noise=0.05, seed=0)
    kw = dict(dims=(18, 15, 12), ranks=(3,) * 3, core_rank=3,
              batch_size=128)
    a = _run_strategy("local", t, FastTuckerConfig(**kw), compress=True)
    b = _run_strategy("local", t, FastTuckerConfig(sorted_batches=True,
                                                   **kw), compress=True)
    _assert_tree_equal(a, b)


# ---------------------------------------------------------------------------
# 4. structure: the sorted scatter is O(B) — no dense one-hot over rows
# ---------------------------------------------------------------------------

def _count_jaxpr_dots(jaxpr) -> int:
    total = 0
    eqns = jaxpr.jaxpr.eqns if hasattr(jaxpr, "jaxpr") else jaxpr.eqns
    for eqn in eqns:
        if eqn.primitive.name == "dot_general":
            total += 1
        for v in eqn.params.values():
            items = v if isinstance(v, (tuple, list)) else (v,)
            for item in items:
                if hasattr(item, "eqns") or hasattr(item, "jaxpr"):
                    total += _count_jaxpr_dots(item)
    return total


def test_segment_reduce_kernel_has_no_dots():
    """The one-hot kernel's work IS a dense (rows×BT)·(BT×J) dot per grid
    cell; the sorted kernel replaces all of it with O(B) accumulates —
    zero dot_generals in the whole jaxpr."""
    g = jnp.zeros((512, 8), jnp.float32)
    idx = jnp.zeros((512,), jnp.int32)
    dots_sorted = _count_jaxpr_dots(jax.make_jaxpr(
        lambda g, i: segment_reduce(g, i, 300, interpret=True))(g, idx))
    dots_onehot = _count_jaxpr_dots(jax.make_jaxpr(
        lambda g, i: scatter_accum(g, i, 300, interpret=True))(g, idx))
    assert dots_sorted == 0, dots_sorted
    assert dots_onehot >= 1, dots_onehot


def test_sorted_step_dot_flops_drop_on_pallas(tensor):
    """hlo_analysis.dot_flops: on the Pallas backend the sorted step's
    compiled program loses the one-hot scatter's O(rows×B) dot FLOPs —
    ≥ the analytic one-hot cost — while keeping every gradient dot."""
    state = init_state(jax.random.PRNGKey(0), _cfg())
    key = jax.random.PRNGKey(1)
    flops = {}
    for s in (False, True):
        cfg = _cfg(backend="pallas_interpret", sorted_batches=s)
        comp = sgd_step.lower(state, key, tensor.indices, tensor.values,
                              cfg).compile()
        flops[s] = analyze(comp.as_text())["dot_flops"]
    B, J = 256, 4
    onehot_flops = sum(2.0 * d * B * J for d in DIMS)
    assert flops[False] - flops[True] >= 0.9 * onehot_flops, flops


def test_sorted_step_dot_flops_equal_on_xla(tensor):
    """On xla both scatters are dot-free segment sums: the sorted step
    adds NO dot FLOPs (the layout is pure integer bookkeeping)."""
    state = init_state(jax.random.PRNGKey(0), _cfg())
    key = jax.random.PRNGKey(1)
    flops = {}
    for s in (False, True):
        cfg = _cfg(backend="xla", sorted_batches=s)
        comp = sgd_step.lower(state, key, tensor.indices, tensor.values,
                              cfg).compile()
        flops[s] = analyze(comp.as_text())["dot_flops"]
    assert flops[True] == pytest.approx(flops[False])
