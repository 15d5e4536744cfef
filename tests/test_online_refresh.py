"""Online training + incremental serve refresh.

Locks the contracts the streaming loop (``repro.launch.online_train``)
rides on:

  * ``NonzeroStore.append`` folds new nonzeros into the existing
    per-(stratum, worker) buckets exactly as rebuilding from the
    concatenated tensor would — in memory and through the spilled
    memmap path, with and without chunk-length regrowth;
  * ``fasttucker.refresh_steps`` / ``DistStrategy.refresh_steps`` run
    bounded factor-phase catch-up (core frozen) and report a dirty-row
    set covering every row they touched;
  * ``TuckerServer.update_rows`` patches ONLY the dirty rows of
    C^(n) = A^(n)B^(n) and lands BITWISE on the tables a full server
    rebuild from the same params would store (f32; bf16 within storage
    tolerance), behind a versioned swap that never writes into a
    generation an in-flight query may have snapshotted;
  * the ``StratumPrefetcher`` surfaces worker-thread failures in
    ``take()`` instead of hanging the training loop.

Single device in tier-1; the 4-device sharded parity + the online CLI
run under the multi-device/slow tier via subprocess.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from helpers import run_with_devices
from repro.core import FastTuckerConfig, FastTuckerParams, init_state
from repro.core import fasttucker as ft
from repro.data.pipeline import NonzeroStore, StratumPrefetcher
from repro.data.synthetic import planted_tensor
from repro.distributed import get_strategy
from repro.launch.mesh import make_host_mesh
from repro.serve import TuckerServer

DIMS = (40, 30, 20)


def _params(seed=0, dims=DIMS, ranks=(4, 3, 2), core_rank=3):
    cfg = FastTuckerConfig(dims=dims, ranks=ranks, core_rank=core_rank,
                           batch_size=32)
    return ft.init_params(jax.random.PRNGKey(seed), cfg)


# ---------------------------------------------------------------------------
# delta patch == full rebuild (single device)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("table_dtype", [None, "bfloat16"])
def test_update_rows_matches_full_rebuild(table_dtype):
    """A chain of row patches across all modes lands on the tables a
    fresh server built from the final params stores — bitwise for f32."""
    params = _params()
    srv = TuckerServer(params, table_dtype=table_dtype)
    rng = np.random.default_rng(1)
    facs = [np.array(f) for f in params.factors]
    v0 = srv.table_version
    for it in range(6):
        mode = it % 3
        f = int(rng.integers(1, srv.dims[mode] + 1))
        ids = np.sort(rng.permutation(srv.dims[mode])[:f]).astype(np.int32)
        new = rng.standard_normal((f, facs[mode].shape[1])) \
            .astype(np.float32)
        facs[mode][ids] = new
        assert srv.update_rows(mode, ids, new) == v0 + it + 1

    ref = TuckerServer(
        FastTuckerParams(tuple(jnp.asarray(f) for f in facs),
                         params.core_factors),
        table_dtype=table_dtype)
    exact = np.dtype(srv.table_dtype) == np.dtype(np.float32)
    for n in range(3):
        a = np.asarray(srv._tables[n], np.float32)
        b = np.asarray(ref._tables[n], np.float32)
        if exact:
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, rtol=0.05, atol=0.05)
        # colsums are incrementally maintained f32 — allclose, not bitwise
        np.testing.assert_allclose(np.asarray(srv._colsums[n]),
                                   np.asarray(ref._colsums[n]),
                                   rtol=1e-4, atol=1e-4)
        # ``server.params`` stayed in sync with the patches
        np.testing.assert_array_equal(np.asarray(srv.params.factors[n]),
                                      facs[n])

    # query parity through every entry point
    rng2 = np.random.default_rng(2)
    q = np.stack([rng2.integers(0, d, 23) for d in srv.dims], 1) \
        .astype(np.int32)
    np.testing.assert_array_equal(np.asarray(srv.predict(q)),
                                  np.asarray(ref.predict(q)))
    s0, i0 = srv.top_k(0, np.arange(10, dtype=np.int32), 4)
    s1, i1 = ref.top_k(0, np.arange(10, dtype=np.int32), 4)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1),
                               rtol=1e-5, atol=1e-5)


def test_refresh_tables_flushes_to_exact_rebuild():
    """After any patch history, ``refresh_tables()`` recomputes from the
    synced params — identical to a from-scratch server, colsums too."""
    params = _params(seed=3)
    srv = TuckerServer(params)
    rng = np.random.default_rng(4)
    ids = np.sort(rng.permutation(DIMS[0])[:7]).astype(np.int32)
    new = rng.standard_normal((7, 4)).astype(np.float32)
    srv.update_rows(0, ids, new)
    v = srv.table_version
    assert srv.refresh_tables() == v + 1
    ref = TuckerServer(srv.params)
    for n in range(3):
        np.testing.assert_array_equal(np.asarray(srv._tables[n]),
                                      np.asarray(ref._tables[n]))
        np.testing.assert_array_equal(np.asarray(srv._colsums[n]),
                                      np.asarray(ref._colsums[n]))


def test_update_rows_validates():
    srv = TuckerServer(_params())
    J = 4
    with pytest.raises(ValueError, match="unique"):
        srv.update_rows(0, [1, 1], np.zeros((2, J), np.float32))
    with pytest.raises(ValueError, match="factor_rows"):
        srv.update_rows(0, [1], np.zeros((2, J), np.float32))
    with pytest.raises(ValueError, match="out of range"):
        srv.update_rows(0, [DIMS[0]], np.zeros((1, J), np.float32))
    with pytest.raises(ValueError, match="mode"):
        srv.update_rows(5, [0], np.zeros((1, J), np.float32))
    # empty patch: version unchanged, no-op
    v = srv.table_version
    assert srv.update_rows(0, np.zeros(0, np.int32),
                           np.zeros((0, J), np.float32)) == v


# ---------------------------------------------------------------------------
# versioned swap: in-flight snapshots are never written
# ---------------------------------------------------------------------------

def test_swap_preserves_inflight_generation():
    """A query that snapshotted generation G answers entirely from G's
    buffers even when patches land mid-flight — the old tables are
    never mutated, only superseded."""
    srv = TuckerServer(_params(seed=5))
    rng = np.random.default_rng(6)
    q = np.stack([rng.integers(0, d, 17) for d in srv.dims], 1) \
        .astype(np.int32)
    before = np.asarray(srv.predict(q)).copy()

    snapshot = srv._live                     # what an in-flight query holds
    frozen = [np.asarray(t).copy() for t in snapshot.tables]

    ids = np.sort(rng.permutation(DIMS[0])[:9]).astype(np.int32)
    new = rng.standard_normal((9, 4)).astype(np.float32)
    srv.update_rows(0, ids, new)

    # the superseded generation's buffers are untouched, bit for bit
    for t, f in zip(snapshot.tables, frozen):
        np.testing.assert_array_equal(np.asarray(t), f)
    assert srv._live.version == snapshot.version + 1
    # ... and the live generation actually changed
    assert not np.array_equal(np.asarray(srv._tables[0]), frozen[0])

    # answers recomputed against the frozen snapshot match the pre-swap
    # answers: one version end to end, no torn reads
    old_pred = srv._predict_fn(snapshot.tables, srv._eyes,
                               jnp.asarray(q))
    np.testing.assert_array_equal(np.asarray(old_pred), before)


def test_frontend_counts_stale_flushes():
    """A table swap landing while a flush is in flight is visible as
    ``stale_flushes`` (the answers were consistent but one version old);
    a flush after the swap is not counted."""
    import asyncio

    srv = TuckerServer(_params(seed=7))
    from repro.serve import AdmissionConfig, ServeFrontend

    class SwapDuringPredict:
        """Server proxy whose first predict also lands a row patch."""

        def __init__(self, inner):
            self.inner = inner
            self.swapped = False

        def __getattr__(self, name):
            return getattr(self.inner, name)

        def predict(self, idx):
            out = self.inner.predict(idx)
            if not self.swapped:
                self.swapped = True
                self.inner.update_rows(
                    0, np.array([1], np.int32),
                    np.zeros((1, 4), np.float32))
            return out

    proxy = SwapDuringPredict(srv)
    req = np.zeros((3, 3), np.int32)

    async def main():
        async with ServeFrontend(proxy,
                                 AdmissionConfig(max_wait_ms=0.1)) as fe:
            await fe.submit(req)     # swap lands mid-flush → stale
            await fe.submit(req)     # clean flush on the new version
            return fe.stats

    stats = asyncio.run(main())
    assert stats.stale_flushes == 1
    assert srv.table_version == 1            # the one swap landed
    assert stats.served == 2


# ---------------------------------------------------------------------------
# bounded refresh: factor-phase catch-up + dirty-row reporting
# ---------------------------------------------------------------------------

def _refresh_problem(dims=(18, 15, 12), nnz=900):
    t = planted_tensor(dims, nnz, noise=0.05, seed=0)
    cfg = FastTuckerConfig(dims=dims, ranks=(3,) * 3, core_rank=3,
                           batch_size=64)
    return t, cfg


def test_refresh_steps_dirty_rows_cover_changes():
    t, cfg = _refresh_problem()
    state = init_state(jax.random.PRNGKey(0), cfg)
    before = [np.asarray(f) for f in state.params.factors]
    cores_before = [np.asarray(b) for b in state.params.core_factors]

    state2, dirty = ft.refresh_steps(
        state, jax.random.PRNGKey(1), t.indices, t.values, cfg,
        num_steps=5)
    assert int(state2.step) == int(state.step) + 5
    assert len(dirty) == t.order
    for n in range(t.order):
        ids = dirty[n]
        assert ids.dtype == np.int32
        assert (np.diff(ids) > 0).all()          # sorted, unique
        assert ids.size and ids.min() >= 0 and ids.max() < cfg.dims[n]
        # every row that actually moved is in the dirty set
        changed = np.nonzero(
            (np.asarray(state2.params.factors[n]) != before[n]).any(1))[0]
        assert np.isin(changed, ids).all()
        # factor phase only: the core stays frozen
        np.testing.assert_array_equal(
            np.asarray(state2.params.core_factors[n]), cores_before[n])

    with pytest.raises(ValueError, match="num_steps"):
        ft.refresh_steps(state, jax.random.PRNGKey(1), t.indices,
                         t.values, cfg, num_steps=0)


@pytest.mark.parametrize("name", ["local", "sync", "strata",
                                  "strata_overlap"])
def test_strategy_refresh_steps(name):
    """Every strategy refreshes through the same interface: K steps
    advance, dirty rows cover the factor changes, and the strategy can
    keep stepping afterwards (state lifted back intact)."""
    t, cfg = _refresh_problem()
    st = get_strategy(name)
    mesh = make_host_mesh() if st.needs_mesh else None
    plan = st.prepare(t, cfg, mesh, seed=0)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    ds = st.init(plan, init_state(k1, cfg), k2)
    step = st.make_step(plan)
    for _ in range(3):
        ds = step(ds)
    fetch = getattr(step, "prefetcher", None)
    if fetch is not None:
        fetch.close()
    before = [np.asarray(f) for f in st.eval_params(plan, ds).factors]

    ds2, dirty = st.refresh_steps(plan, ds, t.indices, t.values,
                                  num_steps=4)
    assert int(ds2.step) == int(ds.step) + 4
    params = st.eval_params(plan, ds2)
    for n in range(t.order):
        changed = np.nonzero(
            (np.asarray(params.factors[n]) != before[n]).any(1))[0]
        assert np.isin(changed, dirty[n]).all()

    # the refreshed state slots straight back into the training loop
    # (strata_overlap advances a whole K-stratum chunk per call)
    step2 = st.make_step(plan)
    ds3 = step2(ds2)
    assert int(ds3.step) > int(ds2.step)
    fetch = getattr(step2, "prefetcher", None)
    if fetch is not None:
        fetch.close()


# ---------------------------------------------------------------------------
# streaming ingest: store.append == rebuild on the concatenation
# ---------------------------------------------------------------------------

def _split(t, n_new):
    from repro.core.sptensor import SparseTensor

    idx, val = np.asarray(t.indices), np.asarray(t.values)
    base = SparseTensor(idx[:-n_new], val[:-n_new], t.dims)
    return base, idx[-n_new:], val[-n_new:]


@pytest.mark.parametrize("num_workers", [1, 4])
def test_append_matches_rebuild(num_workers):
    t = planted_tensor((18, 15, 12), 2000, seed=0)
    base, new_idx, new_val = _split(t, 600)
    store = NonzeroStore.build(base, num_workers)
    # tiny chunk_nnz: the scatter must stay stable across many passes
    out = store.append(new_idx, new_val, chunk_nnz=101)
    ref = NonzeroStore.build(t, num_workers)
    assert out.meta["nnz"] == t.nnz
    assert out.chunk_len == ref.chunk_len
    np.testing.assert_array_equal(out.indices, ref.indices)
    np.testing.assert_array_equal(out.values, ref.values)
    np.testing.assert_array_equal(out.mask, ref.mask)


def test_append_in_place_vs_growth():
    from repro.core.sptensor import SparseTensor

    t = planted_tensor((14, 11, 9), 1200, seed=2)
    store = NonzeroStore.build(t, 2)
    L0 = store.chunk_len
    # a single entry fits in the existing padding → patched in place
    one = np.array([[1, 2, 3]], np.int32)
    same = store.append(one, np.ones(1, np.float32))
    assert same is store and store.meta["nnz"] == t.nnz + 1
    # more entries into ONE bucket than its whole chunk length → the
    # store must regrow (reallocate), in pad_multiple steps
    burst_idx = np.zeros((L0 + 1, 3), np.int32)
    burst_val = np.full(L0 + 1, 2.0, np.float32)
    grown = store.append(burst_idx, burst_val)
    assert grown is not store
    assert grown.chunk_len > L0
    assert grown.chunk_len % int(grown.meta["pad_multiple"]) == 0
    all_idx = np.concatenate([np.asarray(t.indices), one, burst_idx])
    all_val = np.concatenate([np.asarray(t.values),
                              np.ones(1, np.float32), burst_val])
    ref = NonzeroStore.build(SparseTensor(all_idx, all_val, t.dims), 2)
    np.testing.assert_array_equal(grown.indices, ref.indices)
    np.testing.assert_array_equal(grown.values, ref.values)


def test_append_spilled_reopens_and_snapshots(tmp_path):
    t = planted_tensor((14, 11, 9), 1200, seed=5)
    base, new_idx, new_val = _split(t, 500)
    store = NonzeroStore.build(base, 2, spill_dir=str(tmp_path / "s"))
    old_vals = store.values.copy()
    old_mask = store.mask.copy()
    out = store.append(new_idx, new_val)
    assert out.spilled and out.path == store.path
    ref = NonzeroStore.build(t, 2)
    np.testing.assert_array_equal(out.indices, ref.indices)
    np.testing.assert_array_equal(out.values, ref.values)
    np.testing.assert_array_equal(out.mask, ref.mask)
    # reopening from disk sees the appended data too
    np.testing.assert_array_equal(
        NonzeroStore.open(str(tmp_path / "s")).values, ref.values)
    # the base entries were only ever appended after, never reordered
    S, M, L = old_vals.shape
    np.testing.assert_array_equal(out.values[:, :, :L][old_mask],
                                  old_vals[old_mask])


def test_append_spilled_crash_midway_recovers_pre_append(tmp_path,
                                                        monkeypatch):
    """A kill between the growth snapshot (``{f}.npy.tmp`` fully written)
    and the atomic reopen (the ``os.replace`` renames + meta rewrite)
    must leave the on-disk store exactly the PRE-append store: the
    published ``.npy`` files and ``meta.json`` are only ever replaced
    whole, never mutated in place on the growth path."""
    import os as _os

    t = planted_tensor((14, 11, 9), 1200, seed=7)
    base, _, _ = _split(t, 500)
    store = NonzeroStore.build(base, 2, spill_dir=str(tmp_path / "s"))
    pre = {f: np.asarray(getattr(store, f)).copy()
           for f in ("indices", "values", "mask")}
    pre_meta = dict(store.meta)
    L0 = store.chunk_len

    # a one-bucket burst larger than the chunk forces the regrow path
    burst_idx = np.zeros((L0 + 1, 3), np.int32)
    burst_val = np.full(L0 + 1, 2.0, np.float32)

    real_replace = _os.replace

    def dying_replace(src, dst):
        raise OSError(f"simulated crash before publishing {dst}")

    monkeypatch.setattr(_os, "replace", dying_replace)
    with pytest.raises(OSError, match="simulated crash"):
        store.append(burst_idx, burst_val)
    monkeypatch.setattr(_os, "replace", real_replace)

    # recovery = plain open(): the pre-append commit is intact
    back = NonzeroStore.open(str(tmp_path / "s"))
    assert back.meta == pre_meta and back.chunk_len == L0
    for f in ("indices", "values", "mask"):
        np.testing.assert_array_equal(np.asarray(getattr(back, f)), pre[f])
    # staged .tmp debris may remain but is invisible to open(); the
    # recovered store accepts the SAME append cleanly afterwards
    out = back.append(burst_idx, burst_val)
    assert out.spilled and out.meta["nnz"] == pre_meta["nnz"] + L0 + 1
    reopened = NonzeroStore.open(str(tmp_path / "s"))
    np.testing.assert_array_equal(out.values, reopened.values)


def test_append_validates_and_empty_is_noop():
    t = planted_tensor((10, 8, 6), 300, seed=1)
    store = NonzeroStore.build(t, 2)
    assert store.append(np.zeros((0, 3), np.int32),
                        np.zeros(0, np.float32)) is store
    with pytest.raises(ValueError, match="indices"):
        store.append(np.zeros((4, 2), np.int32), np.zeros(4, np.float32))
    with pytest.raises(ValueError, match="values"):
        store.append(np.zeros((4, 3), np.int32), np.zeros(3, np.float32))
    with pytest.raises(ValueError, match="range"):
        store.append(np.array([[10, 0, 0]], np.int32),
                     np.ones(1, np.float32))


# ---------------------------------------------------------------------------
# prefetcher failure propagation (regression: silent hang)
# ---------------------------------------------------------------------------

def test_prefetcher_raises_worker_failure():
    """A load_fn that dies used to leave ``take()`` blocked forever on an
    empty queue; now the failure is re-raised at the take that needs it,
    with the original exception chained."""
    t = planted_tensor((14, 11, 9), 600, seed=1)
    store = NonzeroStore.build(t, 2)
    S = store.num_strata

    def flaky(pos):
        if pos == 2:
            raise OSError("disk pulled")
        return store.stratum(pos)

    pf = StratumPrefetcher(flaky, lambda p: (p + 1) % S, depth=1)
    try:
        pf.take(0)
        pf.take(1)
        with pytest.raises(RuntimeError, match="position 2") as ei:
            pf.take(2)
        assert isinstance(ei.value.__cause__, OSError)
        # the failure is sticky until a reset-style jump reloads
        with pytest.raises(RuntimeError, match="position 2"):
            pf.take(3)
    finally:
        pf.close()


def test_prefetcher_recovers_after_reset():
    t = planted_tensor((14, 11, 9), 600, seed=1)
    store = NonzeroStore.build(t, 2)
    S = store.num_strata
    calls = {"n": 0}

    def flaky_once(pos):
        calls["n"] += 1
        if calls["n"] == 1:
            raise ValueError("transient")
        return store.stratum(pos)

    # retries=0 pins the pre-retry behavior this test locks: the FIRST
    # failure is fatal-and-sticky, and only reset() restarts the walk
    pf = StratumPrefetcher(flaky_once, lambda p: (p + 1) % S, depth=2,
                           retries=0)
    try:
        with pytest.raises(RuntimeError):
            pf.take(0)
        pf.reset(0)
        idx, _, _ = pf.take(0)
        np.testing.assert_array_equal(np.asarray(idx), store.indices[0])
    finally:
        pf.close()


def test_prefetcher_retries_transient_failure():
    """A transient load failure self-heals inside the retry budget: the
    walk never dies, the consumer never sees an exception, and the
    absorbed failures are counted."""
    t = planted_tensor((14, 11, 9), 600, seed=1)
    store = NonzeroStore.build(t, 2)
    S = store.num_strata
    fails = {0: 2, 3: 1}   # pos → number of leading failures

    def flaky(pos):
        if fails.get(pos, 0) > 0:
            fails[pos] -= 1
            raise OSError(f"transient at {pos}")
        return store.stratum(pos)

    pf = StratumPrefetcher(flaky, lambda p: (p + 1) % S, depth=2,
                           retries=2, retry_base_s=1e-4, retry_cap_s=1e-3)
    try:
        for pos in range(S):
            idx, _, _ = pf.take(pos)
            np.testing.assert_array_equal(np.asarray(idx),
                                          store.indices[pos])
        assert pf.retried == 3
        assert not any(fails.values())
    finally:
        pf.close()


def test_prefetcher_budget_exhaustion_still_fatal():
    """retries bound the healing: one more consecutive failure than the
    budget covers surfaces exactly like the old sticky-fatal path."""
    t = planted_tensor((14, 11, 9), 600, seed=1)
    store = NonzeroStore.build(t, 2)
    S = store.num_strata

    def always_bad(pos):
        if pos == 1:
            raise OSError("persistent")
        return store.stratum(pos)

    pf = StratumPrefetcher(always_bad, lambda p: (p + 1) % S, depth=1,
                           retries=1, retry_base_s=1e-4, retry_cap_s=1e-3)
    try:
        pf.take(0)
        with pytest.raises(RuntimeError, match="position 1") as ei:
            pf.take(1)
        assert isinstance(ei.value.__cause__, OSError)
    finally:
        pf.close()


def test_prefetcher_fault_plan_transfer_site():
    """A FaultPlan 'transfer' spec exercises the same retry loop as an
    organic device_put failure — two hits clear inside retries=2."""
    from repro.runtime.fault import FaultInjected, FaultPlan, FaultSpec

    t = planted_tensor((14, 11, 9), 600, seed=1)
    store = NonzeroStore.build(t, 2)
    S = store.num_strata
    plan = FaultPlan([FaultSpec("transfer", hits=frozenset({0, 1}))])
    pf = StratumPrefetcher(store.stratum, lambda p: (p + 1) % S, depth=0,
                           retries=2, retry_base_s=1e-4, retry_cap_s=1e-3,
                           fault_plan=plan)
    idx, _, _ = pf.take(0)
    np.testing.assert_array_equal(np.asarray(idx), store.indices[0])
    assert plan.fired == 2 and pf.retried == 2

    # budget below the consecutive-hit count → the injection is fatal
    plan2 = FaultPlan([FaultSpec("transfer", hits=frozenset({0, 1}))])
    pf2 = StratumPrefetcher(store.stratum, lambda p: (p + 1) % S, depth=0,
                            retries=1, retry_base_s=1e-4,
                            retry_cap_s=1e-3, fault_plan=plan2)
    with pytest.raises(FaultInjected):
        pf2.take(0)


# ---------------------------------------------------------------------------
# bench_refresh/v1 schema contract
# ---------------------------------------------------------------------------

def _refresh_doc(**row_overrides):
    r = {"dirty_fraction": 0.01, "dirty_rows": 600, "patch_ms": 2.0,
         "rebuild_ms": 20.0, "speedup": 10.0}
    r.update(row_overrides)
    return {"schema": "bench_refresh/v1", "smoke": False,
            "contract_max_fraction": 0.10, "rows": [r]}


def test_validate_bench_refresh():
    from benchmarks.bench_refresh import validate

    validate(_refresh_doc())
    # patch slower than rebuild inside the contract band must fail
    with pytest.raises(ValueError, match="beat rebuild"):
        validate(_refresh_doc(patch_ms=30.0, speedup=0.67))
    # ... but above the band a sub-1 speedup is informational only
    validate(_refresh_doc(dirty_fraction=0.25, patch_ms=30.0,
                          speedup=0.67))
    with pytest.raises(ValueError, match="schema"):
        validate({**_refresh_doc(), "schema": "bench_refresh/v0"})
    with pytest.raises(ValueError, match="rows"):
        validate({**_refresh_doc(), "rows": []})
    with pytest.raises(ValueError, match="patch_ms"):
        validate(_refresh_doc(patch_ms="fast"))


def test_committed_bench_refresh_document_validates():
    """BENCH_refresh.json at the repo root stays schema-valid — the same
    contract CI's refresh-bench smoke enforces on a fresh emission."""
    import json
    from pathlib import Path

    from benchmarks.bench_refresh import validate

    path = Path(__file__).parent.parent / "BENCH_refresh.json"
    validate(json.loads(path.read_text()))


def test_online_train_cli_in_process(monkeypatch, tmp_path):
    """The streaming driver end to end, in-process on tiny shapes: spilled
    ingest store, local-strategy refresh, a row-mode serve patch each
    round, and the CLI's own bitwise verify at the end."""
    import sys

    from repro.launch import online_train

    monkeypatch.setattr(sys, "argv", [
        "online_train", "--strategy", "local", "--dims", "16,12,10",
        "--nnz", "400", "--warmup-steps", "4", "--rounds", "2",
        "--refresh-steps", "2", "--batch", "64", "--rank", "2",
        "--core-rank", "2", "--window", "128",
        "--serve-shard-mode", "row",
        "--spill-dir", str(tmp_path / "spill"), "--verify"])
    online_train.main()


# ---------------------------------------------------------------------------
# 4-device tier: sharded delta parity + the online CLI end to end
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sharded_update_rows_bitwise_four_devices():
    """Row- and batch-sharded servers patch to the exact tables a fresh
    sharded rebuild stores — same placement, same bits."""
    run_with_devices("""
        import numpy as np, jax
        import jax.numpy as jnp
        assert jax.device_count() == 4
        from repro.core import FastTuckerConfig, FastTuckerParams
        from repro.core import fasttucker as ft
        from repro.launch.mesh import make_host_mesh
        from repro.serve import TuckerServer

        cfg = FastTuckerConfig(dims=(50, 40, 30), ranks=(4, 4, 4),
                               core_rank=3, batch_size=32)
        params = ft.init_params(jax.random.PRNGKey(0), cfg)
        mesh = make_host_mesh()
        for kind in ("row", "batch"):
            srv = TuckerServer(params, mesh=mesh, shard_mode=kind)
            rng = np.random.default_rng(2)
            facs = [np.array(f) for f in params.factors]
            for it in range(4):
                m = it % 3
                f = int(rng.integers(1, srv.dims[m] + 1))
                ids = np.sort(rng.permutation(srv.dims[m])[:f]) \\
                    .astype(np.int32)
                new = rng.standard_normal((f, 4)).astype(np.float32)
                facs[m][ids] = new
                srv.update_rows(m, ids, new)
            ref = TuckerServer(
                FastTuckerParams(tuple(jnp.asarray(f) for f in facs),
                                 params.core_factors),
                mesh=mesh, shard_mode=kind)
            for n in range(3):
                a, b = srv._tables[n], ref._tables[n]
                assert a.sharding.is_equivalent_to(b.sharding, a.ndim)
                assert (np.asarray(a) == np.asarray(b)).all(), (kind, n)
            q = np.stack([rng.integers(0, d, 17) for d in srv.dims], 1) \\
                .astype(np.int32)
            np.testing.assert_array_equal(np.asarray(srv.predict(q)),
                                          np.asarray(ref.predict(q)))
            print(kind, "OK")
    """)


@pytest.mark.slow
def test_online_train_cli_verifies():
    """The full loop — append → refresh_steps → update_rows — on a
    4-device row-sharded server, with the CLI's own bitwise verify."""
    run_with_devices("""
        import sys
        sys.argv = ["online_train", "--strategy", "strata",
                    "--dims", "24,18,12", "--nnz", "800",
                    "--warmup-steps", "6", "--rounds", "3",
                    "--refresh-steps", "2", "--batch", "64",
                    "--rank", "3", "--core-rank", "3",
                    "--serve-shard-mode", "row", "--verify"]
        from repro.launch.online_train import main
        main()
    """)
