"""The synthetic generators draw distinct in-range index tuples at every
order of the paper's synthesis set, and a tensor whose flat index fits in
int64 is drawn bit for bit as before."""
import os
import subprocess
import sys

import numpy as np
import pytest

from helpers import REPO
from repro.data import synthetic
from repro.data.synthetic import planted_tensor, synthesis_suite


def _flat_draw(rng, dims, nnz):
    """The flat-index draw as it stood before tensors past 2^63 cells
    were supported, kept here to pin the draw of those below."""
    dims = np.asarray(dims, dtype=np.int64)
    total = np.prod(dims.astype(object))
    flat = rng.integers(0, int(total), size=int(nnz * 1.2), dtype=np.int64)
    flat = np.unique(flat)[:nnz]
    while len(flat) < nnz:
        extra = rng.integers(0, int(total), size=nnz, dtype=np.int64)
        flat = np.unique(np.concatenate([flat, extra]))[:nnz]
    idx = np.zeros((nnz, len(dims)), dtype=np.int32)
    rem = flat
    for n in range(len(dims)):
        idx[:, n] = rem % dims[n]
        rem = rem // dims[n]
    return idx


def _assert_distinct_in_range(idx, dims, nnz):
    idx = np.asarray(idx)
    assert idx.shape == (nnz, len(dims))
    assert (idx >= 0).all() and (idx < np.asarray(dims)).all()
    assert len(np.unique(idx, axis=0)) == nnz


@pytest.mark.parametrize("dims,nnz,seed", [
    ((18, 15, 12), 2500, 0),
    ((4, 4, 4), 60, 1),                  # most cells: the redraw runs
    ((2000,) * 5, 3000, 2),
    ((2,) * 63, 500, 3),                 # exactly 2^63 cells
])
def test_draw_below_2_63_cells_is_unchanged(dims, nnz, seed):
    got = synthetic._unique_indices(np.random.default_rng(seed), dims, nnz)
    want = _flat_draw(np.random.default_rng(seed), dims, nnz)
    np.testing.assert_array_equal(got, want)
    t = planted_tensor(dims, nnz, seed=seed)
    np.testing.assert_array_equal(np.asarray(t.indices), want)


def test_order10_tensor_of_the_paper_draws_distinct_rows():
    dims = (10_000,) * 10
    t = planted_tensor(dims, 20_000, rank=4, core_rank=4, seed=5)
    _assert_distinct_in_range(t.indices, dims, 20_000)
    assert t.dims == dims
    assert np.isfinite(np.asarray(t.values)).all()
    again = planted_tensor(dims, 20_000, rank=4, core_rank=4, seed=5)
    np.testing.assert_array_equal(np.asarray(t.indices),
                                  np.asarray(again.indices))
    # every mode's coordinate is drawn over its whole range
    idx = np.asarray(t.indices)
    assert (idx.max(0) > 9_000).all() and (idx.min(0) < 1_000).all()


def test_per_mode_draw_drops_duplicates_and_redraws():
    """With few cells most draws repeat: every row kept is still distinct
    and the count is made up."""
    dims = (3, 3, 3)
    idx = synthetic._unique_rows_per_mode(np.random.default_rng(0), dims, 27)
    _assert_distinct_in_range(idx, dims, 27)


def test_synthesis_suite_every_order_at_a_small_scale():
    suite = synthesis_suite(scale=1e-4, seed=0)
    assert sorted(suite) == sorted(f"order{k}" for k in range(3, 11))
    for name, t in suite.items():
        assert len(t.dims) == int(name[len("order"):])
        _assert_distinct_in_range(t.indices, t.dims, t.nnz)
    # from order 6 up the cells overflow int64 at this scale
    assert np.prod(np.asarray(suite["order10"].dims, dtype=object)) > 2 ** 63


def test_std_train_runs_the_order10_tensor():
    dims = ",".join(["10000"] * 10)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.std_train", "--dims", dims,
         "--nnz", "3000", "--steps", "4", "--batch", "128", "--rank", "4",
         "--core-rank", "4", "--eval-every", "2"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": str(REPO / "src")})
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "step 4 rmse" in proc.stderr
    assert "local done" in proc.stderr
