"""Held-out evaluation's lane-dense row lookup: ``LaneDenseTable`` returns
the rows ``A[ids]`` does, bit for bit; ``rmse_mae`` as one program gives
what the per-chunk loop gave; and the program packs each table once."""
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cutucker as cu
from repro.core import fasttucker as ft
from repro.core.metrics import (
    LANES, LaneDenseTable, _held_out_err, _padded_width, rmse_mae,
)
from repro.core.sptensor import SparseTensor

ROWS = 103                      # a multiple of no slot count p > 1
DIMS = (37, 23, 11)


def _ids(rows, n=64, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(np.r_[0, rows - 1, rng.integers(0, rows, n)],
                       jnp.int32)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("width", [32, 8, 5, 48])
def test_lookup_returns_the_rows_bitwise(width, dtype):
    table = jax.random.normal(jax.random.PRNGKey(width), (ROWS, width))
    table = table.at[ROWS - 1, 0].set(-0.0).astype(dtype)
    packed = LaneDenseTable.pack(table)
    wide = _padded_width(width)
    assert packed.lines.shape == (-(-ROWS // (LANES // wide)), LANES)
    ids = _ids(ROWS)
    got, want = np.asarray(packed[ids]), np.asarray(table[ids])
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got.view(np.uint8), want.view(np.uint8))


@pytest.mark.parametrize("width,wide", [(1, 1), (5, 8), (32, 32), (48, 64),
                                        (128, 128), (130, 256)])
def test_padded_width_tiles_a_line(width, wide):
    assert _padded_width(width) == wide
    table = jnp.arange(7 * width, dtype=jnp.float32).reshape(7, width)
    ids = jnp.arange(7, dtype=jnp.int32)
    np.testing.assert_array_equal(np.asarray(LaneDenseTable.pack(table)[ids]),
                                  np.asarray(table))


@partial(jax.jit, static_argnames=("predict_fn",))
def _chunk_sums(params, idx, val, predict_fn):
    err = predict_fn(params, idx) - val
    return jnp.sum(err**2), jnp.sum(jnp.abs(err))


def _per_chunk(params, test, predict_fn, chunk):
    """The evaluation as one program per chunk, summed on the host."""
    se = ae = 0.0
    for s in range(0, test.nnz, chunk):
        a, b = _chunk_sums(params, test.indices[s:s + chunk],
                           test.values[s:s + chunk], predict_fn)
        se, ae = se + float(a), ae + float(b)
    return np.sqrt(se / test.nnz), ae / test.nnz


def _model(kind):
    key = jax.random.PRNGKey(3)
    if kind == "fasttucker":
        cfg = ft.FastTuckerConfig(dims=DIMS, ranks=(5, 8, 32), core_rank=4)
        return ft.init_params(key, cfg), ft.predict
    cfg = cu.CuTuckerConfig(dims=DIMS, ranks=(5, 8, 6))
    return cu.init_params(key, cfg), cu.predict


def _test_set(nnz, seed=1):
    rng = np.random.default_rng(seed)
    idx = np.stack([rng.integers(0, d, nnz) for d in DIMS], 1)
    return SparseTensor(jnp.asarray(idx, jnp.int32),
                        jnp.asarray(rng.random(nnz), jnp.float32), DIMS)


@pytest.mark.parametrize("nnz,chunk", [(1000, 300), (200, 4096),
                                       (900, 300)])
@pytest.mark.parametrize("kind", ["fasttucker", "cutucker"])
def test_rmse_mae_matches_the_per_chunk_loop(kind, nnz, chunk):
    params, predict_fn = _model(kind)
    test = _test_set(nnz)
    rmse, mae = rmse_mae(params, test, predict_fn, chunk=chunk)
    want = _per_chunk(params, test, predict_fn, chunk)
    np.testing.assert_allclose([float(rmse), float(mae)], want, rtol=1e-6)


def test_params_without_factor_tables_take_the_plain_path():
    params, _ = _model("fasttucker")
    plain = (params.factors, params.core_factors)

    def predict_fn(p, idx):
        return ft.predict(ft.FastTuckerParams(*p), idx)

    test = _test_set(500)
    got = rmse_mae(plain, test, predict_fn, chunk=128)
    want = rmse_mae(params, test, ft.predict, chunk=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def _op_paths(text):
    """(op line, scope path) of every op of a lowered program's text."""
    names = {}
    for alias, body in re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, re.M):
        quoted = re.match(r'"([^"]*)"', body)
        names[alias] = quoted.group(1) if quoted else ""
    out = []
    for line in text.splitlines():
        m = re.search(r"= (stablehlo\.\w+).*loc\((#loc\d+)\)\s*$", line)
        if m:
            out.append((line, names.get(m.group(2), "")))
    return out


def test_evaluation_packs_each_table_once():
    params, _ = _model("fasttucker")
    test = _test_set(1000)
    text = _held_out_err.lower(params, test.indices, test.values,
                               predict_fn=ft.predict, chunk=300
                               ).as_text(debug_info=True)
    packs = [line for line, path in _op_paths(text)
             if "/lane_pack/" in path and "stablehlo.reshape" in line]
    want = sorted(LaneDenseTable.pack(f).lines.shape for f in params.factors)
    got = sorted(tuple(int(d) for d in
                       re.search(r"-> tensor<(\d+)x(\d+)x", line).groups())
                 for line in packs)
    assert got == want
    # the pack sits under the evaluation's scope and outside its loop
    assert all(path.count("repro.eval.chunk/lane_pack/") == 1
               and "while" not in path
               for line, path in _op_paths(text) if "lane_pack" in path)
