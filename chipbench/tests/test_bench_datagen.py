"""The on-device generator at a tiny shape."""
from __future__ import annotations

import numpy as np

from chipbench import datagen

CFG = {"dims": [50, 40, 7], "train_nnz": 5000, "test_nnz": 300,
       "ranks": [4, 4, 4], "core_rank": 4,
       "data": {"seed": 3, "planted_rank": 3, "noise": 0.1,
                "min_value": 1.0,
                "max_value": 5.0}}


def gen(seed):
    cfg = {**CFG, "data": {**CFG["data"], "seed": seed}}
    return [np.asarray(x) for x in datagen.ratings(cfg)]


def test_same_seed_same_tensor_and_seeds_differ():
    a, b, c = gen(5), gen(5), gen(6)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0], c[0])
    assert not np.array_equal(a[1], c[1])


def test_seeds_past_32_bits_differ():
    big = 2 ** 32 + 5
    assert not np.array_equal(gen(big)[0], gen(5)[0])


def test_values_in_range_and_indices_in_bounds():
    tri, trv, tei, tev = gen(2 ** 31 + 3)
    assert tri.shape == (5000, 3) and tei.shape == (300, 3)
    for idx in (tri, tei):
        assert idx.dtype == np.int32
        assert (idx >= 0).all() and (idx < np.asarray(CFG["dims"])).all()
    for v in (trv, tev):
        assert v.dtype == np.float32 and np.isfinite(v).all()
        assert v.min() >= 1.0 and v.max() <= 5.0
    # every row of every mode is reachable: the marginals are uniform
    assert len(np.unique(tri[:, 2])) == CFG["dims"][2]


def test_quantile_bisection_matches_numpy():
    v = np.random.default_rng(0).normal(size=4096).astype(np.float32)
    for q in (0.01, 0.5, 0.99):
        got = float(datagen.quantile(v, q))
        want = np.sort(v)[int(np.ceil(q * v.size)) - 1]
        assert abs(got - want) <= 1e-5 * max(1.0, abs(want))


def test_serving_factors_shapes():
    f, c = datagen.serving_factors(datagen.seed_key(1), CFG)
    assert [x.shape for x in f] == [(50, 4), (40, 4), (7, 4)]
    assert [x.shape for x in c] == [(4, 4)] * 3
