"""Operation and byte counts against hand counts at a small shape."""
from __future__ import annotations

import pytest

from chipbench import counts

CFG = {"dims": [10, 20, 30], "ranks": [2, 3, 4], "core_rank": 5,
       "param_bytes": 4}
PEAK = {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e3}


def test_train_flops_by_hand():
    # per mode 6·J·R: 6·2·5 + 6·3·5 + 6·4·5 = 60 + 90 + 120
    assert counts.train_flops_per_nnz(CFG) == 270
    assert counts.train_step_flops(CFG, 8) == 2160


def test_train_bytes_by_hand():
    rows = 8 * (2 + 3 + 4) * 4              # gather 288
    coo = 8 * (3 * 4 + 4)                   # 128
    core = 2 * (2 + 3 + 4) * 5 * 4          # 360
    assert counts.train_step_bytes(CFG, 8) == 3 * rows + coo + core


def test_topk_by_hand():
    assert counts.topk_flush_flops(CFG, 3, 1) == 2 * 3 * 5 * 20
    assert counts.topk_flush_bytes(CFG, 3, 1) == 20 * 5 * 4 + 3 * 5 * 4


@pytest.mark.parametrize("extra", [
    {}, {"backend": "pallas"}, {"sorted_batches": True, "backend": "xla"},
    {"phase_split": True, "update_order": "gauss_seidel"}])
def test_counts_depend_only_on_shape_and_batch(extra):
    cfg = {**CFG, **extra}
    assert counts.train_step_flops(cfg, 64) == counts.train_step_flops(
        CFG, 64)
    assert counts.train_step_bytes(cfg, 64) == counts.train_step_bytes(
        CFG, 64)
    assert counts.topk_flush_bytes(cfg, 9, 2) == counts.topk_flush_bytes(
        CFG, 9, 2)


def test_least_time_names_its_bound():
    assert counts.least_time(1e6, 10, PEAK) == (1.0, "compute")
    assert counts.least_time(1.0, 2e3, PEAK) == (2.0, "memory")


def test_netflix_step_is_memory_bound():
    cfg = {"dims": [480189, 17770, 2182], "ranks": [32] * 3,
           "core_rank": 32, "param_bytes": 4}
    assert counts.train_flops_per_nnz(cfg) == 18432
    v5e = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = counts.least_time(counts.train_step_flops(cfg, 4096),
                                 counts.train_step_bytes(cfg, 4096), v5e)
    assert bound == "memory" and 5e-6 < t < 7e-6
