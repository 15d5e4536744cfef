"""A configuration of any tensor order runs through the harness, its
check fails the control and the planted faults at that order, and one
whose ``ranks`` do not match its ``dims`` is refused.

The program takes the order from ``dims`` and the plain reference takes
its initial scale from ``ranks``: a configuration whose two lengths
differ would train one tensor and check it against the start of another.
"""
from __future__ import annotations

import json
import shutil
import time

import pytest

from chipbench import harness

from . import tiny

CELL = "netflix.train"


def of_order(n: int) -> dict:
    """``netflix.json`` as an order-``n`` tensor, every mode alike."""
    cfg = harness.load_config("netflix")
    cfg.update(name=f"netflix_order{n}", dims=[2_000] * n, ranks=[32] * n)
    return cfg


@pytest.mark.parametrize("order", [4, 10])
@pytest.mark.parametrize("trace", [False, True])
def test_order_n_training_rehearsal_is_correct(order, trace):
    cfg = of_order(order)
    shrunk, _ = tiny.shrink(CELL, cfg)
    assert len(shrunk["dims"]) == len(shrunk["ranks"]) == order
    assert shrunk["ranks"] == cfg["ranks"]
    assert shrunk["core_rank"] == cfg["core_rank"]
    out = tiny.run(CELL, trace=trace, cfg=cfg)
    assert out["correct"] is True, out["checks"]
    assert 0 <= out["failed"] < out["attempted"]
    if trace:
        assert out["device"]["busy_s"] > 0


@pytest.mark.parametrize("order", [4, 10])
@pytest.mark.parametrize("options", tiny.FAULTS[("train", None)],
                         ids=lambda o: str(next(iter(o.values()))))
def test_order_n_fault_or_control_reads_not_correct(order, options):
    """The check still separates at order ``n``: the control and each
    planted fault exceed a limit, as they do at order 3."""
    out = tiny.run(CELL, cfg=of_order(order), **options)
    assert out["correct"] is False
    assert tiny.over_limits(out), out["checks"]


@pytest.mark.parametrize("workload",
                         [w["name"] for w in tiny.bench()["workloads"]])
def test_cells_keep_their_order_and_tiny_tensor(workload):
    wl = harness.workload_entry(tiny.bench(), workload)
    order = len(harness.load_config(wl["config"])["dims"])
    cfg, _ = tiny.shrink(workload)
    assert len(cfg["dims"]) == len(cfg["ranks"]) == order
    if order == 3:
        assert cfg["dims"] == [300, 200, 40]
    assert (cfg["train_nnz"], cfg["test_nnz"]) == (40_000, 3_000)


def bad_config() -> dict:
    cfg = harness.load_config("netflix")
    cfg.update(name="netflix_misranked", ranks=[32, 32])
    return cfg


def test_load_config_refuses_ranks_that_miss_a_mode(tmp_path, monkeypatch):
    shutil.copytree(harness.HERE / "configs", tmp_path / "configs")
    (tmp_path / "configs" / "netflix_misranked.json").write_text(
        json.dumps(bad_config()))
    monkeypatch.setattr(harness, "HERE", tmp_path)
    assert harness.load_config("netflix")["dims"]
    with pytest.raises(ValueError, match=r"'netflix_misranked'.* 2 .* 3 "):
        harness.load_config("netflix_misranked")


def test_run_cell_refuses_ranks_that_miss_a_mode():
    with pytest.raises(ValueError, match=r"'netflix_misranked'.* 2 .* 3 "):
        harness.run_cell(CELL, tiny.SEED, 0.4, False, time.perf_counter(),
                         bench=tiny.bench(), peaks=tiny.CPU_PEAKS,
                         cfg=bad_config())
