"""``exclusive_products_share``: device time of the ops whose scope path
holds ``exclusive_products`` (nested and overlapping ops counted once,
averaged over devices) over the traced busy time, read from hand-built
traces; and the program's real op paths, which keep their enclosing
``repro.*`` scope for the readers that were there before."""
from __future__ import annotations

import gzip
import json
import re
import time

import jax
import numpy as np
import pytest

from chipbench import harness, oppaths, scopes

from . import tiny
from .test_bench_scopes import meta, op, tpu

CELL = "synth_order10.train"
EVAL = "jit(_held_out_err)/repro.eval.chunk/while/body/closed_call/"
STEP = "jit(core_step)/jit(sgd_step)/repro.step.grad/"
EXCL = "exclusive_products/"
OPS = [op(0, 0, 100, EVAL + EXCL + "rev"),
       op(0, 50, 100, EVAL + EXCL + "jit(cumprod)/" + EXCL
          + "reduce_window"),                                 # 0..150
       op(0, 20, 10, STEP + EXCL + "mul"),                    # nested
       op(0, 200, 40, STEP + "jvp(exclusive_products)/mul"),  # 200..240
       op(0, 300, 100, EVAL + "gather"),                      # outside
       op(0, 400, 50, STEP + "not_exclusive_products/mul"),   # outside
       op(0, 450, 30)]                                        # no tf_op


def test_component_is_a_whole_part_of_the_path():
    assert oppaths.has_component(EVAL + EXCL + "rev", "exclusive_products")
    assert oppaths.has_component("a/transpose(jvp(exclusive_products))/b",
                                 "exclusive_products")
    assert oppaths.has_component("exclusive_products", "exclusive_products")
    assert not oppaths.has_component(EVAL + "gather", "exclusive_products")
    assert not oppaths.has_component("a/exclusive_products_x/b",
                                     "exclusive_products")
    assert not oppaths.has_component(None, "exclusive_products")


def test_component_ns_is_the_union_of_its_ops():
    assert oppaths.component_ns(tpu(0, OPS), "exclusive_products") == 190.0


def test_component_ns_is_averaged_over_devices():
    events = tpu(0, OPS) + tpu(1, [op(1, 0, 50, STEP + EXCL + "rev"),
                                   op(1, 60, 30, EVAL + "gather")])
    got = oppaths.component_ns(events, "exclusive_products")
    assert got == pytest.approx((190 + 50) / 2)


def test_the_cpu_client_threads_are_read_as_scopes_reads_them():
    events = [meta(7, "/host:CPU"), meta(7, "tf_XLAPjRtCpuClient/1", 11),
              meta(7, "python3", 12),
              op(7, 0, 100, EVAL + EXCL + "rev", tid=11),
              op(7, 0, 999, EVAL + EXCL + "rev", tid=12)]
    assert oppaths.component_ns(events, "exclusive_products") == 100.0


def test_scoped_still_files_the_products_under_the_enclosing_scope():
    got = scopes.scoped(tpu(0, OPS))
    assert set(got) == {"repro.eval.chunk", "repro.step.grad",
                        scopes.UNSCOPED}
    assert got["repro.eval.chunk"] == 250.0     # 0..150 and 300..400
    assert got["repro.step.grad"] == 100.0      # 20..30, 200..240, 400..450


def write_trace(directory, events):
    """A run's trace as the profiler leaves it: the ``.xplane.pb`` that
    ``scopes.newest_trace`` finds and the events beside it."""
    directory.mkdir(parents=True)
    (directory / "vm.xplane.pb").write_bytes(b"")
    with gzip.open(directory / "vm.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)


def read(run):
    return harness.load_reader("exclusive_products_share")(run)


def test_the_reader_reads_the_share_of_busy_time(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "RUNS", tmp_path)
    run = {"traced": {"t0": time.perf_counter() - 1.0, "busy_s": 1e-6,
                      "count": 4}}
    write_trace(tmp_path / CELL / "trace", tpu(0, OPS))
    assert read(run) == pytest.approx(19.0)
    # read once, then kept
    assert run["traced"]["components"] == {"exclusive_products": 190.0}


def test_the_reader_finds_nothing_in_a_program_without_the_name(
        tmp_path, monkeypatch):
    """The parent's program has no such scope: no number, and no error;
    an untraced run and a run whose trace is not found give none."""
    monkeypatch.setattr(harness, "RUNS", tmp_path)
    t0 = time.perf_counter() - 1.0
    write_trace(tmp_path / CELL / "trace", tpu(0, [op(0, 0, 100,
                                                      EVAL + "rev")]))
    assert read({"traced": {"t0": t0, "busy_s": 1e-6}}) is None
    assert read({"traced": None}) is None
    later = {"traced": {"t0": time.perf_counter() + 5.0, "busy_s": 1e-6}}
    assert read(later) is None


@pytest.mark.parametrize("order", [3, 10])
def test_real_evaluation_paths_keep_their_scope(order):
    """The program's compiled evaluation: the products' op paths hold the
    component, and ``scope_of`` still gives ``repro.eval.chunk``."""
    from repro.core import FastTuckerConfig
    from repro.core import fasttucker as ft
    from repro.core.metrics import _held_out_err

    dims = (7, 5, 4) if order == 3 else (4,) * order
    cfg = FastTuckerConfig(dims=dims, ranks=(3,) * order, core_rank=3,
                           batch_size=16)
    rng = np.random.default_rng(0)
    idx = np.stack([rng.integers(0, d, 40) for d in dims], 1)
    text = _held_out_err.lower(
        ft.init_params(jax.random.PRNGKey(0), cfg),
        jax.numpy.asarray(idx, np.int32),
        jax.numpy.asarray(rng.random(40), np.float32),
        predict_fn=ft.predict, chunk=16).compile().as_text()
    paths = [p for p in re.findall(r'op_name="([^"]*)"', text)
             if oppaths.has_component(p, "exclusive_products")]
    assert paths
    assert {scopes.scope_of(p) for p in paths} == {"repro.eval.chunk"}


def test_the_order10_cell_runs_every_rehearsal_fault_and_control():
    from . import test_bench_faults, test_bench_rehearsal

    assert CELL in test_bench_rehearsal.CELLS
    cases = [o for w, o in test_bench_faults.CASES if w == CELL]
    assert cases == tiny.FAULTS[("train", None)]
    cfg, _ = tiny.shrink(CELL)
    assert len(cfg["dims"]) == 10
