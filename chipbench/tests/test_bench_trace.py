"""The trace reduction on a small synthetic trace (no chip needed)."""
from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

from chipbench import harness, trace


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def planes(ops0, ops1=(), host=()):
    out = [NS(name="/device:TPU:0",
              lines=[NS(name="XLA Ops", events=list(ops0)),
                     NS(name="XLA Modules",
                        events=[ev("module", 0, 10_000)])]),
           NS(name="/host:CPU",
              lines=[NS(name="python3", events=list(host))])]
    if ops1:
        out.append(NS(name="/device:TPU:1",
                      lines=[NS(name="XLA Ops", events=list(ops1))]))
    return out


OVERLAPPING = [ev("a", 0, 100), ev("b", 50, 100),    # overlap: 0..150
               ev("c", 120, 10),                       # nested in b
               ev("d", 300, 100),                      # gap 150..300
               ev("e", 900, 100)]                      # gap 400..900


def test_busy_is_the_union_of_op_intervals():
    out = trace.reduce(planes(OVERLAPPING), "tpu", window_s=2e-6)
    assert out["busy_s"] == pytest.approx(350e-9)      # 150 + 100 + 100
    idle = 1 - out["busy_s"] / out["window_s"]
    assert idle == pytest.approx(1 - 350 / 2000)
    reader = harness.load_reader("device_idle_share.train")
    assert reader({"traced": out}) == pytest.approx(100 * (1 - 350 / 2000))


def test_modules_line_and_other_planes_are_not_ops():
    only_module = planes([])
    assert trace.reduce(only_module, "tpu", 1.0)["busy_s"] is None


def test_busy_is_averaged_over_chips():
    out = trace.reduce(planes(OVERLAPPING, [ev("x", 0, 50)]), "tpu", 1.0)
    assert out["busy_s"] == pytest.approx((350e-9 + 50e-9) / 2)


def test_device_time_per_step():
    out = trace.reduce(planes(OVERLAPPING), "tpu", window_s=2e-6)
    out["count"] = 7
    step_ms = harness.load_reader("step_device_ms")({"traced": out})
    assert step_ms == pytest.approx(350e-9 / 7 * 1e3)


def test_breakdown_names_ops_and_gaps():
    host = [ev("chipbench.evaluate", 350, 600), ev("outer", 0, 2000)]
    out = trace.reduce(planes(OVERLAPPING, host=host), "tpu", 2e-6)
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["b"] == pytest.approx(100e-9) and len(ops) == 5
    gaps = out["breakdown"]["idle_gaps"]
    assert gaps[0] == ["chipbench.evaluate", pytest.approx(500e-9)]
    assert gaps[1] == ["outer", pytest.approx(150e-9)]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no entry"):
        harness.load_peaks("TPU v99 imaginary")
    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
