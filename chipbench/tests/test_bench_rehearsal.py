"""Rehearsal of the harness on the CPU: BENCHMARK.json, lookups by name,
and every mix run end to end at a tiny size through the harness's own
functions.  The command itself still refuses a machine without a TPU."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import harness, traffic

from . import tiny

BENCH = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def test_names_and_units_use_allowed_characters():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [w["name"] for w in BENCH["workloads"]]
    names += [c["name"] for c in BENCH["configs"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    for kind in ("end_to_end", "per_layer", "workloads", "configs"):
        listed = [x["name"] for x in BENCH[kind]]
        assert len(listed) == len(set(listed)), kind
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")


def test_per_layer_metrics_move_one_reported_end_to_end_metric():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        for w in m.get("workloads", []):
            assert any(x["name"] == m["moves"]
                       for x in harness.end_to_end_for(BENCH, w)), (m, w)
    for w in BENCH["workloads"]:
        reported = harness.end_to_end_for(BENCH, w["name"])
        assert any(m["name"] == "setup_s" for m in reported)
        assert len(reported) >= 2
        assert harness.per_layer_for(BENCH, w["name"])


def test_configs_mixes_limits_and_readers_are_found_by_name():
    for c in BENCH["configs"]:
        cfg = harness.load_config(c["name"])
        assert Path(harness.ROOT / c["file"]) == (
            harness.HERE / "configs" / f"{c['name']}.json")
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
    for w in BENCH["workloads"]:
        mix = harness.load_mix(w["traffic"])
        assert callable(harness.driver(mix["kind"]))
        if mix["kind"] == "serve":
            assert callable(harness.load_module(
                "arrivals", mix["arrivals"]["process"]).due_times)
            assert callable(harness.load_module(
                "sizes", mix["size"]["dist"]).sizes)
        assert harness.load_limits(w["name"]), w["name"]
    for m in BENCH["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
    with pytest.raises(FileNotFoundError):
        harness.load_mix("no_such_mix")
    with pytest.raises(ValueError):
        harness.load_config("../BENCHMARK")


def test_every_mix_file_has_a_cell():
    mixes = sorted(p.stem for p in (harness.HERE / "mixes").glob("*.json"))
    assert mixes == sorted({w["traffic"] for w in BENCH["workloads"]})


@pytest.mark.parametrize("workload", CELLS + sorted(tiny.EXTRA))
@pytest.mark.parametrize("trace", [False, True])
def test_every_mix_runs_end_to_end(workload, trace, capsys):
    out = tiny.run(workload, trace=trace)
    harness.print_result(out)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    want = KEYS + (["breakdown"] if trace else []) + [
        "compiles_in_window", "checks"]
    assert list(line) == want
    assert line["correct"] is True, line["checks"]
    # a loaded CPU may shed a request past its deadline: that is counted,
    # not wrong
    assert 0 <= line["failed"] < line["attempted"]
    bench = tiny.bench()
    if trace:
        expect = {m["name"] for m in harness.per_layer_for(bench, workload)}
        assert set(line["metrics"]) <= expect and line["metrics"]
        assert line["device"]["busy_s"] > 0
    else:
        expect = {m["name"] for m in harness.end_to_end_for(bench, workload)}
        assert set(line["metrics"]) == expect
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}


def test_the_command_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(harness.HERE / "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=harness.ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_a_new_arrival_process_is_a_new_file(tmp_path, monkeypatch):
    """A mix that names an arrival process the harness has never seen
    runs once its file is there: no file of the harness changes."""
    for sub in ("arrivals", "sizes"):
        shutil.copytree(harness.HERE / sub, tmp_path / sub)
    (tmp_path / "arrivals" / "evenly.py").write_text(
        "import numpy as np\n\n\n"
        "def due_times(params, n, seconds, rng):\n"
        "    return (np.arange(n) + 0.5) * seconds / n\n")
    mix = harness.load_mix("serve_topk_open")
    mix["arrivals"] = {"process": "evenly", "rate_per_s": 10}
    cfg = harness.load_config("yahoo_music")
    with pytest.raises(FileNotFoundError):
        traffic.schedule(mix, cfg, 3, 2.0)
    monkeypatch.setattr(harness, "HERE", tmp_path)
    due = [t for t, _ in traffic.schedule(mix, cfg, 3, 2.0)]
    assert np.allclose(due, (np.arange(20) + 0.5) / 10)


@pytest.mark.parametrize("arrivals", [
    {"process": "poisson", "rate_per_s": 400},
    {"process": "onoff", "rate_per_s": 400, "on_s": 0.25, "off_s": 0.5}])
def test_arrivals_keep_count_and_window(arrivals):
    mix = dict(harness.load_mix("serve_topk_open"), arrivals=arrivals)
    cfg = harness.load_config("yahoo_music")
    seed = 2 ** 31 + 5
    a = traffic.schedule(mix, cfg, seed, 3.0)
    b = traffic.schedule(mix, cfg, seed, 3.0)
    c = traffic.schedule(mix, cfg, seed + 1, 3.0)
    due = np.array([t for t, _ in a])
    assert len(a) == len(c) == 1200
    assert np.array_equal(due, [t for t, _ in b])
    assert not np.array_equal(due, [t for t, _ in c])
    assert (np.diff(due) >= 0).all() and 0 <= due[0] and due[-1] <= 3.0
    if arrivals["process"] == "onoff":
        assert (due % 0.75 <= 0.25).all()


def test_time_to_target_is_interpolated_between_evaluations():
    train = harness.load_module("kinds", "train")
    t, s = train.crossing((1.0, 100, 1.0), (2.0, 150, 0.9), 0.975)
    assert t == pytest.approx(1.25) and s == pytest.approx(112.5)
    t, s = train.crossing((1.0, 100, 1.0), (2.0, 150, 0.9), 0.9)
    assert t == pytest.approx(2.0) and s == pytest.approx(150)
