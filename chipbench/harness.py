"""Runs one cell of ``BENCHMARK.json`` and builds its result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

* ``configs/<config>.json``   sizes, ranks, batch, hyper-parameters, the
                              RMSE target and the plain reference's facts;
* ``mixes/<traffic>.json``    parameters for the driver of its ``kind``,
                              ``kinds/<kind>.py`` (``run(ctx)``); a
                              serving mix names its arrival process
                              (``arrivals/<name>.py``) and size
                              distribution (``sizes/<name>.py``), which
                              ``traffic.py`` reads;
* ``limits/<workload>.json``  the limit of each number the check compares;
* ``metrics/<metric>.py``     a reader ``read(run) -> float | None`` of one
                              per-layer metric.

A cell is added with new files of these kinds and a ``workloads`` entry;
no file here changes.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import resource
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Callable

import jax

from . import trace as trace_mod

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
RUNS = HERE / "_runs"
NAME_CHARS = set("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"
                 "0123456789_.-")

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def _by_name(kind: str, name: str, suffix: str) -> Path:
    if not name or set(name) - NAME_CHARS or name[0] in ".-":
        raise ValueError(f"bad {kind} name {name!r}")
    path = HERE / kind / f"{name}{suffix}"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    return path


def check_config(name: str, cfg: dict) -> dict:
    """``cfg`` if its ``ranks`` give one entry per mode of ``dims``; a
    configuration that does not is refused, naming it: the program takes
    the order from ``dims`` and the reference from ``ranks``."""
    dims, ranks = cfg["dims"], cfg["ranks"]
    if len(ranks) != len(dims):
        raise ValueError(
            f"configuration {name!r}: ranks has {len(ranks)} entries for "
            f"the {len(dims)} modes of dims")
    return cfg


def load_config(name: str) -> dict:
    return check_config(name, load_json(_by_name("configs", name, ".json")))


def load_mix(name: str) -> dict:
    return load_json(_by_name("mixes", name, ".json"))


def load_limits(workload: str) -> dict:
    try:
        return load_json(_by_name("limits", workload, ".json"))
    except FileNotFoundError:
        return {}


_MODULES: dict = {}


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` as a module (loaded once per file)."""
    path = _by_name(kind, name, ".py")
    if path not in _MODULES:
        spec = importlib.util.spec_from_file_location(
            f"chipbench_{kind}_" + name.replace(".", "_").replace("-", "_"),
            path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _MODULES[path] = module
    return _MODULES[path]


def load_reader(metric: str) -> Callable[[dict], float | None]:
    return load_module("metrics", metric).read


def load_peaks(kind: str, table: dict | None = None) -> dict:
    """The chip's published peaks; a ``device_kind`` not in the table is
    an error, never a default."""
    table = load_json(HERE / "peaks.json") if table is None else table
    if kind not in table:
        raise KeyError(f"device_kind {kind!r} has no entry in peaks.json")
    return table[kind]


def driver(kind: str):
    """``run(ctx)`` of the mix's kind, ``kinds/<kind>.py``."""
    return load_module("kinds", kind).run


def enter(chips: int) -> bool:
    """The entry of every command that drives a cell on the chip: the
    program (``src/``) on the path, a TPU with ``chips`` chips or more,
    the program's compile cache kept for every program (most steps
    compile in about a second, the default threshold).  False, with the
    reason on standard error, where the machine or checkout lacks one."""
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"needs {chips} TPU chip(s), found {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        return False
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"the program is not at {src}", file=sys.stderr)
        return False
    sys.path.insert(0, str(src))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.runtime.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}", file=sys.stderr)
    return True


# (time, program) of every lowering and compile in this process
COMPILE_LOG: list = []
_listening = False


def _on_event(event: str, duration: float, fun_name: str = "?", **_):
    if event in COMPILE_EVENTS:
        COMPILE_LOG.append((time.perf_counter(), fun_name))


def listen_compiles() -> None:
    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _listening = True


def workload_entry(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def end_to_end_for(bench: dict, name: str) -> list[dict]:
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or name in m["workloads"]]


def per_layer_for(bench: dict, name: str) -> list[dict]:
    e2e = {m["name"] for m in end_to_end_for(bench, name)}
    return [m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


class Tracer:
    """Starts and stops the profiler around the traced part of a window;
    ``stop`` returns the reduction of what it recorded."""

    def __init__(self, directory: Path, platform: str):
        self.directory = directory
        self.platform = platform
        self.active = False
        self._t0 = self._n0 = None

    def start(self, count: int) -> None:
        shutil.rmtree(self.directory, ignore_errors=True)
        jax.profiler.start_trace(str(self.directory))
        self.active = True
        self._n0 = count
        self._t0 = time.perf_counter()

    def stop(self, count: int) -> dict | None:
        if not self.active:
            return None
        window_s = time.perf_counter() - self._t0
        jax.profiler.stop_trace()
        self.active = False
        out = trace_mod.reduce_dir(self.directory, self.platform, window_s)
        out["count"] = count - self._n0
        out["t0"] = self._t0
        return out


class HostWatch:
    """What the host did in the window besides the run's own work: the
    collector's passes (count by generation and the longest, in ms), the
    process's CPU seconds against the wall's, and its context switches
    and page faults."""

    def __init__(self):
        self.gc = []
        self._t = None
        gc.callbacks.append(self._on_gc)
        self.r0 = resource.getrusage(resource.RUSAGE_SELF)
        self.cpu0, self.wall0 = time.process_time(), time.perf_counter()

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.gc.append((info["generation"], time.perf_counter() - self._t))

    def stop(self) -> dict:
        gc.callbacks.remove(self._on_gc)
        r1 = resource.getrusage(resource.RUSAGE_SELF)
        return {
            "wall_s": round(time.perf_counter() - self.wall0, 3),
            "cpu_s": round(time.process_time() - self.cpu0, 3),
            "gc_passes": [sum(g == k for g, _ in self.gc) for k in range(3)],
            "gc_longest_ms": round(1e3 * max((d for _, d in self.gc),
                                             default=0.0), 3),
            **{k: getattr(r1, "ru_" + k) - getattr(self.r0, "ru_" + k)
               for k in ("nvcsw", "nivcsw", "minflt", "majflt")}}


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell's files, the run's options, and the
    harness's clocks, tracer and log."""

    workload: str
    cfg: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    t_process: float
    devices: list
    fault: str | None = None
    variant: dict = dataclasses.field(default_factory=dict)
    check_dtype: str = "float32"
    t_window: float | None = None
    t_window_end: float | None = None
    host: dict | None = None

    def log(self, msg: str) -> None:
        print(f"[{self.workload}] {msg}", file=sys.stderr, flush=True)

    def tracer(self) -> Tracer:
        return Tracer(RUNS / self.workload / "trace",
                      self.devices[0].platform)

    def window_start(self) -> None:
        """Start the window's clock.  What set-up made (data, schedule,
        compiled programs) is moved out of the garbage collector's sight,
        so that its passes in the window scan only what the window makes."""
        gc.collect()
        gc.freeze()
        self._host = HostWatch()
        self.t_window = time.perf_counter()

    def window_end(self) -> None:
        self.t_window_end = time.perf_counter()
        gc.unfreeze()
        self.host = self._host.stop()
        self.log(f"host in the window: {self.host}")

    def compiles_in_window(self) -> list[str]:
        """Names of the programs lowered or compiled inside the window."""
        lo, hi = self.t_window, self.t_window_end
        return [name for t, name in COMPILE_LOG
                if lo is not None and lo <= t <= (hi or math.inf)]

    def memory_peak(self) -> int:
        peak = 0
        for d in self.devices:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        return peak


def context(bench: dict, workload: str, seed: int, seconds: float,
            trace: bool, t_process: float, *, cfg: dict | None = None,
            mix: dict | None = None, **options) -> Context:
    """The cell's files (or ``cfg``/``mix`` in their place), its chips,
    and the compile listener."""
    wl = workload_entry(bench, workload)
    cfg = (load_config(wl["config"]) if cfg is None
           else check_config(cfg.get("name", wl["config"]), cfg))
    mix = load_mix(wl["traffic"]) if mix is None else mix
    listen_compiles()
    return Context(workload, cfg, mix, int(seed), float(seconds),
                   bool(trace), t_process, jax.devices()[: int(wl["chips"])],
                   **options)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_process: float, *, bench: dict | None = None,
             peaks: dict | None = None, cfg: dict | None = None,
             mix: dict | None = None, limits: dict | None = None,
             **options) -> dict:
    """Set-up, window, check and per-layer readers of one cell: returns
    the result object (its keys in the order they are printed).

    ``cfg``/``mix``/``limits`` replace the cell's files (the harness's
    own tests run a cell at a tiny size); ``options`` go to the
    ``Context``.
    """
    bench = load_benchmark() if bench is None else bench
    ctx = context(bench, workload, seed, seconds, trace, t_process,
                  cfg=cfg, mix=mix, **options)
    cfg, mix, devices = ctx.cfg, ctx.mix, ctx.devices
    rec = driver(mix["kind"])(ctx)
    setup_s = ctx.t_window - t_process
    rec["metrics"]["setup_s"] = setup_s
    compiled = ctx.compiles_in_window()
    rec["compiles_in_window"] = len(compiled)
    ctx.log(f"set-up {setup_s:.3f} s, compiles inside the window "
            f"{len(compiled)} {sorted(set(compiled))[:10]}")

    limits = load_limits(workload) if limits is None else limits
    checks = {}
    for name, value in rec["checks"].items():
        limit = limits.get(name, {}).get("limit")
        checks[name] = {"value": value, "limit": limit}
    correct = bool(checks) and all(
        c["limit"] is not None and math.isfinite(c["value"])
        and c["value"] <= c["limit"] for c in checks.values())

    units = {m["name"]: m["unit"] for m in
             bench["end_to_end"] + bench["per_layer"]}
    if trace:
        d0 = devices[0]
        rec["peak"] = load_peaks(d0.device_kind, peaks)
        rec["cfg"], rec["mix"] = cfg, mix
        values = {}
        for m in per_layer_for(bench, workload):
            v = load_reader(m["name"])(rec)
            if v is not None:
                values[m["name"]] = v
    else:
        values = {m["name"]: rec["metrics"][m["name"]]
                  for m in end_to_end_for(bench, workload)
                  if m["name"] in rec["metrics"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    out = {"correct": correct, "attempted": int(rec["attempted"]),
           "failed": int(rec["failed"]),
           "metrics": {k: {"value": v, "unit": units[k]}
                       for k, v in values.items()},
           "device": device}
    traced = rec.get("traced")
    if trace and traced:
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
        out["breakdown"] = traced["breakdown"]
    out["compiles_in_window"] = rec["compiles_in_window"]
    out["checks"] = checks
    return out


def print_result(out: dict) -> None:
    """Each compared number beside its limit as the last lines of stderr,
    then the result as the last line of stdout (a compared number that is
    not finite is written as a string)."""
    for c in out["checks"].values():
        if not math.isfinite(c["value"]):
            c["value"] = str(c["value"])
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
