"""The program's own names in a profiler trace: device time per scope and
the host spans.

The program wraps its jitted phases in ``jax.named_scope("repro.<...>")``
(training step, evaluation, top-k) and its serving path in
``jax.profiler.TraceAnnotation("repro.serve.<...>")`` host spans with
stats.  This module reads both from the trace a ``--trace 1`` run leaves
(``harness.Tracer``, under ``_runs/<workload>/trace``):

* ``scoped``: device busy ns per scope, the union of the intervals of the
  ops under it (nested or overlapping ops count once), averaged over
  devices as ``trace.reduce`` averages ``busy_s``; ops under no
  ``repro.*`` scope make up ``UNSCOPED``.  An op belongs to the innermost
  ``repro.*`` component of its scope path, which a TPU's ``XLA Ops``
  events carry in the stat ``tf_op`` (``SCOPE_STAT``; e.g.
  ``jit(core_step)/jit(sgd_step)/repro.step.scatter/scatter-add``).
  ``ProfileData`` does not show that stat (it is the op's metadata, not
  the event's), so the ops are read from the ``.trace.json.gz`` the
  profiler writes beside the ``.xplane.pb``, where it is an argument of
  each op event.
* ``spans``: the host events named ``repro.*``, and the benchmark's own
  ``chipbench.*``, as ``Span`` (name, start and end in ns, stats, and the
  host thread it ran on), read from the ``.xplane.pb``, where the stats
  keep their types.

``of(run)`` adds both to ``run["traced"]`` the first time a reader asks,
and leaves them where they are already there.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
import time
from collections import defaultdict
from typing import NamedTuple

from chipbench import trace

PREFIXES = ("repro.", "chipbench.")
SCOPE_STAT = "tf_op"
UNSCOPED = "unscoped"
_SCOPE = re.compile(r"repro\.[A-Za-z0-9_]+(?:\.[A-Za-z0-9_]+)*")


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    stats: dict
    thread: str


def scope_of(path: str | None) -> str:
    """The innermost ``repro.*`` component of an op's scope path."""
    found = _SCOPE.findall(path or "")
    return found[-1] if found else UNSCOPED


def _op_threads(events) -> tuple[str, dict]:
    """The platform, and per device its op threads as {(pid, tid)}: the
    ``XLA Ops`` thread of each ``/device:TPU:<n>``, or on the CPU the XLA
    client's threads (as ``trace.device_ops`` reads the planes)."""
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            threads[(e["pid"], e["tid"])] = e["args"]["name"]
    tpu = {pid: name for pid, name in procs.items()
           if name.startswith(trace.TPU_PLANE)
           and name[len(trace.TPU_PLANE):].isdigit()}
    if tpu:
        return "tpu", {pid: {k for k, t in threads.items()
                             if k[0] == pid and t == trace.TPU_OPS_LINE}
                       for pid in tpu}
    host = [pid for pid, name in procs.items() if name == trace.HOST_PLANE]
    return "cpu", {pid: {k for k, t in threads.items() if k[0] == pid
                         and t.startswith(trace.CPU_OPS_THREAD)}
                   for pid in host}


def scoped(events) -> dict[str, float]:
    """Device busy ns per ``repro.*`` scope (and ``UNSCOPED``), averaged
    over devices, from the events of a ``.trace.json.gz``."""
    _, devices = _op_threads(events)
    by_device = {pid: defaultdict(list) for pid in devices}
    for e in events:
        pid = e.get("pid")
        if e.get("ph") != "X" or pid not in devices \
                or (pid, e.get("tid")) not in devices[pid] \
                or e["name"].startswith("Threadpool"):
            continue
        start = round(float(e["ts"]) * 1e3)
        end = start + round(float(e.get("dur", 0.0)) * 1e3)
        if end > start:
            scope = scope_of(e.get("args", {}).get(SCOPE_STAT))
            by_device[pid][scope].append((start, end))
    totals: dict[str, float] = defaultdict(float)
    for by_scope in by_device.values():
        for name, intervals in by_scope.items():
            totals[name] += trace.busy_ns(intervals) / len(by_device)
    return dict(totals)


def spans(planes) -> list[Span]:
    """The host events named ``repro.*`` or ``chipbench.*`` of the
    ``ProfileData`` planes, in start order."""
    out = []
    for plane in planes:
        if plane.name != trace.HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    start = int(e.start_ns)
                    out.append(Span(e.name, start,
                                    start + int(e.duration_ns),
                                    dict(e.stats), f"{line.name}/{i}"))
    return sorted(out, key=lambda s: s.start_ns)


def newest_trace(since_wall_s: float, root=None) -> str | None:
    """The newest ``.xplane.pb`` under ``root`` (the harness's runs)
    written at or after ``since_wall_s``."""
    from chipbench import harness

    root = harness.RUNS if root is None else root
    files = [f for f in glob.glob(os.path.join(str(root), "**",
                                               "*.xplane.pb"), recursive=True)
             if os.path.getmtime(f) >= since_wall_s]
    return max(files, key=os.path.getmtime) if files else None


def read_trace(xplane: str) -> dict:
    """``scoped`` and ``spans`` of one trace: the ``.xplane.pb`` and the
    ``.trace.json.gz`` written beside it."""
    from jax.profiler import ProfileData

    events = []
    for path in glob.glob(os.path.join(os.path.dirname(xplane),
                                       "*.trace.json.gz")):
        with gzip.open(path, "rt") as f:
            events += json.load(f).get("traceEvents", [])
    return {"scoped": scoped(events),
            "spans": spans(list(ProfileData.from_file(xplane).planes))}


def of(run) -> dict | None:
    """``run["traced"]`` with ``scoped`` and ``spans`` in it, read from the
    trace this run's window recorded; None for an untraced run.  A run
    whose trace is not found gets empty ones."""
    t = run.get("traced")
    if not t:
        return None
    if "scoped" not in t:
        started = time.time() - (time.perf_counter() - t["t0"])
        path = newest_trace(started)
        t.update(read_trace(path) if path else {"scoped": {}, "spans": []})
    return t
