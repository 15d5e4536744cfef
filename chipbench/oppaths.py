"""Device time of the ops whose scope path holds a given component.

``scopes.scoped`` files each op under the innermost ``repro.*`` component
of its ``tf_op`` path, so a name the program puts inside one of those
scopes without the prefix (``exclusive_products`` inside
``repro.step.grad`` and ``repro.eval.chunk``) is not among its keys, and
leaves the enclosing scope's share as it was.  This module reads the
whole path of each op for such a component, from the same
``.trace.json.gz`` events, on the same device threads, as a union of
intervals averaged over devices.

``of(run, name)`` keeps what it read in ``run["traced"]["components"]``.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
import time

from chipbench import scopes, trace

# a component, bare or under a transform's wrapper, e.g. jvp(name)
_WRAPPED = r"(?:[A-Za-z0-9_]+\()*{}\)*"


def has_component(path: str | None, name: str) -> bool:
    """Whether ``name`` is one component of an op's scope path."""
    pattern = re.compile(_WRAPPED.format(re.escape(name)))
    return any(pattern.fullmatch(c) for c in (path or "").split("/"))


def component_ns(events, name: str) -> float:
    """Device busy ns of the ops with ``name`` in their path, averaged
    over devices (each device's ops as a union of intervals)."""
    _, devices = scopes._op_threads(events)
    by_device = {pid: [] for pid in devices}
    for e in events:
        pid = e.get("pid")
        if e.get("ph") != "X" or pid not in devices \
                or (pid, e.get("tid")) not in devices[pid] \
                or e["name"].startswith("Threadpool") \
                or not has_component(e.get("args", {}).get(
                    scopes.SCOPE_STAT), name):
            continue
        start = round(float(e["ts"]) * 1e3)
        end = start + round(float(e.get("dur", 0.0)) * 1e3)
        if end > start:
            by_device[pid].append((start, end))
    if not by_device:
        return 0.0
    return sum(trace.busy_ns(iv) for iv in by_device.values()) \
        / len(by_device)


def read_events(xplane: str) -> list:
    """The events of the ``.trace.json.gz`` written beside ``xplane``."""
    events = []
    for path in glob.glob(os.path.join(os.path.dirname(xplane),
                                       "*.trace.json.gz")):
        with gzip.open(path, "rt") as f:
            events += json.load(f).get("traceEvents", [])
    return events


def of(run, name: str) -> float | None:
    """Device busy ns of the ops under ``name`` in the trace this run's
    window recorded; None for an untraced run, 0 where none is found."""
    t = run.get("traced")
    if not t:
        return None
    found = t.setdefault("components", {})
    if name not in found:
        started = time.time() - (time.perf_counter() - t["t0"])
        path = scopes.newest_trace(started)
        found[name] = component_ns(read_events(path), name) if path else 0.0
    return found[name]
