"""Reduction of a profiler trace to device busy time and a breakdown.

``jax.profiler`` writes an ``.xplane.pb``; ``ProfileData`` reads it as
planes of lines of events (name, start and duration in ns).  A TPU's
operations are the events of the line ``XLA Ops`` on its plane
``/device:TPU:<n>``.  Busy time is the union of those intervals, so ops
that overlap (or nest) count once; the idle share is 1 - busy / window.

The host's spans (``TraceAnnotation``s of the benchmark, and whatever
the host threads record) name the idle gaps: each of the longest gaps
between device ops is labelled with the innermost host span that covers
its middle.

On the CPU (the harness's own tests) the ops are the non-marker events
of the XLA client's threads on ``/host:CPU``; nothing else reads those.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

TPU_PLANE = "/device:TPU:"
TPU_OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
CPU_OPS_THREAD = "tf_XLAPjRtCpuClient"
TOP = 10


def _events(line):
    return [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
            for e in line.events]


def device_ops(planes, platform: str) -> list[list[tuple[int, int, str]]]:
    """Per device, its operations as (start_ns, end_ns, name)."""
    out = []
    for plane in planes:
        if platform == "tpu" and plane.name.startswith(TPU_PLANE) \
                and plane.name[len(TPU_PLANE):].isdigit():
            ops = [ev for line in plane.lines if line.name == TPU_OPS_LINE
                   for ev in _events(line)]
            out.append(ops)
        elif platform == "cpu" and plane.name == HOST_PLANE:
            ops = [ev for line in plane.lines
                   if line.name.startswith(CPU_OPS_THREAD)
                   for ev in _events(line)
                   if ev[1] > ev[0] and not ev[2].startswith("Threadpool")]
            out.append(ops)
    return out


def merge(intervals) -> list[tuple[int, int]]:
    """The union of [start, end) intervals as sorted disjoint intervals."""
    merged: list[list[int]] = []
    for s, e, *_ in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(intervals) -> int:
    return sum(e - s for s, e in merge(intervals))


def host_spans(planes) -> list[tuple[int, int, str]]:
    return [ev for plane in planes if plane.name == HOST_PLANE
            for line in plane.lines if not line.name.startswith(
                CPU_OPS_THREAD) for ev in _events(line) if ev[1] > ev[0]]


def label_gap(start: int, end: int, spans) -> str:
    """The innermost host span covering the gap's middle."""
    mid = (start + end) // 2
    best = None
    for s, e, name in spans:
        if s <= mid < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "no host span"


def reduce(planes, platform: str, window_s: float) -> dict:
    """busy_s (mean over devices), window_s and the breakdown."""
    per_device = device_ops(planes, platform)
    if not per_device or not any(per_device):
        return {"busy_s": None, "window_s": window_s,
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    busy = [busy_ns(ops) / 1e9 for ops in per_device]
    totals: dict[str, int] = defaultdict(int)
    for ops in per_device:
        for s, e, name in ops:
            totals[name] += e - s
    top_ops = sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]
    merged = merge(per_device[0])
    gaps = [(merged[i][1], merged[i + 1][0])
            for i in range(len(merged) - 1)]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    spans = host_spans(planes)
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": window_s,
        "breakdown": {
            "device_ops": [[n, ns / 1e9 / len(per_device)]
                           for n, ns in top_ops],
            "idle_gaps": [[label_gap(s, e, spans), (e - s) / 1e9]
                          for s, e in gaps],
        },
    }


def reduce_dir(directory, platform: str, window_s: float) -> dict:
    """Read the newest trace under ``directory`` and reduce it."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no trace under {directory}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    return reduce(list(data.planes), platform, window_s)
