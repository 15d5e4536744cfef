"""Least time of the traced window's top-k flushes (read C^(target)
once, 2·b·R·I FLOPs; chipbench.counts) over the device busy time of that
window."""
from chipbench import counts


def read(run):
    t = run.get("traced") or {}
    c = run["counters"]
    if not t.get("busy_s") or c.get("query") != "top_k":
        return None
    target = int(run["mix"]["target_mode"])
    least = 0.0
    for start, _, b in c["engine_calls"]:
        if start >= t["t0"]:
            least += counts.least_time(
                counts.topk_flush_flops(run["cfg"], b, target),
                counts.topk_flush_bytes(run["cfg"], b, target),
                run["peak"])[0]
    return 100.0 * least / t["busy_s"] if least else None
