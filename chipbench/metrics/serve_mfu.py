"""Top-k FLOPs the traced window's flushes needed (2·b·R·I each;
chipbench.counts) over the window's length, as a share of the chip's
bf16 peak."""
from chipbench import counts


def read(run):
    t = run.get("traced") or {}
    c = run["counters"]
    if not t.get("window_s") or c.get("query") != "top_k":
        return None
    target = int(run["mix"]["target_mode"])
    flops = sum(counts.topk_flush_flops(run["cfg"], b, target)
                for start, _, b in c["engine_calls"] if start >= t["t0"])
    if not flops:
        return None
    return 100.0 * flops / t["window_s"] / run["peak"]["bf16_flops_per_s"]
