"""Training FLOPs the algorithm needs (chipbench.counts, 6·R·ΣJ per
nonzero) at the nonzeros per second of the traced window, as a share of
the chip's bf16 peak."""
from chipbench import counts


def read(run):
    t = run.get("traced") or {}
    if not t.get("busy_s") or not t.get("count"):
        return None
    nnz = t["count"] * run["counters"]["batch"]
    flops = counts.train_flops_per_nnz(run["cfg"]) * nnz
    return 100.0 * flops / t["window_s"] / run["peak"]["bf16_flops_per_s"]
