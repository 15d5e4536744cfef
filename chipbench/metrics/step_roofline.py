"""Least time of one training step (the larger of its FLOPs over the
bf16 peak and its necessary bytes over HBM bandwidth, chipbench.counts)
over the device time per step of the traced window."""
from chipbench import counts


def read(run):
    t = run.get("traced") or {}
    if not t.get("busy_s") or not t.get("count"):
        return None
    batch = run["counters"]["batch"]
    least, _ = counts.least_time(counts.train_step_flops(run["cfg"], batch),
                                 counts.train_step_bytes(run["cfg"], batch),
                                 run["peak"])
    return 100.0 * least / (t["busy_s"] / t["count"])
