"""Share of the traced window in which no operation ran on the device
(1 - union of device-op intervals / window), serving cells."""


def read(run):
    t = run.get("traced") or {}
    if not t.get("busy_s") or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
