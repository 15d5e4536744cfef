"""Device busy time in the traced window per training step taken in it
(periodic evaluation included, as in the window)."""


def read(run):
    t = run.get("traced") or {}
    if not t.get("busy_s") or not t.get("count"):
        return None
    return 1e3 * t["busy_s"] / t["count"]
