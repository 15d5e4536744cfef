"""Device time per traced training step in the dense table sweep: the
ops under the program's ``repro.step.scatter`` (per-mode row-gradient
scatter into full-size tables) and ``repro.step.update`` (full-table
factor and core updates) scopes, over the steps of the traced window."""
from chipbench import scopes

SWEEP = ("repro.step.scatter", "repro.step.update")


def read(run):
    t = scopes.of(run)
    if not t or not t.get("count"):
        return None
    ns = sum(t["scoped"].get(s, 0.0) for s in SWEEP)
    return ns / 1e6 / t["count"] if ns else None
