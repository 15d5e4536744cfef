"""Share of the traced device busy time spent selecting the top k of
each scored row: the ops under the program's ``repro.topk.select``
scope (``lax.top_k`` over all target items)."""
from chipbench import scopes


def read(run):
    t = scopes.of(run)
    if not t or not t.get("busy_s"):
        return None
    ns = t["scoped"].get("repro.topk.select", 0.0)
    return 100.0 * ns / 1e9 / t["busy_s"] if ns else None
