"""Share of the traced device busy time spent in the held-out
evaluation: the ops under the program's ``repro.eval.chunk`` scope."""
from chipbench import scopes


def read(run):
    t = scopes.of(run)
    if not t or not t.get("busy_s"):
        return None
    ns = t["scoped"].get("repro.eval.chunk", 0.0)
    return 100.0 * ns / 1e9 / t["busy_s"] if ns else None
