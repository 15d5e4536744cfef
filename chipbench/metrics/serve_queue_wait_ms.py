"""Mean time a served request waited in the front end's queue before
its flush began: the sum of the ``wait_sum_us`` stats of the traced
``repro.serve.flush`` spans over the sum of their ``requests``."""
from chipbench import scopes


def read(run):
    t = scopes.of(run)
    if not t:
        return None
    flushes = [s.stats for s in t["spans"] if s.name == "repro.serve.flush"
               and s.stats.get("requests")]
    if not flushes:
        return None
    return (sum(f["wait_sum_us"] for f in flushes) / 1e3
            / sum(f["requests"] for f in flushes))
