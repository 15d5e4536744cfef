"""Share of the traced device busy time spent in the N-way exclusive
products of the contraction (``kruskal.exclusive_products``: prefix and
suffix cumprods, their reverses and the product): the ops whose scope
path holds the program's unprefixed ``exclusive_products`` component,
inside the step's ``repro.step.grad`` and the evaluation's
``repro.eval.chunk``."""
from chipbench import oppaths


def read(run):
    t = run.get("traced") or {}
    if not t.get("busy_s"):
        return None
    ns = oppaths.of(run, "exclusive_products")
    return 100.0 * ns / 1e9 / t["busy_s"] if ns else None
