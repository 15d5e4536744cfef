"""The one generator of serving traffic, driven by a mix's parameters.

A serving mix (``mixes/<name>.json``, ``"kind": "serve"``) gives:

* ``arrivals``: ``{"process": <name>, "rate_per_s": r, ...}``.  The
  process is ``arrivals/<name>.py``, whose ``due_times(params, n,
  seconds, rng)`` places ``n = round(r * seconds)`` requests over the
  window; every seed sends the same number of requests.
* ``size``: queries per request, ``{"dist": <name>, ...}``, from
  ``sizes/<name>.py``, whose ``sizes(params, n, rng)`` gives every seed
  the same multiset of sizes in another order.
* ``query``: ``"top_k"`` (each query one entity id of ``mode``, ranked
  against every entity of ``target_mode``) or ``"predict"`` (one page of
  index tuples: ``pinned_modes`` share one id across the page,
  ``candidate_mode`` differs per query).

Ids are uniform over each mode.  Everything comes from ``seed``, so the
same seed gives the same requests at the same offsets.  A new arrival
process or size distribution is a new file of its directory.
"""
from __future__ import annotations

import numpy as np

from chipbench import harness


def count(mix: dict, seconds: float) -> int:
    return max(1, int(round(float(mix["arrivals"]["rate_per_s"])
                            * float(seconds))))


def payload(mix: dict, cfg: dict, rng: np.random.Generator, size: int):
    """One request of ``size`` queries."""
    dims = cfg["dims"]
    if mix["query"] == "top_k":
        return rng.integers(0, dims[int(mix["mode"])], size, dtype=np.int32)
    if mix["query"] == "predict":
        idx = np.empty((size, len(dims)), np.int32)
        for n in mix["pinned_modes"]:
            idx[:, n] = rng.integers(0, dims[n])
        m = int(mix["candidate_mode"])
        idx[:, m] = rng.integers(0, dims[m], size)
        return idx
    raise ValueError(f"unknown query {mix['query']!r}")


def schedule(mix: dict, cfg: dict, seed: int, seconds: float
             ) -> list[tuple[float, object]]:
    """[(due offset in seconds from the window's start, request), ...]."""
    rng = np.random.default_rng([seed, 0])
    n = count(mix, seconds)
    arrivals = harness.load_module("arrivals", mix["arrivals"]["process"])
    due = np.sort(np.asarray(arrivals.due_times(
        mix["arrivals"], n, float(seconds), rng), float))
    if due.shape != (n,) or due[0] < 0 or due[-1] > seconds:
        raise ValueError(f"arrivals {mix['arrivals']['process']!r} gave "
                         f"{due.shape} due times outside [0, {seconds}]")
    dist = harness.load_module("sizes", mix["size"]["dist"])
    sz = np.asarray(dist.sizes(mix["size"], n, rng), int)
    return [(float(t), payload(mix, cfg, rng, int(s)))
            for t, s in zip(due, sz)]
