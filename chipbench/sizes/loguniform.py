"""Request sizes log-uniform over ``min``..``max`` queries: the
distribution's quantiles at evenly spaced levels, shuffled by the seed,
so every seed sends the same multiset of sizes in another order."""
import numpy as np


def sizes(params: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    lo, hi = np.log(params["min"]), np.log(params["max"] + 1)
    levels = (np.arange(n) + 0.5) / max(n, 1)
    out = np.floor(np.exp(lo + levels * (hi - lo))).astype(int)
    out = np.clip(out, int(params["min"]), int(params["max"]))
    return rng.permutation(out)
