"""Every request the same number of queries, ``value``."""
import numpy as np


def sizes(params: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    return np.full(n, int(params["value"]))
