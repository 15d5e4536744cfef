"""Poisson arrivals at a constant rate: given their count, the due times
are independent uniform draws over the window."""
import numpy as np


def due_times(params: dict, n: int, seconds: float,
              rng: np.random.Generator) -> np.ndarray:
    return rng.uniform(0.0, seconds, n)
