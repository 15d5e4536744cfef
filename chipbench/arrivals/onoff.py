"""On/off bursts at the mix's mean rate: Poisson arrivals during ``on_s``
seconds, none during the ``off_s`` seconds that follow, repeated from
the window's start.  Given their count, the due times are uniform over
the on-periods that fall inside the window."""
import numpy as np


def due_times(params: dict, n: int, seconds: float,
              rng: np.random.Generator) -> np.ndarray:
    on, off = float(params["on_s"]), float(params["off_s"])
    period = on + off
    starts = np.arange(0.0, seconds, period)
    lengths = np.minimum(on, seconds - starts)
    # a point uniform over the union of the on-periods
    u = rng.uniform(0.0, float(lengths.sum()), n)
    edges = np.concatenate([[0.0], np.cumsum(lengths)])
    k = np.clip(np.searchsorted(edges, u, side="right") - 1, 0,
                len(starts) - 1)
    return starts[k] + (u - edges[k])
