"""Steps the window took until the held-out RMSE reached the
configuration's target, taken as linear between the two evaluations on
either side of it (none when it never got there)."""


def read(run):
    return run["counters"].get("steps_to_rmse")
