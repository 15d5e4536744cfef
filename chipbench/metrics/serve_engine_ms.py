"""Mean host-clock time of the engine call each flush makes
(block_until_ready included), over the whole window."""


def read(run):
    calls = run["counters"].get("engine_calls") or []
    if not calls:
        return None
    return 1e3 * sum(end - start for start, end, _ in calls) / len(calls)
