"""Queries answered per engine flush in the window, from the front
end's own counters (FrontendStats.served_queries / flushes)."""


def read(run):
    c = run["counters"]
    if not c.get("flushes"):
        return None
    return c["served_queries"] / c["flushes"]
