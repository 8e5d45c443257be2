"""Planner and storage: physical reads issued per cell delivered, from the
loader's ``IOStats`` counters (``runs`` over ``rows``) over the window."""


def read(r):
    rows = r.counters.get("rows", 0)
    return r.counters.get("runs", 0) / rows if rows else None
