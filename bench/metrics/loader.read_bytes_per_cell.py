"""Planner and storage: bytes read from storage per cell delivered, from the
loader's ``IOStats`` counters (``bytes_read`` over ``rows``) over the window.
A cell of the atlas is ~24.7 kB of CSR, so a reading well above that is
over-read: blocks read whole for the few rows a fetch wants of them."""


def read(r):
    rows = r.counters.get("rows", 0)
    return r.counters.get("bytes_read", 0) / rows if rows else None
