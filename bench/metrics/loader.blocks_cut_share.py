"""Planner and cache: the share of missed cache blocks cut out of their read
extents as blocks of their own, from the loader's ``IOStats`` counters
(``blocks_cut`` over ``cache_misses``) over the window.  The rest went from
the extent straight into the batch.  None where the program keeps no such
counter."""


def read(r):
    misses = r.counters.get("cache_misses", 0)
    if "blocks_cut" not in r.counters or not misses:
        return None
    return r.counters["blocks_cut"] / misses
