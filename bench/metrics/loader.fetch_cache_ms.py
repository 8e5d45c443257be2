"""Planner and cache: self time of the planner's ``scdataset.plan`` span
(``PlannedCollection.fetch`` less its reads and its assembly): the cache
lookup, slicing read extents into cache blocks, insertion and eviction.
In ms per fetch (``scdataset.fetch`` span) in the window."""
from bench import program_spans


def read(r):
    spans = program_spans.find(r)
    return spans.per_fetch_ms("scdataset.plan") if spans else None
