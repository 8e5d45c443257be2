"""Planner and cache: self time of the planner's ``scdataset.assemble`` span:
taking the fetch's rows out of their blocks, concatenating them and
restoring the caller's order.  In ms per fetch (``scdataset.fetch`` span)
in the window."""
from bench import program_spans


def read(r):
    spans = program_spans.find(r)
    return spans.per_fetch_ms("scdataset.assemble") if spans else None
