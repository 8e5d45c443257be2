"""Storage: time in the loader's physical reads (``scdataset.read`` spans,
one per ``read_range``, retries included), in ms per fetch
(``scdataset.fetch`` span) in the window."""
from bench import program_spans


def read(r):
    spans = program_spans.find(r)
    return spans.per_fetch_ms("scdataset.read") if spans else None
