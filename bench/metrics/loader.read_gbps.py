"""Storage: the loader's read rate, in GB/s: ``IOStats`` ``bytes_read`` over
the window (the count at the read boundary) over the summed seconds of the
``scdataset.read`` spans that start in it (the time of the same reads)."""
from bench import program_spans


def read(r):
    spans = program_spans.find(r)
    seconds = sum(spans.self_times("scdataset.read")) if spans else 0.0
    nbytes = r.counters.get("bytes_read", 0)
    return nbytes / seconds / 1e9 if seconds > 0 and nbytes else None
