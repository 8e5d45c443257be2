"""Host batch assembly, from inside: self time of the program's
``scdataset.to_dense`` span (``CSRBatch.to_dense``), in ms per window batch.
``feed.densify_host_ms`` times the same call from outside, with the label
columns."""
from bench import program_spans


def read(r):
    spans = program_spans.find(r)
    times = spans.self_times("scdataset.to_dense") if spans else []
    return sum(times) / r.n_batches * 1e3 if times and r.n_batches else None
