"""Host to device, from inside: self time of the program's
``scdataset.put_batch`` span (``put_batch``, which enqueues the copy), in ms
per window batch.  ``feed.h2d_ms`` times the same call from outside."""
from bench import program_spans


def read(r):
    spans = program_spans.find(r)
    times = spans.self_times("scdataset.put_batch") if spans else []
    return sum(times) / r.n_batches * 1e3 if times and r.n_batches else None
