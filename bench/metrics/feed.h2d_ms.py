"""Host to device: self time of the feed's ``bench.h2d`` span (the
``put_batch`` call that enqueues the copy), in ms per window batch."""


def read(r):
    times = r.trace.self_times("bench.h2d")
    return sum(times) / r.n_batches * 1e3 if times and r.n_batches else None
