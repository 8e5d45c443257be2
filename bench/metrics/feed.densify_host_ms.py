"""Host batch assembly: self time of the feed's ``bench.densify`` span
(``CSRBatch.to_dense`` and the label columns), in ms per window batch."""


def read(r):
    times = r.trace.self_times("bench.densify")
    return sum(times) / r.n_batches * 1e3 if times and r.n_batches else None
