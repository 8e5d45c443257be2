"""Loader: self time of the ``bench.loader`` span around ``next()`` on the
``DataPipeline``, in ms per window batch.  It holds sampling, the read
planner and cache, the storage adapter and the fetch's reshuffle and split;
most of it falls on the one batch in ``fetch_factor`` that fetches."""


def read(r):
    times = r.trace.self_times("bench.loader")
    return sum(times) / r.n_batches * 1e3 if times and r.n_batches else None
