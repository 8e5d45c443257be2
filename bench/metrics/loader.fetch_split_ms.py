"""Loader: self time of ``ScDataset.fetch``'s ``scdataset.split`` span: the
fetch transform, the in-memory reshuffle and the split into minibatches.
In ms per fetch (``scdataset.fetch`` span) in the window."""
from bench import program_spans


def read(r):
    spans = program_spans.find(r)
    return spans.per_fetch_ms("scdataset.split") if spans else None
