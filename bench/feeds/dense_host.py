"""Feed ``dense_host``: the loader's main path today.

``CSRBatch.to_dense()`` on the host, the label columns from ``batch.obs``,
then ``repro.distributed.dataio.put_batch`` onto the ``("data",)`` mesh of the
cell's own chips.  Spans: ``bench.densify`` (host batch assembly) and
``bench.h2d`` (the call that enqueues the copy to the device).
"""
from __future__ import annotations

import numpy as np
from jax.profiler import TraceAnnotation

from repro.distributed.dataio import put_batch
from repro.distributed.sharding import RULES_TRAIN


class Feed:
    def __init__(self, pipe, labels, mesh):
        self.mesh = mesh
        self.labels = tuple(labels)

    def host(self, batch) -> dict:
        with TraceAnnotation("bench.densify"):
            return {"x": batch.to_dense(),
                    **{t: np.asarray(batch.obs[t], np.int32) for t in self.labels}}

    def device(self, host: dict) -> dict:
        with TraceAnnotation("bench.h2d"):
            return put_batch(host, self.mesh, RULES_TRAIN)
