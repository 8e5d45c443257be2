"""Feed ``ell_device``: densify on the chip.

``CSRBatch.to_ell(k_max=store.ell_width)`` on the host, ``put_batch`` of the
ELL slab and the labels, then ``repro.kernels.ops.ell_to_dense(backend=
"auto")`` in a jitted ``densify`` under ``named_scope("densify")``, so the
device trace names its module ``jit_densify``.  Spans: ``bench.densify``
(host ELL assembly) and ``bench.h2d``.  ``shapes`` records what the
``densify_roofline`` reader needs.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.distributed.dataio import put_batch
from repro.distributed.sharding import RULES_TRAIN
from repro.kernels import ops


class Feed:
    def __init__(self, pipe, labels, mesh):
        self.mesh = mesh
        self.labels = tuple(labels)
        store = getattr(pipe.collection.adapter, "store", None)
        if not hasattr(store, "ell_width"):
            raise ValueError("feed ell_device needs a store with ell_width (sharded-csr)")
        self.k = store.ell_width
        self.n_genes = store.n_var

        def densify(vals, cols):
            with jax.named_scope("densify"):
                return ops.ell_to_dense(vals, cols, n_cols=self.n_genes, backend="auto")

        self._densify = jax.jit(densify)
        self.shapes = {"k": self.k, "n_genes": self.n_genes}

    def host(self, batch) -> dict:
        with TraceAnnotation("bench.densify"):
            vals, cols = batch.to_ell(k_max=self.k)
            return {"vals": vals, "cols": cols,
                    **{t: np.asarray(batch.obs[t], np.int32) for t in self.labels}}

    def device(self, host: dict) -> dict:
        with TraceAnnotation("bench.h2d"):
            dev = put_batch(host, self.mesh, RULES_TRAIN)
        self.shapes["rows"] = host["vals"].shape[0]
        return {"x": self._densify(dev.pop("vals"), dev.pop("cols")), **dev}
