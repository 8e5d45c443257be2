"""Host batches → sharded global jax.Arrays.

The bridge between the scDataset host pipeline (numpy, per-rank batches) and
the device mesh.  On a single host with N local devices, ``device_put`` with
a NamedSharding both lays the batch out across local devices and validates
the spec; in a real multi-host pod the same call sites switch to
``jax.make_array_from_process_local_data`` (each host contributes the rows
its scDataset rank round-robin owns — the paper's Appendix B partitioning is
exactly a per-host data-parallel feed).
"""
from __future__ import annotations

from typing import Any, Mapping, Optional

import jax
import numpy as np

from ..data.iostats import span
from .sharding import Rules, sharding_for_axes

__all__ = ["put_batch", "batch_axes_for"]


def batch_axes_for(batch: Mapping[str, Any]) -> dict:
    """Default logical axes for a host batch dict."""
    out = {}
    for k, v in batch.items():
        nd = np.ndim(v)
        if nd == 0:
            out[k] = ()
        elif nd == 1:
            out[k] = ("batch",)
        elif nd == 2:
            out[k] = ("batch", "seq")
        else:
            out[k] = ("batch", "seq") + (None,) * (nd - 2)
    return out


def put_batch(
    batch: Mapping[str, np.ndarray],
    mesh,
    rules: Rules,
    axes: Optional[Mapping[str, tuple]] = None,
) -> dict:
    """device_put every leaf with its resolved NamedSharding.

    Its span, ``scdataset.put_batch``, carries the number of this host's
    devices the batch is laid over (``devices``) and the batch's bytes."""
    batch = {k: np.asarray(v) for k, v in batch.items()}
    nbytes = sum(v.nbytes for v in batch.values())
    with span("scdataset.put_batch", devices=len(mesh.local_devices), bytes=nbytes):
        axes = axes or batch_axes_for(batch)
        out = {}
        for k, v in batch.items():
            sh = sharding_for_axes(axes[k], rules, mesh, v.shape)
            if jax.process_count() > 1:  # pragma: no cover (multi-host path)
                out[k] = jax.make_array_from_process_local_data(sh, v)
            else:
                out[k] = jax.device_put(v, sh)
        return out
