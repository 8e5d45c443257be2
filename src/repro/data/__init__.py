"""repro.data — storage substrates behind one unified backend layer.

Every format is reachable through the **Collection protocol** via
:func:`open_collection`, which wraps the format's adapter in a
:class:`~repro.data.backend.PlannedCollection`: fetches are coalesced by the
shared cross-shard read planner and served through a byte-budgeted LRU block
cache, with one :class:`IOStats` counting runs / bytes / requests / cache
hits uniformly (see :mod:`repro.data.readplan`).

Registered URI schemes (see the README's scheme table):

========================  ===================================================
``csr://``                one on-disk CSR shard (AnnData-like ``.npy`` trio)
``sharded-csr://``        lazy concat of CSR shards (Tahoe plate files)
``chunked://``            Zarr-style chunked dense store
``tokens://``             flat token stream viewed as sequences
``h5ad://``               real AnnData/HDF5 files (h5py or pure-Python shim)
``sharded-h5ad://``       manifest over many ``.h5ad`` plate files, one row
                          space (composite of the h5ad adapter)
``cloud://<inner-uri>``   any of the above behind object-store request
                          semantics (first-byte latency, bandwidth,
                          ``max_inflight``) — :mod:`repro.data.cloud`
``fault://<inner-uri>``   any of the above behind seeded, deterministic
                          fault injection (transient errors, latency
                          spikes, shard blackouts, stuck reads) —
                          :mod:`repro.data.faults`
========================  ===================================================

**Writing a new storage adapter** — the full authoring guide, with the
``h5ad://`` adapter as its worked example, lives in ``docs/adapters.md``.
Short form: subclass :class:`~repro.data.backend.StorageAdapter`
(``__len__``, one-contiguous-extent ``read_range``, ``boundaries``,
``take``/``concat`` on your batch type, ``nbytes_of``/``avg_row_bytes``,
``schema``), register an opener with ``@register_backend("scheme")``, and
the planner, cache, async execution, accounting and benchmarks come for
free.  Planner and async knobs on :func:`open_collection` are documented on
that function and in ``docs/architecture.md``.
"""
from .backend import (
    ChunkedAdapter,
    Collection,
    CSRAdapter,
    CSRCompositeAdapter,
    PlannedCollection,
    ShardedCSRAdapter,
    StorageAdapter,
    TokenAdapter,
    open_adapter,
    open_collection,
    register_backend,
    registered_schemes,
)
from .chunked_store import ChunkedStore, write_chunked_store
from .cloud import CLOUD_PROFILES, CloudAdapter, CloudProfile
from .csr_store import CSRBatch, CSRStore, ShardedCSRStore, write_csr_shard
from .faults import (
    FaultInjectingAdapter,
    FaultProfile,
    RetryBudgetExhausted,
    RetryPolicy,
    ShardBreaker,
    TransientStorageError,
)
from .h5ad import H5adAdapter, H5adStore, ShardedH5adAdapter
from .iostats import CLOUD_OBJECT, NVME_SSD, SATA_SSD, IOStats, PendingIO, StorageModel, span
from .readplan import (
    BlockCache,
    SegmentedBlockCache,
    StreamDetector,
    coalesce_rows,
    plan_reads,
)
from .synth import (
    TAHOE_PLATE_FRACS,
    csr_shard_to_h5ad,
    generate_h5ad_like,
    generate_sharded_h5ad_like,
    generate_tahoe_like,
    load_tahoe_like,
    write_h5ad,
)
from .tokens import TokenStore, generate_token_corpus

__all__ = [
    "CSRBatch",
    "CSRStore",
    "ShardedCSRStore",
    "write_csr_shard",
    "ChunkedStore",
    "write_chunked_store",
    "H5adStore",
    "H5adAdapter",
    "ShardedH5adAdapter",
    "write_h5ad",
    "csr_shard_to_h5ad",
    "generate_h5ad_like",
    "generate_sharded_h5ad_like",
    "CloudProfile",
    "CloudAdapter",
    "CLOUD_PROFILES",
    "FaultProfile",
    "FaultInjectingAdapter",
    "TransientStorageError",
    "RetryBudgetExhausted",
    "RetryPolicy",
    "ShardBreaker",
    "IOStats",
    "PendingIO",
    "StorageModel",
    "span",
    "SATA_SSD",
    "NVME_SSD",
    "CLOUD_OBJECT",
    "Collection",
    "StorageAdapter",
    "CSRAdapter",
    "CSRCompositeAdapter",
    "ShardedCSRAdapter",
    "ChunkedAdapter",
    "TokenAdapter",
    "PlannedCollection",
    "open_adapter",
    "open_collection",
    "register_backend",
    "registered_schemes",
    "BlockCache",
    "SegmentedBlockCache",
    "StreamDetector",
    "coalesce_rows",
    "plan_reads",
    "generate_tahoe_like",
    "load_tahoe_like",
    "TAHOE_PLATE_FRACS",
    "TokenStore",
    "generate_token_corpus",
]
