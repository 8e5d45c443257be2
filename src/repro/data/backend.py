"""Unified storage-backend layer: one Collection protocol over every format.

The paper's pitch is "seamless integration across diverse storage formats";
before this module each backend (CSR shards, chunked dense, token streams)
privately reimplemented read coalescing and IOStats accounting, and nothing
composed across them.  This module is the substrate they all plug into:

- :class:`StorageAdapter` — the small contract a storage format implements
  (contiguous ``read_range`` + ``take``/``concat`` on its batch type, shard
  ``boundaries``, byte estimates, obs/schema access).
- a **backend registry** — formats register under a URI scheme; callers do
  ``open_collection("csr:///data/plate_00")`` and never touch format classes.
- :class:`PlannedCollection` — the :class:`Collection` every consumer sees.
  It routes fetches through the shared cross-shard read planner and the
  byte-budgeted LRU block cache of :mod:`repro.data.readplan`, and threads a
  single :class:`~repro.data.iostats.IOStats` so runs / bytes / cache hits
  are counted once, uniformly, for every backend.

Adding a new storage format (h5ad, cloud bucket, Zarr...) means writing one
adapter subclass and one ``@register_backend("scheme")`` opener — the
planner, cache, accounting, ScDataset/PrefetchPool integration and the
benchmarks come for free.  See :mod:`repro.data` for the written contract.
"""
from __future__ import annotations

import concurrent.futures as _cf
import json
import os
import threading
import time
import urllib.parse
from contextlib import contextmanager
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Optional, Protocol, Sequence, runtime_checkable

import numpy as np

from .chunked_store import ChunkedStore
from .csr_store import (
    BufferPool,
    CSRBatch,
    CSRStore,
    ShardedCSRStore,
    _concat_batches,
    gather_rows,
    gathered_nbytes,
)
from .iostats import IOStats, span
from .readplan import (
    BlockCache,
    SegmentedBlockCache,
    FrequencySketch,
    ReadaheadController,
    StreamDetector,
    blocks_to_row_spans,
    normalize_readahead,
    split_at_boundaries,
    split_max_extent,
)
from .tokens import TokenStore

__all__ = [
    "Collection",
    "StorageAdapter",
    "CSRAdapter",
    "CSRCompositeAdapter",
    "ShardedCSRAdapter",
    "ChunkedAdapter",
    "TokenAdapter",
    "PlannedCollection",
    "register_backend",
    "registered_schemes",
    "open_adapter",
    "open_collection",
    "piece_nbytes",
]

DEFAULT_CACHE_BYTES = 64 << 20
DEFAULT_BLOCK_ROWS = 256
DEFAULT_MAX_EXTENT_ROWS = 32768


@runtime_checkable
class Collection(Protocol):
    """What ScDataset / PrefetchPool require of a data collection."""

    def __len__(self) -> int: ...

    def fetch(self, rows) -> Any:
        """Batched read of ``rows`` (any order, duplicates allowed)."""
        ...

    def nbytes_of(self, rows) -> int:
        """Estimated on-disk bytes of ``rows`` (autotuning / cache budgets)."""
        ...

    @property
    def schema(self) -> dict:
        """Shape/kind description of what ``fetch`` returns."""
        ...


def piece_nbytes(piece: Any) -> int:
    """In-memory bytes of a backend batch (CSRBatch / ndarray / dict)."""
    if hasattr(piece, "nbytes"):
        return int(piece.nbytes)
    if isinstance(piece, dict):
        return int(sum(int(v.nbytes) for v in piece.values()))
    raise TypeError(f"cannot size {type(piece).__name__}")


class StorageAdapter:
    """The contract a storage format implements to join the unified layer.

    Subclasses supply contiguous physical reads and batch algebra on their
    native batch type; the planner/cache in :class:`PlannedCollection` never
    inspects batches beyond these methods.
    """

    def __len__(self) -> int:
        raise NotImplementedError

    def boundaries(self) -> Optional[np.ndarray]:
        """Ascending physical-extent offsets ``[0, ..., n]`` (shards/chunks);
        None means one uninterrupted extent."""
        return None

    def read_range(self, start: int, stop: int) -> Any:
        """ONE contiguous read of rows ``[start, stop)`` — never crosses an
        interior boundary (the planner guarantees it).  No stats recording."""
        raise NotImplementedError

    def take(self, piece: Any, rows: np.ndarray) -> Any:
        """Row-index a batch (relative indices; duplicates/order preserved)."""
        raise NotImplementedError

    def concat(self, pieces: Sequence[Any]) -> Any:
        """Concatenate batches in order."""
        raise NotImplementedError

    def gather(self, sources: Sequence[tuple[Any, np.ndarray]]) -> Any:
        """One batch of ``take(piece, rows)`` for each ``(piece, rows)`` of
        ``sources``, in order: how the planner assembles a fetch from the
        read extents and cached blocks that hold its rows.  Default: that,
        then :meth:`concat`.  Batch types that can copy whole runs of rows
        at once override it to build the batch in one copy."""
        parts = [self.take(piece, rows) for piece, rows in sources]
        return parts[0] if len(parts) == 1 else self.concat(parts)

    def gather_nbytes(self, ranges: Sequence[tuple[Any, int, int]]) -> int:
        """In-memory bytes of the :meth:`gather` of rows ``[lo, hi)`` of
        each ``(piece, lo, hi)``: what the block cache would be charged for
        it.  Default: build it and measure.  Adapters that can read the
        size off the pieces override it, so the planner sizes a block it
        will not keep without copying it."""
        return piece_nbytes(
            self.gather([(piece, np.arange(lo, hi)) for piece, lo, hi in ranges])
        )

    def nbytes_of(self, rows: np.ndarray) -> int:
        """Estimated payload bytes of ``rows`` without reading them."""
        raise NotImplementedError

    @property
    def avg_row_bytes(self) -> float:
        raise NotImplementedError

    @property
    def schema(self) -> dict:
        raise NotImplementedError

    # Optional obs/metadata access (formats without metadata return nothing).
    def obs_keys(self) -> list[str]:
        return []

    def obs_column(self, key: str) -> np.ndarray:
        raise KeyError(key)

    def bind_iostats(self, iostats: IOStats) -> None:
        """Called once by :class:`PlannedCollection` with the shared stats.

        Default: ignore.  Adapters with accounting dimensions the planner
        cannot see (``cloud://`` counts one *request* per ``read_range``)
        record them through this handle — never runs/bytes, which the
        planner counts itself.
        """

    def end_fetch(self) -> None:
        """Called by :class:`PlannedCollection` when a demand fetch has
        returned its batch.  Default: nothing.  Adapters that keep buffers
        across fetches give back here those the fetch did not use.
        Wrappers must delegate to their inner adapter."""

    def close(self) -> None:
        """Release OS resources (file handles).  Default: nothing to do
        (mmap-backed stores release on GC).  Reached through
        :meth:`PlannedCollection.release`; ``read_range`` after close may
        raise.  Wrappers must delegate to their inner adapter."""


# --------------------------------------------------------------------- CSR
class CSRAdapter(StorageAdapter):
    """Single CSR shard (one AnnData-like file)."""

    def __init__(self, store: CSRStore):
        self.store = store
        self.pool = BufferPool()  # read extents and gathered batches

    def __len__(self) -> int:
        return len(self.store)

    def read_range(self, start: int, stop: int) -> CSRBatch:
        return self.store.read_range(start, stop, self.pool)

    def take(self, piece: CSRBatch, rows: np.ndarray) -> CSRBatch:
        return piece[rows]

    def concat(self, pieces: Sequence[CSRBatch]) -> CSRBatch:
        return _concat_batches(list(pieces), self.store.n_var)

    def gather(self, sources: Sequence[tuple[CSRBatch, np.ndarray]]) -> CSRBatch:
        return gather_rows(sources, self.store.n_var, self.pool)

    def gather_nbytes(self, ranges: Sequence[tuple[CSRBatch, int, int]]) -> int:
        return gathered_nbytes(ranges)

    def end_fetch(self) -> None:
        self.pool.trim()

    def nbytes_of(self, rows: np.ndarray) -> int:
        rows = np.asarray(rows, dtype=np.int64)
        nnz = (self.store._indptr[rows + 1] - self.store._indptr[rows]).sum()
        per = self.store._data.dtype.itemsize + self.store._indices.dtype.itemsize
        return int(nnz) * per

    @property
    def avg_row_bytes(self) -> float:
        return self.store.avg_row_bytes

    @property
    def schema(self) -> dict:
        return {
            "kind": "csr",
            "n_obs": self.store.n_obs,
            "n_var": self.store.n_var,
            "obs_keys": list(self.store.obs.keys()),
        }

    def obs_keys(self) -> list[str]:
        return list(self.store.obs.keys())

    def obs_column(self, key: str) -> np.ndarray:
        return self.store.obs[key]


class CSRCompositeAdapter(StorageAdapter):
    """Shared plumbing for MANY CSR-shaped row stores behind one row space.

    A "CSR-shaped store" is anything with ``read_range(start, stop) ->
    CSRBatch`` plus ``_indptr``/``_data``/``_indices`` arrays and
    ``avg_row_bytes`` (``CSRStore``, ``H5adStore``).  Subclasses
    (:class:`ShardedCSRAdapter`, :class:`~repro.data.h5ad
    .ShardedH5adAdapter`) supply the store list + schema/obs access; shard
    edges are planner ``boundaries`` (a physical read never crosses one,
    so :meth:`read_range` dispatches to exactly one store), and the batch
    algebra / nnz byte accounting live here ONCE.
    """

    def __init__(self, stores: Sequence[Any], n_var: int):
        if not stores:
            raise ValueError("need at least one shard")
        self.stores = list(stores)
        self.n_var = int(n_var)
        sizes = np.array([len(s) for s in self.stores], dtype=np.int64)
        self.offsets = np.concatenate(([0], np.cumsum(sizes)))
        self.n_obs = int(self.offsets[-1])
        self.pool = BufferPool()  # read extents and gathered batches

    def __len__(self) -> int:
        return self.n_obs

    def boundaries(self) -> np.ndarray:
        return self.offsets

    def read_range(self, start: int, stop: int) -> CSRBatch:
        sid = int(np.searchsorted(self.offsets, start, side="right") - 1)
        off = int(self.offsets[sid])
        return self.stores[sid].read_range(start - off, stop - off, self.pool)

    def take(self, piece: CSRBatch, rows: np.ndarray) -> CSRBatch:
        return piece[rows]

    def concat(self, pieces: Sequence[CSRBatch]) -> CSRBatch:
        return _concat_batches(list(pieces), self.n_var)

    def gather(self, sources: Sequence[tuple[CSRBatch, np.ndarray]]) -> CSRBatch:
        return gather_rows(sources, self.n_var, self.pool)

    def gather_nbytes(self, ranges: Sequence[tuple[CSRBatch, int, int]]) -> int:
        return gathered_nbytes(ranges)

    def end_fetch(self) -> None:
        self.pool.trim()

    def nbytes_of(self, rows: np.ndarray) -> int:
        rows = np.asarray(rows, dtype=np.int64)
        sids = np.searchsorted(self.offsets, rows, side="right") - 1
        total = 0
        for sid in np.unique(sids):
            shard = self.stores[int(sid)]
            local = rows[sids == sid] - int(self.offsets[sid])
            nnz = (shard._indptr[local + 1] - shard._indptr[local]).sum()
            per = shard._data.dtype.itemsize + shard._indices.dtype.itemsize
            total += int(nnz) * per
        return total

    @property
    def avg_row_bytes(self) -> float:
        return float(np.mean([s.avg_row_bytes for s in self.stores]))


class ShardedCSRAdapter(CSRCompositeAdapter):
    """Sharded CSR (the 14 Tahoe plate files) — boundaries at shard edges."""

    def __init__(self, store: ShardedCSRStore):
        super().__init__(store.shards, store.n_var)
        self.store = store

    @property
    def schema(self) -> dict:
        return {
            "kind": "csr",
            "n_obs": self.store.n_obs,
            "n_var": self.store.n_var,
            "n_shards": len(self.store.shards),
            "obs_keys": self.store.obs_keys,
        }

    def obs_keys(self) -> list[str]:
        return self.store.obs_keys

    def obs_column(self, key: str) -> np.ndarray:
        return self.store.obs_column(key)


# ----------------------------------------------------------------- chunked
class ChunkedAdapter(StorageAdapter):
    """Zarr-style chunked dense store — boundaries at chunk edges, so the
    planner's run count equals objects touched (request semantics)."""

    def __init__(self, store: ChunkedStore):
        self.store = store

    def __len__(self) -> int:
        return len(self.store)

    def boundaries(self) -> np.ndarray:
        edges = np.arange(self.store.n_chunks + 1, dtype=np.int64) * self.store.chunk_rows
        edges[-1] = self.store.n
        return edges

    def read_range(self, start: int, stop: int) -> np.ndarray:
        return self.store.read_range(start, stop)

    def take(self, piece: np.ndarray, rows: np.ndarray) -> np.ndarray:
        return piece[rows]

    def concat(self, pieces: Sequence[np.ndarray]) -> np.ndarray:
        return np.concatenate(list(pieces))

    def gather_nbytes(self, ranges: Sequence[tuple[np.ndarray, int, int]]) -> int:
        return int(sum(piece[lo:hi].nbytes for piece, lo, hi in ranges))

    def nbytes_of(self, rows: np.ndarray) -> int:
        return int(len(np.asarray(rows)) * self.store.d * 4)

    @property
    def avg_row_bytes(self) -> float:
        return self.store.avg_row_bytes

    @property
    def schema(self) -> dict:
        return {
            "kind": "dense",
            "n_obs": self.store.n,
            "n_var": self.store.d,
            "chunk_rows": self.store.chunk_rows,
            "obs_keys": list(self.store.obs.keys()),
        }

    def obs_keys(self) -> list[str]:
        return list(self.store.obs.keys())

    def obs_column(self, key: str) -> np.ndarray:
        return self.store.obs[key]


# ------------------------------------------------------------------ tokens
class TokenAdapter(StorageAdapter):
    """Flat token stream viewed as sequences (LM pretraining workload)."""

    def __init__(self, store: TokenStore):
        self.store = store

    def __len__(self) -> int:
        return len(self.store)

    def read_range(self, start: int, stop: int) -> dict:
        return self.store.read_range(start, stop)

    def take(self, piece: dict, rows: np.ndarray) -> dict:
        return {k: v[rows] for k, v in piece.items()}

    def concat(self, pieces: Sequence[dict]) -> dict:
        keys = pieces[0].keys()
        return {k: np.concatenate([p[k] for p in pieces]) for k in keys}

    def gather_nbytes(self, ranges: Sequence[tuple[dict, int, int]]) -> int:
        return int(sum(v[lo:hi].nbytes for piece, lo, hi in ranges for v in piece.values()))

    def nbytes_of(self, rows: np.ndarray) -> int:
        return int(len(np.asarray(rows)) * self.store.avg_row_bytes)

    @property
    def avg_row_bytes(self) -> float:
        return self.store.avg_row_bytes

    @property
    def schema(self) -> dict:
        return {
            "kind": "tokens",
            "n_seqs": self.store.n_seqs,
            "seq_len": self.store.seq_len,
            "vocab_size": self.store.vocab_size,
        }


# --------------------------------------------------------- planned wrapper
class PlannedCollection:
    """A :class:`Collection` that executes fetches through the shared planner.

    ``fetch(rows)`` maps rows to fixed-size cache blocks, serves resident
    blocks from the LRU byte-budgeted :class:`~repro.data.readplan.BlockCache`
    and reads the rest as maximal contiguous runs — merged across shard
    boundaries in planning, split back at physical boundaries and at
    ``max_extent_rows`` for execution.  The batch is gathered in one
    :meth:`StorageAdapter.gather` from the read extents and the blocks
    served; a missed block is cut out of its extent only for the cache or
    a waiting fetch that will read it.  One IOStats record per fetch counts
    runs (physical reads actually issued), bytes, rows, and block cache
    hits/misses — identically for every backend.

    **Async execution** (opt-in, off by default so the synchronous path is
    bit-for-bit the PR-1 behavior):

    - ``io_workers > 1`` — a fetch's miss extents execute concurrently on a
      shared bounded thread pool (mmap/numpy/decompress reads release the
      GIL); pieces are gathered in plan order, so delivery stays
      bit-identical to the synchronous path.
    - ``readahead > 0`` — :meth:`prefetch` issues a *future* fetch's read
      plan in the background (``ScDataset`` calls it with the next fetches'
      indices before blocking on the current fetch).  In-flight blocks are
      registered in a rendezvous table; a fetch that needs one waits on its
      future instead of re-reading, so double-buffering never duplicates
      physical reads.  ``readahead="auto"`` hands the depth to a
      :class:`~repro.data.readplan.ReadaheadController`: it grows the window
      while the cache budget and in-flight headroom allow and shrinks it
      (down to zero) under eviction pressure — adaptation changes only WHEN
      bytes are read, never which rows a batch contains.
    - ``admission`` — ``"always"`` (default LRU), ``"auto"``, or ``"never"``.
      ``"auto"`` is two detectors layered over the LRU: a
      :class:`~repro.data.readplan.StreamDetector` spots forward-streaming
      epochs and bypasses LRU insertion for all but the fetch's last block
      (pure streams churn the cache for zero hits), and a TinyLFU-style
      :class:`~repro.data.readplan.FrequencySketch` takes over from pure LRU
      the moment the sampled working set exceeds ``cache_bytes`` (an
      insertion needs an eviction): a candidate block must be *hotter* than
      the LRU victim to displace it, which keeps hot blocks resident across
      weighted / class-balanced redraws instead of thrashing.

    **Resilience** (all off by default — the failure-free path is byte for
    byte the legacy behavior):

    - ``retries > 0`` — every physical read runs under a
      :class:`~repro.data.faults.RetryPolicy`: transient failures
      (``OSError``/``TimeoutError``, incl. injected
      :class:`~repro.data.faults.TransientStorageError`) are retried with
      exponential backoff + decorrelated jitter, bounded by the attempt
      budget and the optional per-read ``retry_deadline_s``; exhaustion
      raises a terminal :class:`~repro.data.faults.RetryBudgetExhausted`.
      Failed rendezvous futures are deregistered BEFORE they are poisoned,
      and a waiter that observes a poisoned future re-issues the block
      idempotently through the rendezvous table — delivered batches under
      faults stay bitwise identical to the fault-free run.
    - ``hedge_factor > 0`` (needs ``io_workers > 1``) — a miss read that
      overruns ``max(hedge_min_s, hedge_factor * wait_EWMA)`` gets a
      duplicate read submitted; first success wins, the loser is discarded
      (``hedges_issued`` / ``hedges_won`` count the duplicates — their
      physical work is deliberately NOT folded into runs/bytes, which
      describe delivered reads).
    - ``breaker_threshold > 0`` — consecutive failures of one shard open a
      :class:`~repro.data.faults.ShardBreaker`; while open, background
      prefetch skips the shard entirely and demand fetches probe it with a
      capped retry budget until a half-open probe closes it
      (``breaker_opens`` / ``breaker_closes`` in IOStats).

    Thread-safe: the BlockCache and the rendezvous table lock their own
    bookkeeping; reads and batch assembly run unlocked so PrefetchPool
    workers overlap I/O and CPU.  In async mode concurrent fetches of the
    same block rendezvous on one read; results are identical either way.

    ``cache_bytes=0`` disables caching: fetches become pure planned reads
    (still coalesced and boundary/extent-split, still uniformly accounted).
    """

    def __init__(
        self,
        adapter: StorageAdapter,
        *,
        iostats: Optional[IOStats] = None,
        cache_bytes: int = DEFAULT_CACHE_BYTES,
        block_rows: int = DEFAULT_BLOCK_ROWS,
        max_extent_rows: Optional[int] = DEFAULT_MAX_EXTENT_ROWS,
        io_workers: int = 1,
        readahead=0,
        admission: str = "always",
        cache_policy: str = "lru",
        retries: int = 0,
        retry_backoff_s: float = 0.005,
        retry_max_backoff_s: float = 0.25,
        retry_deadline_s: float = 0.0,
        hedge_factor: float = 0.0,
        hedge_min_s: float = 0.05,
        breaker_threshold: int = 0,
        breaker_cooldown_s: float = 1.0,
    ):
        if block_rows <= 0:
            raise ValueError("block_rows must be positive")
        if io_workers < 1:
            raise ValueError("io_workers must be >= 1")
        if retries < 0 or hedge_factor < 0 or breaker_threshold < 0:
            raise ValueError("resilience knobs must be non-negative")
        if hedge_min_s <= 0:
            raise ValueError("hedge_min_s must be positive")
        readahead = normalize_readahead(readahead)
        ra_auto = readahead == "auto"
        if admission not in ("always", "auto", "never"):
            raise ValueError(f"admission must be always|auto|never, got {admission!r}")
        if cache_policy not in ("lru", "wtinylfu"):
            raise ValueError(
                f"cache_policy must be lru|wtinylfu, got {cache_policy!r}"
            )
        if (ra_auto or readahead > 0) and cache_bytes <= 0:
            # staged blocks hand over through the cache; without one every
            # prefetched block would silently be read twice
            raise ValueError("readahead > 0 requires cache_bytes > 0")
        self.adapter = adapter
        self.iostats = iostats if iostats is not None else IOStats()
        adapter.bind_iostats(self.iostats)
        self.cache = BlockCache(cache_bytes)
        if cache_policy == "wtinylfu":
            # same interface, windowed segmented organization (scan-resistant
            # protected segment — see SegmentedBlockCache)
            self.cache = SegmentedBlockCache(cache_bytes)
        self.cache_policy = cache_policy
        self.block_rows = int(block_rows)
        self.max_extent_rows = max_extent_rows
        self.io_workers = int(io_workers)
        self._ra_fixed = 0 if ra_auto else int(readahead)
        self._ra_controller = (
            ReadaheadController(self.cache) if ra_auto else None
        )  # guarded-by: external — observe() under _fl; depth reads stale-ok
        self.admission = admission
        # TinyLFU frequency sketch backing admission="auto" in the weighted
        # (non-streaming) regime; sized to the dataset's block universe so
        # collisions stay rare without over-allocating on small collections
        self._sketch: Optional[FrequencySketch] = None  # guarded-by: external
        if admission == "auto" and cache_bytes > 0:
            n_blocks = max(1, (len(adapter) + block_rows - 1) // block_rows)
            width = 1 << min(16, max(10, int(np.ceil(np.log2(2 * n_blocks)))))
            self._sketch = FrequencySketch(width=width)
        self._boundaries = adapter.boundaries()
        self._stream = StreamDetector()  # guarded-by: _fl
        self._avg_row_bytes = float(adapter.avg_row_bytes)
        self._executor: Optional[ThreadPoolExecutor] = None  # guarded-by: _exec_lock
        self._closed = False  # guarded-by: _exec_lock
        self._exec_lock = threading.Lock()
        # rendezvous table: block id -> Future resolving to the block's value
        # while a background (or concurrent) read of it is in flight
        self._inflight: dict[int, Future] = {}  # guarded-by: _fl
        # blocks staged by prefetch, not yet consumed by any fetch: their
        # first consumption counts as `prefetched` (not a cache hit), and
        # under a bypassing admission policy they are dropped after use
        self._pf_marks: set[int] = set()  # guarded-by: _fl
        self._fl = threading.Lock()
        # cross-rank attribution for the elastic fabric: consumers identify
        # themselves via tagged(); block id -> tag of the rank whose read
        # produced the resident value.  A tagged fetch that obtains a block
        # another tag produced counts one `shared_rank_hits` — the read the
        # shared cache saved it.  Untagged traffic neither claims nor counts.
        self._tag = threading.local()
        self._block_owner: dict[int, Any] = {}  # guarded-by: _fl
        # resilience: policy objects are frozen/internally-locked, set once
        self._retry = None  # guarded-by: external — frozen RetryPolicy
        if retries > 0:
            from .faults import RetryPolicy  # lazy: faults imports backend

            self._retry = RetryPolicy(
                retries=int(retries),
                backoff_s=float(retry_backoff_s),
                max_backoff_s=float(retry_max_backoff_s),
                deadline_s=float(retry_deadline_s),
            )
        self._breaker = None  # guarded-by: external — set once, locks itself
        if breaker_threshold > 0:
            from .faults import ShardBreaker  # lazy: faults imports backend

            self._breaker = ShardBreaker(
                int(breaker_threshold), float(breaker_cooldown_s)
            )
        self.hedge_factor = float(hedge_factor)
        self.hedge_min_s = float(hedge_min_s)
        # per-physical-read seconds, smoothed: drives the hedge deadline and
        # the readahead controller's storage-tier signal.  A single float
        # store/load — the benign read-modify-write race only blurs the
        # smoothing, never corrupts scheduling.
        self._wait_ewma = 0.0  # guarded-by: external — benign-race EWMA

    @property
    def readahead(self) -> int:
        """Current double-buffer depth.  Fixed ints return themselves; under
        ``readahead="auto"`` this is the controller's live depth — callers
        (``ScDataset``) consult it per fetch, so the window tracks the
        feedback loop without any coordination."""
        if self._ra_controller is not None:
            return self._ra_controller.depth
        return self._ra_fixed

    @property
    def readahead_auto(self) -> bool:
        return self._ra_controller is not None

    @property
    def async_enabled(self) -> bool:
        return self.io_workers > 1 or self.readahead > 0 or self.readahead_auto

    def epoch_boundary(self) -> None:
        """Signal an epoch boundary (``ScDataset`` calls this between
        epochs).  The access regime may change across it — a weighted epoch
        can follow a streaming one and vice versa — so the stream detector
        restarts cold (its streak and high-water mark describe the OLD
        epoch) and the readahead controller opens a fresh eviction window.
        Cache contents and the frequency sketch persist: the data did not
        change, only the access pattern might."""
        with self._fl:
            self._stream.reset()
            if self._ra_controller is not None:
                self._ra_controller.epoch_boundary()

    @contextmanager
    def tagged(self, tag: Any):
        """Attribute this thread's fetch/prefetch traffic to ``tag`` (a rank
        id in the elastic fabric).  Blocks read while tagged are owned by the
        tag; a later tagged consumer of a block owned by a DIFFERENT tag
        records one ``shared_rank_hits`` — the physical read that co-located
        rank loaders sharing one collection did not have to repeat.  Tags are
        thread-local and restore on exit, so nesting and pooling are safe."""
        prev = getattr(self._tag, "value", None)
        self._tag.value = tag
        try:
            yield
        finally:
            self._tag.value = prev

    def _pool(self) -> Optional[ThreadPoolExecutor]:
        if not self.async_enabled:
            return None
        # double-checked fast path: a stale non-None executor is the common
        # steady state, and close() never swaps a live executor for another
        ex = self._executor  # unlocked-ok: double-checked fast path
        if ex is not None:
            return ex
        with self._exec_lock:
            if self._closed:
                return None
            if self._executor is None:
                self._executor = ThreadPoolExecutor(
                    max_workers=self.io_workers, thread_name_prefix="scds-io"
                )
            return self._executor

    def close(self) -> None:
        """Shut down the I/O executor and drop any unconsumed prefetch
        staging.  Permanent: stragglers still iterating fall back to
        synchronous reads rather than resurrecting a leaked executor.
        Adapter file handles stay open for those stragglers — use
        :meth:`release` when the collection is truly done."""
        with self._exec_lock:
            self._closed = True
            ex, self._executor = self._executor, None
        if ex is not None:
            ex.shutdown(wait=True)
        with self._fl:
            marks, self._pf_marks = self._pf_marks, set()
        for b in marks:  # staged-but-never-consumed blocks must not linger
            self.cache.discard(b)

    def release(self) -> None:
        """:meth:`close` + release the adapter's OS resources (``h5ad://``
        file descriptors / HDF5 handles).  Unlike ``close``, the collection
        must NOT be used afterwards — subsequent fetches may raise."""
        self.close()
        self.adapter.close()

    def __len__(self) -> int:
        return len(self.adapter)

    @property
    def schema(self) -> dict:
        return self.adapter.schema

    @property
    def avg_row_bytes(self) -> float:
        return self.adapter.avg_row_bytes

    def obs_keys(self) -> list[str]:
        return self.adapter.obs_keys()

    def obs_column(self, key: str) -> np.ndarray:
        return self.adapter.obs_column(key)

    def nbytes_of(self, rows) -> int:
        return self.adapter.nbytes_of(np.asarray(rows, dtype=np.int64))

    def _spans_for_blocks(self, blocks: np.ndarray) -> np.ndarray:
        """Cache-block ids -> the physical read plan, an ``(n, 2)`` span
        array (shared by plan/fetch)."""
        spans = blocks_to_row_spans(blocks, self.block_rows, len(self.adapter))
        spans = split_at_boundaries(spans, self._boundaries)
        return split_max_extent(spans, self.max_extent_rows)

    def plan(self, rows) -> np.ndarray:
        """The physical reads a COLD-cache fetch of ``rows`` would issue, as
        an ``(n, 2)`` int64 array of ``[start, stop)`` spans.

        Exactly the spans ``fetch`` executes when nothing is resident —
        including the rounding of rows to ``block_rows`` cache blocks; a
        warm cache only removes spans from this list.
        """
        rows = np.asarray(rows, dtype=np.int64)
        return self._spans_for_blocks(np.unique(rows // self.block_rows))

    def __getitem__(self, rows) -> Any:
        return self.fetch(rows)

    # ---------------------------------------------------- read primitives
    def _shard_of(self, row: int) -> int:
        """Physical shard (boundary interval) containing ``row`` — the unit
        of circuit breaking.  Boundary-free adapters are one shard 0."""
        edges = self._boundaries
        if edges is None or len(edges) <= 2:
            return 0
        return int(np.searchsorted(edges, row, side="right") - 1)

    def _read_one(self, lo: int, hi: int) -> tuple[Any, int]:
        """ONE logical read (retried under the policy, if any) + its per-read
        simulated latency, slept in the reading thread so concurrent reads
        overlap it like real storage.  Also feeds the wait EWMA — backoff
        sleeps inflate it, which conservatively widens the hedge deadline
        while storage is misbehaving."""
        t0 = time.perf_counter()
        with span("scdataset.read", start=int(lo), stop=int(hi)):
            piece = self._resilient_read(lo, hi)
        nb = piece_nbytes(piece)
        self.iostats.sleep_for(runs=1, bytes_read=nb)
        dt = time.perf_counter() - t0
        prev = self._wait_ewma
        self._wait_ewma = dt if prev == 0.0 else 0.8 * prev + 0.2 * dt
        return piece, nb

    def _resilient_read(self, lo: int, hi: int) -> Any:
        """One logical contiguous read: bounded retries with decorrelated-
        jitter backoff and an optional per-read deadline, feeding the
        per-shard circuit breaker.  With nothing configured this is a bare
        ``adapter.read_range`` — the legacy path, byte for byte."""
        retry, breaker = self._retry, self._breaker
        if retry is None and breaker is None:
            return self.adapter.read_range(lo, hi)
        from .faults import RetryBudgetExhausted, is_transient  # lazy: cycle

        shard = self._shard_of(lo)
        budget = retry.retries if retry is not None else 0
        if breaker is not None and breaker.admit(shard) == "open":
            # breaker open and not our turn to probe: demand reads still go
            # through (delivery must survive), but with a capped budget —
            # the blackout is outlived by backoff, not by hammering a shard
            # known to be dark
            budget = min(budget, 1)
        deadline = (
            time.monotonic() + retry.deadline_s
            if retry is not None and retry.deadline_s > 0
            else None
        )
        attempt, prev_delay = 0, 0.0
        while True:
            try:
                piece = self.adapter.read_range(lo, hi)
            except BaseException as e:
                # breaker transitions are recorded by THIS caller, outside
                # the breaker's lock (no breaker->stats lock edge)
                if breaker is not None and breaker.record_failure(shard):
                    self.iostats.record_resilience(breaker_opens=1)
                if retry is None or not is_transient(e):
                    raise
                if attempt >= budget:
                    raise RetryBudgetExhausted(
                        f"read [{lo}, {hi}) failed after {attempt + 1} "
                        f"attempts (budget {budget})"
                    ) from e
                delay = retry.backoff(lo, hi, attempt, prev_delay)
                if deadline is not None:
                    left = deadline - time.monotonic()
                    if left <= 0.0:
                        raise RetryBudgetExhausted(
                            f"read [{lo}, {hi}) deadline "
                            f"({retry.deadline_s:.3f}s) exhausted after "
                            f"{attempt + 1} attempts"
                        ) from e
                    delay = min(delay, left)
                time.sleep(delay)
                self.iostats.record_resilience(retries=1, retry_wait_s=delay)
                prev_delay = delay
                attempt += 1
                continue
            if breaker is not None and breaker.record_success(shard):
                self.iostats.record_resilience(breaker_closes=1)
            return piece

    def _gather_hedged(
        self,
        read_futs: list,
        spans,
        pool: ThreadPoolExecutor,
        pend,
    ) -> list:
        """Gather a fetch's concurrent miss reads with tail hedging.

        Each primary gets ``max(hedge_min_s, hedge_factor * wait_EWMA)``
        from fetch issue time; one that overruns it races a duplicate read,
        first SUCCESS wins and the loser is discarded.  Both sides execute
        the identical ``_read_one`` over the identical span, so which one
        wins can never change delivered bytes — only ``hedges_won``."""
        t_issue = time.perf_counter()
        out = []
        for fut, (lo, hi) in zip(read_futs, spans):
            ewma = self._wait_ewma
            tail = max(self.hedge_min_s, self.hedge_factor * ewma)
            left = t_issue + tail - time.perf_counter()
            try:
                out.append(fut.result(timeout=max(0.0, left)))
                continue
            except _cf.TimeoutError:  # py3.10: NOT the builtin TimeoutError
                pass
            hedge = pool.submit(self._read_one_for, lo, hi, pend)
            self.iostats.record_resilience(hedges_issued=1)
            val, hedge_won = self._first_success(fut, hedge)
            if hedge_won:
                self.iostats.record_resilience(hedges_won=1)
            out.append(val)
        return out

    @staticmethod
    def _first_success(primary: Future, hedge: Future) -> tuple[Any, bool]:
        """Race a late primary against its hedge; first SUCCESS wins (a
        failed racer defers to the other, both failing re-raises the last
        failure).  Ties prefer the primary.  Returns (result, hedge_won)."""
        waiting = {primary, hedge}
        last_exc: Optional[BaseException] = None
        while waiting:
            done, waiting = _cf.wait(waiting, return_when=_cf.FIRST_COMPLETED)
            if primary in done:
                exc = primary.exception()
                if exc is None:
                    return primary.result(), False
                last_exc = exc
            if hedge in done:
                exc = hedge.exception()
                if exc is None:
                    return hedge.result(), True
                last_exc = exc
        assert last_exc is not None
        raise last_exc

    def _reissue_block(self, b: int) -> tuple[Any, int, int, str]:
        """Idempotent recovery of ONE block whose rendezvous producer
        failed.  Re-checks the cache, joins any newer in-flight read, else
        claims the block in the rendezvous table and reads it synchronously
        (retries included, so other waiters of the failed future converge on
        this one recovery read).  Returns ``(value, physical_runs,
        bytes_read, outcome)`` for the calling fetch's accounting; outcome
        ``"served"`` means no new physical read was issued here.  A second
        failure propagates — recovery gets one round, the retry budget
        lives inside the read itself."""
        with self._fl:
            val = self.cache.peek(b)
            if val is not None:
                return val, 0, 0, "served"
            other = self._inflight.get(b)
            if other is None:
                f: Future = Future()
                self._inflight[b] = f
                my_tag = getattr(self._tag, "value", None)
                if my_tag is not None:
                    self._block_owner[b] = my_tag
                else:
                    self._block_owner.pop(b, None)
        if other is not None:
            # someone else is already recovering it; their terminal failure
            # (RetryBudgetExhausted is not transient) is terminal for us too
            return other.result(), 0, 0, "served"
        try:
            spans = self._spans_for_blocks(np.asarray([b]))
            results = [self._read_one(lo, hi) for lo, hi in spans]
            nb = sum(x for _, x in results)
            val = self._cut(b, spans, [p for p, _ in results])
            with self._fl:
                streaming = self._stream.streaming
            outcome = self._cache_put(b, val, last_block=b, streaming=streaming)
            f.set_result(val)
            with self._fl:
                if self._inflight.get(b) is f:
                    del self._inflight[b]
            return val, len(spans), nb, outcome
        except BaseException as e:
            # deregister BEFORE poisoning, same publish discipline as the
            # fetch/prefetch producers
            with self._fl:
                if self._inflight.get(b) is f:
                    del self._inflight[b]
            f.set_exception(e)
            raise

    def _read_one_for(self, lo: int, hi: int, pend) -> tuple[Any, int]:
        """Pool-thread read on behalf of a (possibly deferred) consumer:
        per-thread recording inside ``read_range`` (cloud request counters)
        must land in the CONSUMER's capture buffer, or a speculative
        duplicate's requests would pollute the delivered-data totals."""
        with self.iostats.borrowed_pending(pend):
            return self._read_one(lo, hi)

    def _cache_put(
        self, block: int, val: Any, *, last_block: int, streaming: bool
    ) -> str:
        """LRU insertion subject to the admission policy; returns the
        outcome (``"stored"`` | ``"bypassed"`` | ``"rejected"``) for the
        fetch's admission accounting.  ``streaming`` is the detector state
        captured once at fetch start (so one fetch applies one consistent
        policy).  In streaming mode only the fetch's last block is kept (the
        next fetch may straddle it); the rest would churn the cache for zero
        future hits.  Outside the streaming regime, ``admission="auto"``
        inserts through the TinyLFU duel (:meth:`BlockCache.put_admit`):
        once the working set exceeds the budget, a candidate must be hotter
        than the LRU victim to displace it."""
        if self.admission == "never" or (streaming and block != last_block):
            self.cache.bypass()
            return "bypassed"
        nb = piece_nbytes(val)
        if (self._sketch is not None and not streaming
                and nb <= self.cache.max_bytes):
            # (oversized values fall through to plain put's silent refusal —
            # never cachable under ANY policy, so not a frequency rejection)
            stored = self.cache.put_admit(block, val, nb, self._sketch.estimate)
            return "stored" if stored else "rejected"
        self.cache.put(block, val, nb)
        return "stored"

    def _block_parts(
        self, bb: int, spans: np.ndarray, pieces: Sequence[Any]
    ) -> tuple[Any, list]:
        """Where missed block ``bb``'s rows lie among a fetch's read extents:
        ``(extent, [])`` where the block is a whole extent, else ``(None,
        [(piece, first, stop), ...])`` per extent holding some of it, rows
        relative to the extent (a block splits where a shard edge or
        ``max_extent_rows`` cuts it)."""
        B = self.block_rows
        blo, bhi = bb * B, min((bb + 1) * B, len(self.adapter))
        k = int(np.searchsorted(spans[:, 0], blo, side="right")) - 1
        parts = []
        while k < len(spans) and spans[k, 0] < bhi:
            lo, hi = int(spans[k, 0]), int(spans[k, 1])
            if (lo, hi) == (blo, bhi):
                return pieces[k], []
            parts.append((pieces[k], max(lo, blo) - lo, min(hi, bhi) - lo))
            k += 1
        return None, parts

    def _cut(self, bb: int, spans: np.ndarray, pieces: Sequence[Any]) -> Any:
        """Missed block ``bb`` as a value of its own (a copy, so a cached
        block never pins the extent it came from), or the extent itself
        where the two coincide."""
        whole, parts = self._block_parts(bb, spans, pieces)
        if whole is not None:
            return whole
        return self.adapter.gather([(p, np.arange(a, z)) for p, a, z in parts])

    def _publish_misses(
        self,
        missing: list[int],
        spans: np.ndarray,
        pieces: Sequence[Any],
        claimed: dict[int, Future],
        *,
        last_block: int,
        streaming: bool,
    ) -> tuple[int, int, int]:
        """Insert a fetch's missed blocks into the cache, in block order, as
        the admission policy says, and hand claimed blocks to their futures.

        A block is cut out of its extent only if someone will read it: a
        claimant (async mode), or the cache, which under plain LRU still
        holds at the end only the newest blocks that fit its budget
        (:meth:`BlockCache.survivors`).  The others are inserted as None
        and evicted within the same :meth:`BlockCache.put_many`, so the
        cache's contents, order and counters come out as if every block had
        been cut.  Where the outcome is known only by trying (the TinyLFU
        duel), every block is cut.  Returns ``(blocks cut, bypassed,
        rejected)``.
        """
        values: dict[int, Any] = {}
        bypassed = rejected = 0
        if self._sketch is not None and not streaming:
            for bb in missing:
                values[bb] = self._cut(bb, spans, pieces)
                outcome = self._cache_put(bb, values[bb], last_block=last_block,
                                          streaming=streaming)
                bypassed += outcome == "bypassed"
                rejected += outcome == "rejected"
        else:
            kept = [bb for bb in missing
                    if self.admission != "never" and (not streaming or bb == last_block)]
            bypassed = len(missing) - len(kept)
            if bypassed:
                self.cache.bypass(bypassed)
            if self.cache.max_bytes <= 0:
                kept = []  # no cache: an insertion would do nothing at all
            sizes = []
            for bb in kept:
                whole, parts = self._block_parts(bb, spans, pieces)
                sizes.append(piece_nbytes(whole) if whole is not None
                             else self.adapter.gather_nbytes(parts))
            for bb, keep in zip(kept, self.cache.survivors(sizes)):
                if keep and bb not in values:
                    values[bb] = self._cut(bb, spans, pieces)
            for bb in claimed:
                if bb not in values:
                    values[bb] = self._cut(bb, spans, pieces)
            self.cache.put_many(
                [(bb, values.get(bb), nb) for bb, nb in zip(kept, sizes)]
            )
        for bb, f in claimed.items():
            f.set_result(values[bb])
        return len(values), bypassed, rejected

    def _sources(
        self,
        srows: np.ndarray,
        local: dict[int, Any],
        spans: np.ndarray,
        pieces: Sequence[Any],
    ) -> list[tuple[Any, np.ndarray]]:
        """The ``(piece, rows)`` runs of sorted rows ``srows`` for
        :meth:`StorageAdapter.gather`: a row of a block this fetch holds a
        value of (cache hit, rendezvous) comes from that block, any other
        from the read extent that holds it."""
        B = self.block_rows
        sblocks = srows // B
        key = sblocks
        if len(spans):
            held = np.isin(sblocks, np.fromiter(local, dtype=np.int64, count=len(local)))
            extent = np.searchsorted(spans[:, 0], srows, side="right") - 1
            key = np.where(held, sblocks, -1 - extent)
        edges = np.flatnonzero(np.diff(key) != 0) + 1
        out = []
        for a, z in zip([0, *edges.tolist()], [*edges.tolist(), len(srows)]):
            k = int(key[a])
            if k >= 0:
                out.append((local[k], srows[a:z] - k * B))
            else:
                out.append((pieces[-1 - k], srows[a:z] - int(spans[-1 - k, 0])))
        return out

    def fetch(self, rows) -> Any:
        rows = np.asarray(rows, dtype=np.int64)
        with span("scdataset.plan", rows=int(rows.size)):
            try:
                return self._fetch(rows)
            finally:
                self.adapter.end_fetch()

    def _fetch(self, rows: np.ndarray) -> Any:
        t0 = time.perf_counter()
        if rows.ndim == 0:
            rows = rows[None]
        if len(rows) == 0:
            raise ValueError("fetch of zero rows")
        B = self.block_rows
        n = len(self.adapter)
        lo_row, hi_row = int(rows.min()), int(rows.max())
        if lo_row < 0 or hi_row >= n:
            # negative rows would silently wrap through numpy indexing in
            # the adapters; catch both ends here with a real message
            raise IndexError(
                f"rows out of range [0, {n}): min={lo_row}, max={hi_row}"
            )
        blocks = np.unique(rows // B)
        streaming = False
        if self.admission == "auto" or self._ra_controller is not None:
            # observe under the rendezvous lock (serialized) and capture the
            # state ONCE so this fetch applies one consistent policy
            with self._fl:
                if self.admission == "auto":
                    streaming = self._stream.observe(blocks)
                if self._ra_controller is not None:
                    self._ra_controller.observe(
                        len(blocks) * B * self._avg_row_bytes,
                        len(blocks),
                        len(self._inflight),
                        wait_s=self._wait_ewma,
                    )
        if self._sketch is not None:
            # one popularity touch per block per fetch — the frequency
            # signal TinyLFU admission duels with.  Vectorized and OUTSIDE
            # the rendezvous lock (the sketch tolerates concurrent touches);
            # holding _fl here would serialize every concurrent fetch.
            self._sketch.touch_many(blocks)
        last_block = int(blocks[-1])
        adm_bypassed = 0
        adm_rejected = 0

        # ---- cache lookup (BlockCache locks internally) ------------------
        local: dict[int, Any] = {}
        missing: list[int] = []
        served: list[int] = []
        for b in blocks.tolist():
            piece = self.cache.get(b)
            if piece is None:
                missing.append(b)
            else:
                local[b] = piece
                served.append(b)
        hits = len(served)

        # ---- rendezvous + claim (async mode) -----------------------------
        # One critical section decides, per missing block: wait on an
        # in-flight read, take a just-landed cache value, or claim the read
        # for ourselves (registering a future other fetches can wait on).
        # It also reconciles prefetch markers: a cache-served block staged by
        # prefetch and consumed here for the first time is `prefetched`, not
        # a cache hit — readahead must not inflate the hit rate autotune uses.
        waits: dict[int, Future] = {}
        claimed: dict[int, Future] = {}
        pf_blocks: list[int] = []
        my_tag = getattr(self._tag, "value", None)
        if self.async_enabled:
            with self._fl:
                if self._pf_marks:
                    for b in served:
                        if b in self._pf_marks:
                            self._pf_marks.discard(b)
                            pf_blocks.append(b)
                            hits -= 1
                if missing:
                    still: list[int] = []
                    for b in missing:
                        fut = self._inflight.get(b)
                        if fut is not None:
                            waits[b] = fut
                            continue
                        val = self.cache.peek(b)  # landed since the get() above
                        if val is not None:
                            local[b] = val
                            if b in self._pf_marks:
                                self._pf_marks.discard(b)
                                pf_blocks.append(b)
                            else:
                                hits += 1
                            continue
                        f: Future = Future()
                        self._inflight[b] = f
                        claimed[b] = f
                        self._pf_marks.discard(b)  # stale staging: we re-read
                        # ownership claims at CLAIM time, not publish time —
                        # a waiter may consume the future before this fetch
                        # reaches its own accounting pass
                        if my_tag is not None:
                            self._block_owner[b] = my_tag
                        else:
                            self._block_owner.pop(b, None)
                        still.append(b)
                    missing = still

        # ---- plan + issue the physical reads -----------------------------
        bytes_read = 0
        spans = np.empty((0, 2), dtype=np.int64)
        read_futs = None
        pieces: list[Any] = []
        pool: Optional[ThreadPoolExecutor] = None
        pend = None
        if missing:
            spans = self._spans_for_blocks(np.asarray(missing))
            pool = self._pool()
            # a single span normally reads inline (no pool round-trip), but
            # hedging needs a future to race — a lone tail GET is exactly
            # the straggler a hedge exists to duplicate
            if pool is not None and self.io_workers > 1 and (
                len(spans) > 1 or self.hedge_factor > 0.0
            ):
                pend = self.iostats.current_pending()
                read_futs = [
                    pool.submit(self._read_one_for, lo, hi, pend)
                    for lo, hi in spans
                ]

        # ---- gather own reads (plan order), publish the blocks kept -----
        n_cut = 0
        if missing:
            try:
                if read_futs is not None:
                    if self.hedge_factor > 0.0 and pool is not None:
                        results = self._gather_hedged(read_futs, spans, pool, pend)
                    else:
                        results = [f.result() for f in read_futs]
                else:
                    results = [self._read_one(lo, hi) for lo, hi in spans]
                pieces = [p for p, _ in results]
                bytes_read = sum(nb for _, nb in results)
                n_cut, adm_bypassed, adm_rejected = self._publish_misses(
                    missing, spans, pieces, claimed,
                    last_block=last_block, streaming=streaming,
                )
                if claimed:
                    with self._fl:
                        for bb, f in claimed.items():
                            if self._inflight.get(bb) is f:
                                del self._inflight[bb]
            except BaseException as e:
                # deregister BEFORE poisoning the futures: a waiter arriving
                # after this block observes an empty rendezvous slot and
                # issues its own read, instead of latching onto a future
                # that is about to fail (the failure-poisoning bug).  One
                # already holding the future sees the exception and recovers
                # through _reissue_block.
                if claimed:
                    with self._fl:
                        for bb, f in claimed.items():
                            if self._inflight.get(bb) is f:
                                del self._inflight[bb]
                for f in claimed.values():
                    if not f.done():
                        f.set_exception(e)
                raise

        # ---- rendezvous with reads other threads own ---------------------
        reissue_runs = 0
        for b, fut in waits.items():
            try:
                local[b] = fut.result()  # raises the producer's failure
                pf_blocks.append(b)
            except BaseException:
                if self._retry is None:
                    raise  # no retry budget: the producer's failure is ours
                # the producer failed but retries remain: re-issue the block
                # idempotently instead of re-raising a failure this fetch
                # never attempted itself
                val, runs2, nb2, outcome = self._reissue_block(b)
                local[b] = val
                if outcome == "served":
                    hits += 1  # another recoverer delivered it to us
                else:
                    missing.append(b)  # a miss this fetch served itself
                    n_cut += 1
                    reissue_runs += runs2
                    bytes_read += nb2
                    if outcome == "bypassed":
                        adm_bypassed += 1
                    elif outcome == "rejected":
                        adm_rejected += 1
        if waits:
            with self._fl:
                for b in waits:
                    self._pf_marks.discard(b)

        # consume-once staging: under a bypassing admission policy the
        # prefetched blocks must not be RETAINED by the LRU — drop them now
        # that this fetch has them in hand.  Streaming keeps the straddled
        # last block exactly like the _cache_put path does, or the next
        # fetch would re-read it and readahead would *add* physical runs.
        if pf_blocks and (self.admission == "never" or streaming):
            for b in pf_blocks:
                if self.admission == "never" or b != last_block:
                    self.cache.discard(b)

        # ---- one gather from extents and held blocks, caller order -------
        with span("scdataset.assemble"):
            order = np.argsort(rows, kind="stable")
            merged = self.adapter.gather(
                self._sources(rows[order], local, spans, pieces)
            )
            inv = np.empty(len(rows), dtype=np.int64)
            inv[order] = np.arange(len(rows))
            if not np.array_equal(inv, np.arange(len(rows))):
                merged = self.adapter.take(merged, inv)

        # ---- cross-rank attribution (elastic fabric) ---------------------
        # Blocks this fetch obtained WITHOUT reading (cache hits + staged +
        # rendezvous waits) that a different tag produced are reads the
        # shared cache saved this rank.  Sync mode has no claim section, so
        # ownership of self-read blocks lands here instead.
        shared = 0
        if my_tag is not None or self._block_owner:  # unlocked-ok: emptiness fast path — untagged traffic skips the lock; a stale non-empty read only adds one locked no-op pass
            obtained = set(served) | set(pf_blocks)
            with self._fl:
                if not self.async_enabled:
                    for b in missing:
                        if my_tag is not None:
                            self._block_owner[b] = my_tag
                        else:
                            self._block_owner.pop(b, None)
                if my_tag is not None:
                    for b in obtained:
                        owner = self._block_owner.get(b)
                        if owner is not None and owner != my_tag:
                            shared += 1

        self.iostats.record(
            runs=len(spans) + reissue_runs,
            rows=len(rows),
            bytes_read=bytes_read,
            wall_s=time.perf_counter() - t0,
            cache_hits=hits,
            cache_misses=len(missing),
            prefetched=len(pf_blocks),
            adm_bypassed=adm_bypassed,
            adm_rejected=adm_rejected,
            shared_rank_hits=shared,
            blocks_cut=n_cut,
            slept=True,
        )
        return merged

    # ------------------------------------------------------- double buffer
    def prefetch(self, rows) -> int:
        """Issue the read plan of a FUTURE fetch in the background.

        Non-blocking.  Blocks already cached or in flight are skipped; the
        rest are registered in the rendezvous table and read by the shared
        executor (one task per contiguous block group, spans split exactly as
        a fetch would split them, so total physical runs never exceed the
        synchronous path).  The later ``fetch`` finds them in the cache or
        waits on their futures.  Returns the number of blocks scheduled.
        No-op unless ``readahead > 0`` or ``io_workers > 1``.
        """
        pool = self._pool()
        if pool is None:
            return 0
        rows = np.asarray(rows, dtype=np.int64)
        if rows.size == 0:
            return 0
        block_list = np.unique(rows // self.block_rows).tolist()
        if self._breaker is not None:
            # graceful degradation: BACKGROUND staging skips shards whose
            # breaker is open (speculative reads of a dark shard only feed
            # its failure count); demand fetches still probe them with a
            # capped budget, so delivery survives.  A block is keyed by its
            # first row's shard — one straddling a boundary follows the
            # shard it starts in.
            block_list = [
                b
                for b in block_list
                if not self._breaker.is_open(self._shard_of(b * self.block_rows))
            ]
        todo: list[int] = []
        futs: dict[int, Future] = {}
        my_tag = getattr(self._tag, "value", None)
        with self._fl:
            for b in block_list:
                if b in self._inflight or self.cache.peek(b) is not None:
                    continue
                f: Future = Future()
                self._inflight[b] = f
                futs[b] = f
                if my_tag is not None:
                    self._block_owner[b] = my_tag
                else:
                    self._block_owner.pop(b, None)
                todo.append(b)
        if not todo:
            return 0
        # one background task per contiguous block group: its spans coalesce
        # exactly as a fetch of those blocks would, groups read in parallel
        arr = np.asarray(todo)
        breaks = np.flatnonzero(np.diff(arr) != 1) + 1
        groups = np.split(arr, breaks)
        for gi, grp in enumerate(groups):
            gspans = self._spans_for_blocks(grp)
            gfuts = {int(b): futs[int(b)] for b in grp.tolist()}
            try:
                pool.submit(self._prefetch_group, gspans, gfuts)
            except BaseException as e:
                # executor shut down mid-issue (close() racing a drain):
                # deregister + fail every future not handed to a task, or a
                # later fetch would wait on them forever
                undone = [int(b) for g in groups[gi:] for b in g.tolist()]
                with self._fl:
                    for b in undone:
                        if self._inflight.get(b) is futs[b]:
                            del self._inflight[b]
                for b in undone:
                    if not futs[b].done():
                        futs[b].set_exception(e)
                return sum(len(g) for g in groups[:gi])
        return len(todo)

    def _prefetch_group(self, spans: np.ndarray, futs: dict[int, Future]) -> None:
        """Executor task: read one contiguous block group, publish its blocks
        (cache first, then future, then rendezvous deregistration — waiters
        observing no inflight entry are guaranteed a cache peek succeeds)."""
        try:
            results = [self._read_one(lo, hi) for lo, hi in spans]
            pieces = [p for p, _ in results]
            bytes_read = sum(nb for _, nb in results)
            vals = {bb: self._cut(bb, spans, pieces) for bb in futs}
            # stage through the cache as the hand-off channel, MARKED: the
            # consuming fetch counts the first touch as `prefetched` (not a
            # hit) and, under a bypassing admission policy, drops the entry
            # after use — so readahead neither inflates the hit rate nor
            # defeats admission="never"/stream-bypass retention semantics.
            # In the TinyLFU regime (admission="auto", not streaming) staged
            # blocks fight the SAME frequency duel as fetched ones — a
            # staged cold block must not evict the protected hot set; a
            # rejected block still hands over through its Future (a fetch
            # arriving later re-reads it, exactly as if it had been evicted).
            with self._fl:
                self._pf_marks.update(vals)
                streaming = self._stream.streaming
            duel = self._sketch is not None and not streaming
            adm_rejected = 0
            for bb, val in vals.items():
                nb = piece_nbytes(val)
                if duel and nb <= self.cache.max_bytes:
                    if not self.cache.put_admit(bb, val, nb,
                                                self._sketch.estimate):
                        adm_rejected += 1
                else:
                    self.cache.put(bb, val, nb)
                futs[bb].set_result(val)
            with self._fl:
                for bb, f in futs.items():
                    if self._inflight.get(bb) is f:
                        del self._inflight[bb]
            # background work: runs/bytes counted once, not a consumer call
            self.iostats.record(
                runs=len(spans),
                rows=0,
                bytes_read=bytes_read,
                wall_s=0.0,
                cache_misses=len(futs),
                adm_rejected=adm_rejected,
                calls=0,
                slept=True,
            )
        except BaseException as e:
            with self._fl:
                for bb, f in futs.items():
                    if self._inflight.get(bb) is f:
                        del self._inflight[bb]
            for f in futs.values():
                if not f.done():
                    f.set_exception(e)

    def stats(self) -> dict:
        out = {"io": self.iostats.snapshot(), "cache": self.cache.snapshot()}
        snap = out["io"]
        if snap.get("div_batches", 0) > 0:
            # diversity observatory (§3.4): derived view over the div_*
            # counters — mean/min batch entropy in bits, valid only while
            # batches have been observed (a DiversityMonitor is attached)
            out["diversity"] = {
                "batches": snap["div_batches"],
                "entropy_mean": snap["div_entropy_sum"] / snap["div_batches"],
                "entropy_min": snap["div_entropy_min"],
            }
        if self._ra_controller is not None:
            out["readahead"] = self._ra_controller.snapshot()
        if self._sketch is not None:
            out["admission"] = {
                "doorkeeper": len(self._sketch.door),
                "ops": self._sketch.ops,
                "ages": self._sketch.ages,
            }
        if (
            self._retry is not None
            or self._breaker is not None
            or self.hedge_factor > 0.0
        ):
            res: dict = {
                "wait_ewma_s": self._wait_ewma,
                "hedge_factor": self.hedge_factor,
                "hedge_min_s": self.hedge_min_s,
            }
            if self._retry is not None:
                res["retry"] = {
                    "retries": self._retry.retries,
                    "backoff_s": self._retry.backoff_s,
                    "max_backoff_s": self._retry.max_backoff_s,
                    "deadline_s": self._retry.deadline_s,
                }
            if self._breaker is not None:
                res["breaker"] = self._breaker.snapshot()
            out["resilience"] = res
        snap = getattr(self.adapter, "fault_snapshot", None)
        if snap is not None:
            out["faults"] = snap()
        return out


# ---------------------------------------------------------------- registry
_REGISTRY: dict[str, Callable[..., StorageAdapter]] = {}


def register_backend(scheme: str):
    """Register an adapter opener under a URI scheme (``scheme://path``)."""

    def deco(fn: Callable[..., StorageAdapter]):
        _REGISTRY[scheme] = fn
        return fn

    return deco


def registered_schemes() -> list[str]:
    return sorted(_REGISTRY)


@register_backend("csr")
def _open_csr(path: str) -> CSRAdapter:
    return CSRAdapter(CSRStore(path))


@register_backend("sharded-csr")
def _open_sharded_csr(path: str) -> ShardedCSRAdapter:
    if "," in path:
        shard_paths = path.split(",")
    else:
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        shard_paths = [os.path.join(path, s) for s in manifest["shards"]]
    return ShardedCSRAdapter(ShardedCSRStore(shard_paths))


@register_backend("chunked")
def _open_chunked(path: str) -> ChunkedAdapter:
    return ChunkedAdapter(ChunkedStore(path))


@register_backend("tokens")
def _open_tokens(path: str, *, seq_len=None) -> TokenAdapter:
    if seq_len is None:
        raise ValueError("tokens:// requires seq_len (e.g. tokens:///corpus?seq_len=128)")
    return TokenAdapter(TokenStore(path, seq_len=int(seq_len)))


def _sniff_scheme(path: str) -> str:
    """Detect the backend of a bare path from its on-disk layout.

    Files: anything named ``*.h5ad`` — or carrying the HDF5 signature —
    is an AnnData file.  Directories: layout markers as before.
    """
    if os.path.isfile(path):
        if path.endswith(".h5ad"):
            return "h5ad"
        with open(path, "rb") as f:
            if f.read(8) == b"\x89HDF\r\n\x1a\n":
                return "h5ad"
        raise ValueError(f"cannot detect a storage backend for file {path!r}")
    manifest_path = os.path.join(path, "manifest.json")
    if os.path.exists(manifest_path):
        with open(manifest_path) as f:
            manifest = json.load(f)
        shards = manifest.get("shards", [])
        if shards and all(str(s).endswith(".h5ad") for s in shards):
            return "sharded-h5ad"
        return "sharded-csr"
    meta_path = os.path.join(path, "meta.json")
    if os.path.exists(meta_path):
        with open(meta_path) as f:
            meta = json.load(f)
        if "chunk_rows" in meta:
            return "chunked"
        if "n_obs" in meta:
            return "csr"
        if os.path.exists(os.path.join(path, "tokens.npy")):
            return "tokens"
    raise ValueError(f"cannot detect a storage backend at {path!r}")


_UNSET = object()  # distinguishes "not passed" from meaningful None/0


def _parse_uri(uri: str, opts: dict) -> tuple[str, str, dict]:
    """``scheme://path[?k=v...]`` (or bare sniffed path) -> (scheme, path,
    merged opts).  Explicit ``opts`` win over query-string duplicates."""
    if "://" in uri:
        scheme, rest = uri.split("://", 1)
    else:
        scheme, rest = _sniff_scheme(uri), uri
    if "?" in rest:
        rest, query = rest.split("?", 1)
        opts = {**dict(urllib.parse.parse_qsl(query)), **opts}
    if scheme not in _REGISTRY:
        raise ValueError(
            f"unknown backend scheme {scheme!r}; known: {registered_schemes()}"
        )
    return scheme, rest, opts


def open_adapter(uri: str, **opts) -> StorageAdapter:
    """Resolve a URI to its RAW adapter — no planner, no cache, no stats.

    The building block for wrapping adapters (``cloud://`` opens its inner
    URI through this) and for tests that poke the adapter contract directly.
    Everything user-facing should use :func:`open_collection` instead.
    """
    scheme, rest, opts = _parse_uri(uri, opts)
    return _REGISTRY[scheme](rest, **opts)


def open_collection(
    uri: str,
    *,
    iostats: Optional[IOStats] = None,
    cache_bytes=_UNSET,
    block_rows=_UNSET,
    max_extent_rows=_UNSET,
    io_workers=_UNSET,
    readahead=_UNSET,
    admission=_UNSET,
    cache_policy=_UNSET,
    retries=_UNSET,
    retry_backoff_s=_UNSET,
    retry_max_backoff_s=_UNSET,
    retry_deadline_s=_UNSET,
    hedge_factor=_UNSET,
    hedge_min_s=_UNSET,
    breaker_threshold=_UNSET,
    breaker_cooldown_s=_UNSET,
    **opts,
) -> PlannedCollection:
    """Open any registered storage format behind the unified planned layer.

    ``uri`` is ``scheme://path[?key=value...]`` (query params become opener
    kwargs) or a bare directory path, in which case the layout is sniffed.
    Planner knobs: ``cache_bytes`` (LRU budget; 0 disables the cache),
    ``block_rows`` (cache granularity), ``max_extent_rows`` (largest single
    read; None = unbounded).  Async knobs (both off by default — the
    synchronous path is the reference): ``io_workers`` (>1 executes one
    fetch's miss extents concurrently on a shared bounded pool),
    ``readahead`` (>0 lets ``ScDataset`` issue that many upcoming fetches'
    read plans in the background — double buffering; ``"auto"`` hands the
    depth to a feedback controller that grows it while cache budget and
    in-flight headroom allow and shrinks it under eviction pressure),
    ``admission`` (``always`` | ``auto`` | ``never``; ``auto`` detects
    forward-streaming epochs and bypasses LRU insertion for them, and
    switches to TinyLFU frequency admission when the sampled working set
    exceeds ``cache_bytes``).  Resilience knobs (all off by default; see the
    :class:`PlannedCollection` docstring): ``retries`` + ``retry_backoff_s``
    / ``retry_max_backoff_s`` / ``retry_deadline_s`` (bounded retries with
    decorrelated-jitter backoff), ``hedge_factor`` / ``hedge_min_s`` (tail
    hedging of miss reads), ``breaker_threshold`` / ``breaker_cooldown_s``
    (per-shard circuit breaking).  The knobs may also ride in
    the query string (``?cache_bytes=0&io_workers=4&admission=auto``); an
    explicit keyword argument wins over the query.  Unknown query keys reach
    the opener, which rejects what it does not understand — nothing is
    silently dropped.
    """
    scheme, rest, opts = _parse_uri(uri, opts)

    def knob(kwarg, key: str, default, allow_none: bool = False, cast=int):
        if kwarg is not _UNSET:
            opts.pop(key, None)
            return kwarg
        raw = opts.pop(key, _UNSET)
        if raw is _UNSET:
            return default
        if allow_none and isinstance(raw, str) and raw.lower() == "none":
            return None
        return cast(raw)

    cache_bytes = knob(cache_bytes, "cache_bytes", DEFAULT_CACHE_BYTES)
    block_rows = knob(block_rows, "block_rows", DEFAULT_BLOCK_ROWS)
    max_extent_rows = knob(
        max_extent_rows, "max_extent_rows", DEFAULT_MAX_EXTENT_ROWS, allow_none=True
    )
    io_workers = knob(io_workers, "io_workers", 1)
    # one shared grammar for the adaptive spelling: int >= 0 or "auto"
    readahead = knob(readahead, "readahead", 0, cast=normalize_readahead)
    admission = knob(admission, "admission", "always", cast=str)
    cache_policy = knob(cache_policy, "cache_policy", "lru", cast=str)
    retries = knob(retries, "retries", 0)
    retry_backoff_s = knob(retry_backoff_s, "retry_backoff_s", 0.005, cast=float)
    retry_max_backoff_s = knob(
        retry_max_backoff_s, "retry_max_backoff_s", 0.25, cast=float
    )
    retry_deadline_s = knob(retry_deadline_s, "retry_deadline_s", 0.0, cast=float)
    hedge_factor = knob(hedge_factor, "hedge_factor", 0.0, cast=float)
    hedge_min_s = knob(hedge_min_s, "hedge_min_s", 0.05, cast=float)
    breaker_threshold = knob(breaker_threshold, "breaker_threshold", 0)
    breaker_cooldown_s = knob(
        breaker_cooldown_s, "breaker_cooldown_s", 1.0, cast=float
    )
    adapter = _REGISTRY[scheme](rest, **opts)
    return PlannedCollection(
        adapter,
        iostats=iostats,
        cache_bytes=int(cache_bytes),
        block_rows=int(block_rows),
        max_extent_rows=max_extent_rows,
        io_workers=int(io_workers),
        readahead=readahead,
        admission=str(admission),
        cache_policy=str(cache_policy),
        retries=int(retries),
        retry_backoff_s=float(retry_backoff_s),
        retry_max_backoff_s=float(retry_max_backoff_s),
        retry_deadline_s=float(retry_deadline_s),
        hedge_factor=float(hedge_factor),
        hedge_min_s=float(hedge_min_s),
        breaker_threshold=int(breaker_threshold),
        breaker_cooldown_s=float(breaker_cooldown_s),
    )
