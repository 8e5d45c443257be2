"""Cross-shard read planning + byte-budgeted block cache (the shared I/O layer).

Every storage backend behind :mod:`repro.data.backend` reduces a fetch to the
same two primitives: *which contiguous row extents to read* and *which of
those extents are already resident*.  This module owns both halves:

- :func:`coalesce_rows` / :func:`plan_reads` — merge sorted row indices into
  maximal contiguous runs in the **global** row space (so a run conceptually
  spans shard boundaries), then split the runs at physical shard boundaries
  (different files cannot be read in one call) and at a configurable
  ``max_extent_rows`` (bounds the largest single read, so one giant run
  cannot blow the fetch buffer or starve concurrent workers).
- :class:`BlockCache` — a thread-safe LRU over fixed-size row blocks with a
  byte budget.  Weighted / class-balanced sampling draws blocks *with
  replacement*, so consecutive fetches overlap; cached blocks turn those
  overlaps into memory hits instead of repeated disk runs.
- the **adaptive-I/O primitives** — :class:`FrequencySketch` (TinyLFU-style
  count-min + doorkeeper over block ids, backing frequency-based admission
  when the sampled working set exceeds the cache budget) and
  :class:`ReadaheadController` (feedback-driven double-buffer depth for
  ``readahead="auto"``).

Spans everywhere in this module are ``(n, 2)`` int64 arrays of ``[start,
stop)`` rows — one row per physical read.  The planner pipeline (coalesce ->
boundary split -> extent cap) is fully vectorized; a large weighted epoch
plans millions of rows without a per-run Python loop.

The planner is deliberately backend-agnostic: it works on integers only.
Backends supply their boundary offsets and execute the resulting
``(start, stop)`` reads; :class:`repro.data.backend.PlannedCollection` glues
the two together and threads one :class:`~repro.data.iostats.IOStats` through
so runs / bytes / cache hits are counted once, uniformly, for every format.
"""
from __future__ import annotations

import collections
import threading
from typing import Any, Optional, Sequence

import numpy as np

__all__ = [
    "coalesce_rows",
    "split_at_boundaries",
    "split_max_extent",
    "plan_reads",
    "block_ids_of",
    "blocks_to_row_spans",
    "normalize_readahead",
    "BlockCache",
    "SegmentedBlockCache",
    "StreamDetector",
    "FrequencySketch",
    "ReadaheadController",
]


def normalize_readahead(value):
    """Validate + normalize the one ``readahead`` spelling everywhere:
    a non-negative int (fixed depth) or the string ``"auto"`` (adaptive).
    Every layer that accepts the knob (``PlannedCollection``,
    ``open_collection`` kwargs/query, the Pipeline builder, ``DataSpec``)
    funnels through here, so the accepted grammar cannot drift apart."""
    if isinstance(value, str):
        if value == "auto":
            return "auto"
        if value.isdigit():  # query-string spelling of a fixed depth
            return int(value)
    elif not isinstance(value, bool):
        iv = int(value)
        if iv == value and iv >= 0:
            return iv
    raise ValueError(f'readahead must be an int >= 0 or "auto", got {value!r}')

_EMPTY_SPANS = np.empty((0, 2), dtype=np.int64)


def _as_spans(spans) -> np.ndarray:
    """Anything span-shaped (list of tuples / (n,2) array) -> (n,2) int64."""
    arr = np.asarray(spans, dtype=np.int64)
    return arr.reshape(-1, 2)


def coalesce_rows(sorted_unique: np.ndarray) -> np.ndarray:
    """Maximal ``[start, stop)`` runs of an ascending, duplicate-free array,
    as an ``(n, 2)`` int64 span array (no per-run Python objects)."""
    a = np.asarray(sorted_unique, dtype=np.int64)
    if len(a) == 0:
        return _EMPTY_SPANS
    breaks = np.flatnonzero(np.diff(a) != 1)
    firsts = np.concatenate(([0], breaks + 1))
    lasts = np.concatenate((breaks, [len(a) - 1]))
    return np.stack((a[firsts], a[lasts] + 1), axis=1)


def split_at_boundaries(
    spans, boundaries: Optional[np.ndarray]
) -> np.ndarray:
    """Split row spans at physical shard boundaries.

    ``boundaries`` is the ascending offset array ``[0, n_0, n_0+n_1, ..., n]``
    (:class:`~repro.data.csr_store.ShardedCSRStore.offsets` shape).  A span
    crossing an interior boundary becomes one span per shard touched.
    Vectorized: every span's interior cuts are located with two searchsorted
    passes and scattered into the output in one shot.
    """
    spans = _as_spans(spans)
    if boundaries is None or len(boundaries) <= 2 or len(spans) == 0:
        return spans
    interior = np.asarray(boundaries, dtype=np.int64)[1:-1]
    lo, hi = spans[:, 0], spans[:, 1]
    i0 = np.searchsorted(interior, lo, side="right")  # first cut > lo
    i1 = np.searchsorted(interior, hi, side="left")  # first cut >= hi
    counts = i1 - i0  # interior cuts strictly inside each span
    total_cuts = int(counts.sum())
    if total_cuts == 0:
        return spans
    reps = counts + 1  # pieces per span
    starts = np.repeat(lo, reps)
    stops = np.repeat(hi, reps)
    # grouped-arange: for span s, its cut values interior[i0[s]:i1[s]]
    cs = np.cumsum(counts)
    local = np.arange(total_cuts) - np.repeat(cs - counts, counts)
    cut_vals = interior[np.repeat(i0, counts) + local]
    # piece j>0 of span s starts at cut j-1; piece j-1 stops there
    ends = np.cumsum(reps)
    first_pos = ends - reps
    pos = np.repeat(first_pos, counts) + 1 + local
    starts[pos] = cut_vals
    stops[pos - 1] = cut_vals
    return np.stack((starts, stops), axis=1)


def split_max_extent(spans, max_extent_rows: Optional[int]) -> np.ndarray:
    """Cap every span at ``max_extent_rows`` rows (None/<=0 = unbounded)."""
    spans = _as_spans(spans)
    if not max_extent_rows or max_extent_rows <= 0 or len(spans) == 0:
        return spans
    M = int(max_extent_rows)
    lo, hi = spans[:, 0], spans[:, 1]
    pieces = (hi - lo + M - 1) // M
    total = int(pieces.sum())
    if total == len(spans):
        return spans
    cs = np.cumsum(pieces)
    local = np.arange(total) - np.repeat(cs - pieces, pieces)
    starts = np.repeat(lo, pieces) + local * M
    stops = np.minimum(starts + M, np.repeat(hi, pieces))
    return np.stack((starts, stops), axis=1)


def plan_reads(
    rows: np.ndarray,
    *,
    boundaries: Optional[np.ndarray] = None,
    max_extent_rows: Optional[int] = None,
) -> np.ndarray:
    """Sorted-unique ``rows`` -> the physical read plan, an ``(n, 2)`` int64
    array of ``[start, stop)`` spans in ascending order.

    Coalesce first (global row space, across shard boundaries), then split at
    boundaries, then cap extents — each returned span is one backend read
    touching exactly one shard.
    """
    runs = coalesce_rows(np.unique(np.asarray(rows, dtype=np.int64)))
    runs = split_at_boundaries(runs, boundaries)
    return split_max_extent(runs, max_extent_rows)


def block_ids_of(rows: np.ndarray, block_rows: int) -> np.ndarray:
    """Cache-block id of each row (blocks are global-row aligned)."""
    return np.asarray(rows, dtype=np.int64) // int(block_rows)


def blocks_to_row_spans(
    block_ids: np.ndarray, block_rows: int, n: int
) -> np.ndarray:
    """Sorted-unique block ids -> coalesced row spans, clipped to ``n``."""
    spans = coalesce_rows(np.unique(np.asarray(block_ids, dtype=np.int64)))
    spans = spans * int(block_rows)
    np.minimum(spans[:, 1], n, out=spans[:, 1])
    return spans


class BlockCache:
    """Byte-budgeted, thread-safe LRU over opaque cached values.

    Keys are cache-block ids; values are whatever batch object the backend
    produces for that block's rows (CSRBatch, ndarray, dict of arrays).  The
    budget is enforced on insertion: least-recently-used blocks are evicted
    until the new value fits.  A value larger than the whole budget is simply
    not cached (it would evict everything for a block that cannot be reused
    before it is evicted itself).

    ``max_bytes == 0`` disables caching entirely — `get` always misses and
    `put` is a no-op — so callers need no special-casing for the uncached
    configuration.
    """

    def __init__(self, max_bytes: int):
        self.max_bytes = int(max_bytes)
        self._entries: collections.OrderedDict[Any, tuple[Any, int]] = (
            collections.OrderedDict()
        )  # guarded-by: _lock
        self._lock = threading.Lock()
        self.cur_bytes = 0  # guarded-by: _lock
        self.hits = 0  # guarded-by: _lock
        self.misses = 0  # guarded-by: _lock
        self.evictions = 0  # guarded-by: _lock
        self.insertions = 0  # guarded-by: _lock
        self.bypasses = 0  # guarded-by: _lock — admission-policy skips
        self.rejections = 0  # guarded-by: _lock — lost TinyLFU victim duels

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def get(self, key) -> Optional[Any]:
        with self._lock:
            ent = self._entries.get(key)
            if ent is None:
                self.misses += 1
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            return ent[0]

    def peek(self, key) -> Optional[Any]:
        """Like ``get`` but without touching the hit/miss counters — for
        rendezvous re-checks that must not distort the accounting (the caller
        counts the outcome itself)."""
        with self._lock:
            ent = self._entries.get(key)
            if ent is None:
                return None
            self._entries.move_to_end(key)
            return ent[0]

    def bypass(self, n: int = 1) -> None:
        """Record that an admission policy skipped ``n`` insertions."""
        with self._lock:
            self.bypasses += n

    def discard(self, key) -> None:
        """Drop an entry if present (no counters) — consume-once semantics
        for prefetch staging under a bypassing admission policy."""
        with self._lock:
            ent = self._entries.pop(key, None)
            if ent is not None:
                self.cur_bytes -= ent[1]

    def put(self, key, value, nbytes: int) -> None:
        self.put_many([(key, value, nbytes)])

    def survivors(self, sizes: Sequence[int]) -> list[bool]:
        """Which of values of these byte sizes, handed to :meth:`put_many`
        in this order, the cache still holds when it returns.

        Under LRU the newest values evict the older ones, so the survivors
        are the longest suffix of the sequence whose bytes fit
        ``max_bytes`` together, leaving out values too large to cache at
        all (they are never inserted).  Entries already resident do not
        change the answer: they are evicted before any of the new values.
        A caller learns this way which values it need not build.
        """
        keep = [False] * len(sizes)
        if self.max_bytes <= 0:
            return keep
        room = self.max_bytes
        for i in range(len(sizes) - 1, -1, -1):
            nbytes = int(sizes[i])
            if nbytes > self.max_bytes:
                continue
            if nbytes > room:
                break
            room -= nbytes
            keep[i] = True
        return keep

    def put_many(self, items: Sequence[tuple[Any, Any, int]]) -> None:
        """:meth:`put` each ``(key, value, nbytes)`` in order, under one hold
        of the lock, so no other thread's insertion falls in between.

        A value :meth:`survivors` says the cache will not hold afterwards
        may be None: it is inserted and evicted within the call, and
        counted so, exactly as a real one would be, but no reader can see
        it.  A None the cache would keep raises ``ValueError``.
        """
        items = [(key, value, int(nbytes)) for key, value, nbytes in items]
        keep = self.survivors([nbytes for _, _, nbytes in items])
        if any(value is None and kept for (_, value, _), kept in zip(items, keep)):
            raise ValueError("put_many: a value the cache keeps is None")
        if self.max_bytes <= 0:
            return
        with self._lock:
            for key, value, nbytes in items:
                if nbytes > self.max_bytes:
                    continue
                if key in self._entries:
                    _, old = self._entries.pop(key)
                    self.cur_bytes -= old
                while self._entries and self.cur_bytes + nbytes > self.max_bytes:
                    _, (_, old) = self._entries.popitem(last=False)
                    self.cur_bytes -= old
                    self.evictions += 1
                self._entries[key] = (value, nbytes)
                self.cur_bytes += nbytes
                self.insertions += 1

    def put_admit(self, key, value, nbytes: int, estimate) -> bool:
        """TinyLFU-guarded insertion: evict only victims *colder* than the
        candidate.

        While the value fits without eviction this is plain LRU insertion —
        frequency admission only takes over once the working set exceeds
        ``max_bytes`` (an eviction is needed).  Then the LRU-front victim's
        estimated access frequency (``estimate(key) -> int``, a
        :class:`FrequencySketch`) is compared against the candidate's: a
        candidate that is not strictly hotter is REJECTED (returns False,
        counted in ``rejections``) and the resident set keeps its hot blocks
        across weighted redraws instead of churning.  Re-inserting a resident
        key refreshes it unconditionally (that path frees its own bytes).
        """
        nbytes = int(nbytes)
        if self.max_bytes <= 0 or nbytes > self.max_bytes:
            return False
        with self._lock:
            resident = key in self._entries
            if resident:
                _, old = self._entries.pop(key)
                self.cur_bytes -= old
            # Decide the FULL victim set before evicting anyone: a candidate
            # that needs several victims' bytes must beat every one of them,
            # or the rejection would still have shed resident blocks as a
            # side effect.  A refresh of a resident key skips the duel — the
            # block already won residency and only its bytes changed.
            victims: list = []
            freed = 0
            cand_freq = None
            rejected = False
            for vkey in self._entries:  # LRU -> MRU order
                if self.cur_bytes - freed + nbytes <= self.max_bytes:
                    break
                if not resident:
                    if cand_freq is None:
                        cand_freq = int(estimate(key))
                    if int(estimate(vkey)) >= cand_freq:
                        rejected = True
                        break
                victims.append(vkey)
                freed += self._entries[vkey][1]
            if rejected:
                self.rejections += 1
                return False
            for vkey in victims:
                _, old = self._entries.pop(vkey)
                self.cur_bytes -= old
                self.evictions += 1
            self._entries[key] = (value, nbytes)
            self.cur_bytes += nbytes
            self.insertions += 1
            return True

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.cur_bytes = 0

    @property
    def hit_rate(self) -> float:
        # locked so the hits/misses pair comes from one consistent state
        with self._lock:
            total = self.hits + self.misses
            return self.hits / total if total else 0.0

    def snapshot(self) -> dict:
        # one consistent cut — e.g. cur_bytes must agree with _entries, or
        # a snapshot taken mid-eviction shows a budget overshoot that never
        # happened.  hit_rate is inlined: the property takes the same
        # non-reentrant lock and calling it here would self-deadlock.
        with self._lock:
            total = self.hits + self.misses
            return {
                "entries": len(self._entries),
                "cur_bytes": self.cur_bytes,
                "max_bytes": self.max_bytes,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "insertions": self.insertions,
                "bypasses": self.bypasses,
                "rejections": self.rejections,
                "hit_rate": self.hits / total if total else 0.0,
            }


class SegmentedBlockCache(BlockCache):
    """W-TinyLFU segmented cache: window LRU + main SLRU (probation/protected).

    Drop-in for :class:`BlockCache` (every method and counter overridden —
    the base ``__init__`` is deliberately not called, this class keeps its
    own segment bookkeeping) behind the
    ``cache_policy="wtinylfu"`` knob.  The budget is split into a small
    *window* LRU (``window_frac`` of ``max_bytes``) where every new block
    lands first, and a *main* segmented LRU whose *protected* sub-segment
    (``protected_frac`` of main) holds blocks that were hit again after
    admission.  A block evicted from the window duels the main segment's
    coldest victim on sketch frequency (``estimate``) exactly like
    :meth:`BlockCache.put_admit` — but crucially the victim is drawn from
    *probation* first, so a scan-heavy tenant's one-touch blocks can only
    churn the window and the probation tail; another tenant's hot redraw
    set, promoted into protected by its re-hits, is insulated.  The plain
    single-segment duel loses this case when overlapping scans touch blocks
    often enough to out-estimate an *aged* hot set; see
    ``tests/test_serve_data.py``.

    Segment walk on lookup: window → protected → probation; a probation hit
    promotes to protected, demoting protected's LRU back to probation MRU
    when it overflows.  ``put`` (the duel-free API used by bypassing
    admission policies and prefetch staging) admits window victims into
    probation unconditionally.  ``max_bytes == 0`` disables caching, like
    the plain cache.
    """

    def __init__(self, max_bytes: int, *, window_frac: float = 0.10,
                 protected_frac: float = 0.80):
        # no super().__init__(): the single-segment _entries dict would sit
        # unused next to the three segment dicts and invite confusion
        if not (0.0 < window_frac < 1.0) or not (0.0 < protected_frac < 1.0):
            raise ValueError("window_frac and protected_frac must be in (0, 1)")
        self.max_bytes = int(max_bytes)
        self.window_bytes = int(self.max_bytes * window_frac)
        main = self.max_bytes - self.window_bytes
        self.protected_bytes = int(main * protected_frac)
        # key -> (value, nbytes); three disjoint key spaces
        self._window: collections.OrderedDict[Any, tuple[Any, int]] = (
            collections.OrderedDict()
        )  # guarded-by: _lock
        self._probation: collections.OrderedDict[Any, tuple[Any, int]] = (
            collections.OrderedDict()
        )  # guarded-by: _lock
        self._protected: collections.OrderedDict[Any, tuple[Any, int]] = (
            collections.OrderedDict()
        )  # guarded-by: _lock
        # RLock: the private segment-maintenance helpers take it themselves,
        # so they are safe from any entry point yet reentrant from the
        # public methods that already hold it
        self._lock = threading.RLock()
        self.cur_bytes = 0  # guarded-by: _lock
        self._window_cur = 0  # guarded-by: _lock
        self._protected_cur = 0  # guarded-by: _lock
        self.hits = 0  # guarded-by: _lock
        self.misses = 0  # guarded-by: _lock
        self.evictions = 0  # guarded-by: _lock
        self.insertions = 0  # guarded-by: _lock
        self.bypasses = 0  # guarded-by: _lock — admission-policy skips
        self.rejections = 0  # guarded-by: _lock — window victims losing duels

    def __len__(self) -> int:
        with self._lock:
            return len(self._window) + len(self._probation) + len(self._protected)

    def _touch(self, key) -> Optional[Any]:
        # Lookup + recency/segment maintenance, no counters.  Reentrant:
        # public callers already hold _lock.
        with self._lock:
            ent = self._window.get(key)
            if ent is not None:
                self._window.move_to_end(key)
                return ent[0]
            ent = self._protected.get(key)
            if ent is not None:
                self._protected.move_to_end(key)
                return ent[0]
            ent = self._probation.get(key)
            if ent is not None:
                # reuse after admission: promote, demoting protected's LRU
                # back to probation MRU while the protected budget overflows
                # (byte totals are unchanged — entries move between segments)
                del self._probation[key]
                self._protected[key] = ent
                self._protected_cur += ent[1]
                while (self._protected_cur > self.protected_bytes
                       and len(self._protected) > 1):
                    dkey, dent = self._protected.popitem(last=False)
                    self._protected_cur -= dent[1]
                    self._probation[dkey] = dent
                return ent[0]
            return None

    def get(self, key) -> Optional[Any]:
        with self._lock:
            val = self._touch(key)
            if val is None:
                self.misses += 1
            else:
                self.hits += 1
            return val

    def peek(self, key) -> Optional[Any]:
        """Like ``get`` but without touching the hit/miss counters — for
        rendezvous re-checks that must not distort the accounting."""
        with self._lock:
            return self._touch(key)

    def bypass(self, n: int = 1) -> None:
        """Record that an admission policy skipped ``n`` insertions."""
        with self._lock:
            self.bypasses += n

    def discard(self, key) -> None:
        """Drop an entry if present (no counters) — consume-once semantics
        for prefetch staging under a bypassing admission policy."""
        with self._lock:
            for seg, attr in ((self._window, "_window_cur"),
                              (self._probation, None),
                              (self._protected, "_protected_cur")):
                ent = seg.pop(key, None)
                if ent is not None:
                    self.cur_bytes -= ent[1]
                    if attr is not None:
                        setattr(self, attr, getattr(self, attr) - ent[1])
                    return

    def _remove(self, key) -> None:
        # Drop a resident key from whichever segment holds it.  Reentrant.
        with self._lock:
            for seg, attr in ((self._window, "_window_cur"),
                              (self._probation, None),
                              (self._protected, "_protected_cur")):
                ent = seg.pop(key, None)
                if ent is not None:
                    self.cur_bytes -= ent[1]
                    if attr is not None:
                        setattr(self, attr, getattr(self, attr) - ent[1])
                    return

    def _main_victim(self) -> Optional[Any]:
        # The main segment's coldest entry: probation LRU first — protected
        # only becomes evictable once probation is empty.  Reentrant.
        with self._lock:
            if self._probation:
                return next(iter(self._probation))
            if self._protected:
                return next(iter(self._protected))
            return None

    def _evict_main(self) -> None:
        # Evict the main segment's coldest entry.  Reentrant.
        with self._lock:
            if self._probation:
                _, (_, nb) = self._probation.popitem(last=False)
            else:
                _, (_, nb) = self._protected.popitem(last=False)
                self._protected_cur -= nb
            self.cur_bytes -= nb
            self.evictions += 1

    def _insert(self, key, value, nbytes: int, estimate) -> bool:
        # Shared body of put/put_admit: land in the window, then drain
        # window victims through main admission.  ``estimate`` None =
        # duel-free (plain `put` semantics: always admit).  Returns whether
        # ``key`` itself is resident afterwards.  Reentrant.
        with self._lock:
            self._remove(key)  # re-insert refreshes bytes wherever it lived
            self._window[key] = (value, nbytes)
            self._window_cur += nbytes
            self.cur_bytes += nbytes
            self.insertions += 1
            main_budget = self.max_bytes - self.window_bytes
            resident = True
            while self._window_cur > self.window_bytes and self._window:
                vkey, vent = self._window.popitem(last=False)
                self._window_cur -= vent[1]
                # main admission for the window victim (possibly `key`
                # itself when it alone exceeds the window budget).  The
                # victim's bytes stay counted in cur_bytes while it is in
                # limbo; main usage including the limbo victim is
                # cur_bytes - window_cur.
                admitted = True
                while self.cur_bytes - self._window_cur > main_budget:
                    mvic = self._main_victim()
                    if mvic is None:
                        # victim alone exceeds the main budget: nothing
                        # left to evict for it, drop it (pressure shows as
                        # an eviction)
                        admitted = False
                        self.evictions += 1
                        break
                    if estimate is not None and int(estimate(vkey)) <= int(
                        estimate(mvic)
                    ):
                        # not strictly hotter than main's coldest: the
                        # window victim loses the duel and leaves the cache
                        admitted = False
                        self.rejections += 1
                        break
                    self._evict_main()
                if admitted:
                    self._probation[vkey] = vent
                else:
                    self.cur_bytes -= vent[1]
                    if vkey == key:
                        resident = False
            return resident

    def put(self, key, value, nbytes: int) -> None:
        nbytes = int(nbytes)
        if self.max_bytes <= 0 or nbytes > self.max_bytes:
            return
        with self._lock:
            self._insert(key, value, nbytes, None)

    def survivors(self, sizes: Sequence[int]) -> list[bool]:
        """Every value the cache could take at all: which of them survive
        the window's admission duels is not known without running them."""
        return [0 < self.max_bytes and int(nbytes) <= self.max_bytes
                for nbytes in sizes]

    def put_many(self, items: Sequence[tuple[Any, Any, int]]) -> None:
        keep = self.survivors([nbytes for _, _, nbytes in items])
        for (key, value, nbytes), kept in zip(items, keep):
            if value is None:
                if kept:
                    raise ValueError("put_many: a value the cache may keep is None")
                continue  # too large to cache: put() would refuse it
            self.put(key, value, nbytes)

    def put_admit(self, key, value, nbytes: int, estimate) -> bool:
        """Frequency-guarded insertion; see the class docstring.  Returns
        whether ``key`` is resident after the operation (a window victim
        losing its duel is the usual False path, counted in
        ``rejections``)."""
        nbytes = int(nbytes)
        if self.max_bytes <= 0 or nbytes > self.max_bytes:
            return False
        with self._lock:
            return self._insert(key, value, nbytes, estimate)

    def clear(self) -> None:
        with self._lock:
            self._window.clear()
            self._probation.clear()
            self._protected.clear()
            self.cur_bytes = self._window_cur = self._protected_cur = 0

    @property
    def hit_rate(self) -> float:
        # locked so the hits/misses pair comes from one consistent state
        with self._lock:
            total = self.hits + self.misses
            return self.hits / total if total else 0.0

    def snapshot(self) -> dict:
        # one consistent cut, superset of BlockCache.snapshot (segment sizes
        # added) so dashboards/tests can treat the two interchangeably
        with self._lock:
            total = self.hits + self.misses
            return {
                "entries": len(self._window) + len(self._probation)
                + len(self._protected),
                "cur_bytes": self.cur_bytes,
                "max_bytes": self.max_bytes,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "insertions": self.insertions,
                "bypasses": self.bypasses,
                "rejections": self.rejections,
                "hit_rate": self.hits / total if total else 0.0,
                "window_entries": len(self._window),
                "probation_entries": len(self._probation),
                "protected_entries": len(self._protected),
                "window_bytes": self._window_cur,
                "protected_bytes": self._protected_cur,
            }


class StreamDetector:
    """Detects forward-streaming access over cache blocks.

    A pure-stream epoch (``Streaming`` strategy) touches every block exactly
    once in ascending order; inserting those blocks into an LRU buys zero
    future hits while evicting blocks that redraw-heavy samplers would have
    reused.  Feed each fetch's sorted-unique block ids to :meth:`observe`;
    after ``threshold`` consecutive fetches that are contiguous within the
    fetch AND advance monotonically past the previous fetch, ``streaming``
    turns on (and off again the moment the pattern breaks — one random fetch
    resets the streak).

    Call :meth:`reset` on epoch boundaries (``ScDataset`` signals them via
    ``PlannedCollection.epoch_boundary``): the streak and high-water mark of
    one epoch say nothing about the next — a weighted epoch's stale
    ``_last_hi`` could otherwise make a scattered first fetch that happens to
    sit above it look like a continuing stream (or keep a genuine stream
    undetected for ``threshold`` extra fetches).

    Not internally synchronized: the caller serializes ``observe`` (the
    planned collection holds its rendezvous lock).  Out-of-order observers
    (concurrent PrefetchPool workers completing fetches in any order) break
    the forward check and keep the streak at zero — detection degrades to
    OFF, i.e. plain LRU admission, never to a wrong bypass.
    """

    def __init__(self, threshold: int = 3):
        self.threshold = int(threshold)
        self.streak = 0  # guarded-by: external — caller serializes observe()
        self._last_hi: Optional[int] = None  # guarded-by: external

    def observe(self, block_ids: np.ndarray) -> bool:
        """Update with one fetch's sorted-unique block ids; returns the new
        streaming state (which classifies this same fetch)."""
        blocks = np.asarray(block_ids)
        contiguous = int(blocks[-1]) - int(blocks[0]) + 1 == len(blocks)
        forward = self._last_hi is not None and int(blocks[0]) >= self._last_hi
        self._last_hi = int(blocks[-1])
        self.streak = self.streak + 1 if (contiguous and forward) else 0
        return self.streaming

    @property
    def streaming(self) -> bool:
        return self.streak >= self.threshold

    def reset(self) -> None:
        self.streak = 0
        self._last_hi = None


class FrequencySketch:
    """TinyLFU-style block-popularity estimator: doorkeeper + count-min.

    Weighted / class-balanced sampling redraws blocks with replacement from a
    skewed distribution; when the drawn working set exceeds the cache budget,
    pure LRU churns hot blocks out to admit cold ones.  This sketch supplies
    the frequency signal for :meth:`BlockCache.put_admit`: a **doorkeeper**
    set absorbs the long tail of once-seen blocks (they never pollute the
    counters), and repeat visitors land in a ``depth x width`` count-min
    table (conservative update, saturating uint8 counters).  Every
    ``reset_interval`` touches all counters HALVE and the doorkeeper clears —
    the classic TinyLFU aging that keeps estimates tracking the *recent*
    distribution instead of all history.

    Deterministic: hashing is fixed odd-multiplier mixing of the integer
    block id, no process randomness.  Not internally locked — the planned
    collection touches it under its own serialization (estimates read racily
    from the cache's eviction path, which is safe: a stale counter can only
    mis-rank one duel, never corrupt state).
    """

    _MULTS = (0x9E3779B97F4A7C15, 0xC2B2AE3D27D4EB4F, 0x165667B19E3779F9,
              0x27D4EB2F165667C5)
    _MASK64 = (1 << 64) - 1

    def __init__(self, width: int = 4096, depth: int = 4,
                 reset_interval: Optional[int] = None):
        if width <= 0 or (width & (width - 1)) != 0:
            raise ValueError("width must be a positive power of two")
        self.width = int(width)
        self.depth = int(depth)
        # PlannedCollection touches the sketch from one planner thread at a
        # time; saturating uint8 increments tolerate the (benign)
        # lost-update race documented in the class docstring
        self.table = np.zeros((self.depth, self.width), dtype=np.uint8)  # guarded-by: external
        self.door: set[int] = set()  # guarded-by: external
        self.ops = 0  # guarded-by: external
        self.reset_interval = int(reset_interval or width * 8)
        self.ages = 0  # guarded-by: external

    def _slots(self, key: int) -> list[int]:
        k = (int(key) + 1) & self._MASK64  # avoid key 0's all-zero fixed point
        return [(((k * m) & self._MASK64) >> 17) & (self.width - 1)
                for m in self._MULTS[: self.depth]]

    def touch(self, key: int) -> None:
        """Record one access of ``key`` (call once per block per fetch)."""
        self.ops += 1
        if key not in self.door:
            self.door.add(key)
        else:
            slots = self._slots(key)
            vals = [int(self.table[i, s]) for i, s in enumerate(slots)]
            lo = min(vals)
            if lo < 255:  # conservative update: bump only the minimum rows
                for i, s in enumerate(slots):
                    if int(self.table[i, s]) == lo:
                        self.table[i, s] = lo + 1
        if self.ops >= self.reset_interval:
            self._age()

    def touch_many(self, keys: np.ndarray) -> None:
        """Vectorized :meth:`touch` of one fetch's (distinct) block ids.

        Equivalent to scalar touches (same hash lanes — uint64 wraparound is
        explicit in ``_slots`` so both paths agree), but the count-min
        update is one gather/compare/scatter instead of a Python loop per
        block, cheap enough to run OUTSIDE the planner's rendezvous lock.
        Concurrent callers may lose an occasional increment to a racing
        scatter — an accepted approximation for a frequency sketch.
        """
        keys = np.asarray(keys, dtype=np.int64)
        if keys.size == 0:
            return
        self.ops += int(keys.size)
        door = self.door
        known = np.fromiter((int(k) in door for k in keys), bool, keys.size)
        door.update(int(k) for k in keys[~known])
        rep = keys[known]
        if rep.size:
            k64 = rep.astype(np.uint64) + np.uint64(1)
            slots = np.empty((self.depth, rep.size), dtype=np.intp)
            for i, m in enumerate(self._MULTS[: self.depth]):
                slots[i] = (
                    ((k64 * np.uint64(m)) >> np.uint64(17))
                    & np.uint64(self.width - 1)
                ).astype(np.intp)
            rows = np.broadcast_to(
                np.arange(self.depth)[:, None], slots.shape
            )
            vals = self.table[rows, slots]
            lo = vals.min(axis=0)
            bump = (vals == lo[None, :]) & (lo[None, :] < 255)
            self.table[rows[bump], slots[bump]] = vals[bump] + 1
        if self.ops >= self.reset_interval:
            self._age()

    def estimate(self, key: int) -> int:
        """Estimated access count of ``key`` (doorkeeper adds its one visit)."""
        est = min(int(self.table[i, s]) for i, s in enumerate(self._slots(key)))
        return est + (1 if key in self.door else 0)

    def _age(self) -> None:
        self.table >>= 1
        self.door.clear()
        self.ops //= 2
        self.ages += 1


class ReadaheadController:
    """Feedback-driven double-buffer depth — the ``readahead="auto"`` brain.

    The right readahead depth K depends on signals only visible at run time:
    how many bytes one fetch stages, how much cache headroom is left for
    staging, and whether staged blocks survive until consumption.  This
    controller closes that loop from the counters the planner already keeps:

    - **grow** (+1, up to ``max_depth``) while the cache could hold roughly
      ``K + 2`` fetches' worth of blocks (the current fetch, the staged
      window, and slack for straddling) AND the in-flight table is draining
      (background reads are being consumed, not piling up);
    - **shrink** (-1, down to ``min_depth``, default 0 = no staging at all)
      under admission pressure — the cache evicted entries during the last
      window (deeper staging would evict blocks, possibly the staged ones,
      before they are used) OR frequency admission rejected insertions (the
      working set exceeds the budget and staged blocks cannot be retained —
      the hot redraw set the TinyLFU duel protects matters more than
      staging, and unretained staging is wasted double reads).

    The caller may additionally feed a **per-request wait EWMA** (the
    planner's observed seconds-per-physical-read — the same EWMA that drives
    the hedged-read deadline).  It adapts depth to the storage *tier*: when
    waits collapse below ``wait_floor_s`` (page-cached local reads — e.g. a
    mid-epoch migration off the cloud tier) staging buys nothing, so depth
    steps down each window toward ``min_depth`` — but only after a genuine
    downward SHIFT (waits that were always under the floor never saw
    latency to hide, and keep the legacy budget logic); when the EWMA rises by
    ``wait_shift_factor``x over the last decision's mark (a latency regime
    shift upward), depth steps up immediately (budget permitting) — deeper
    staging is exactly what hides slower storage.  ``wait_s=0`` (the
    default) reports nothing and leaves the legacy pressure/budget logic
    untouched.

    Depth starts at ``max(1, min_depth)`` — optimistic one-fetch double
    buffering, withdrawn within one decision window if the cache cannot
    afford it.

    Decisions fire every ``interval`` observed fetches; between decisions the
    depth is stable so ``ScDataset`` sees a consistent window.  Adaptation
    changes only WHEN bytes are read (how far ahead plans are issued) —
    delivered batches are bit-identical to any fixed depth, by the same
    rendezvous argument as fixed readahead.

    Not internally locked: :class:`PlannedCollection` calls :meth:`observe`
    under its rendezvous lock, and readers of :attr:`depth` tolerate a stale
    value (it only schedules background work).
    """

    def __init__(
        self,
        cache: BlockCache,
        *,
        min_depth: int = 0,
        max_depth: int = 8,
        interval: int = 4,
        wait_floor_s: float = 0.002,
        wait_shift_factor: float = 2.0,
    ):
        if min_depth < 0 or max_depth < max(1, min_depth):
            raise ValueError("need 0 <= min_depth <= max_depth, max_depth >= 1")
        self.cache = cache
        self.min_depth = int(min_depth)
        self.max_depth = int(max_depth)
        self.interval = int(interval)
        self.wait_floor_s = float(wait_floor_s)
        self.wait_shift_factor = float(wait_shift_factor)
        # observe() runs under the collection's rendezvous lock; depth
        # readers tolerate staleness (see class docstring)
        self.depth = max(1, self.min_depth)  # guarded-by: external
        self.grows = 0  # guarded-by: external
        self.shrinks = 0  # guarded-by: external
        self._fetches = 0  # guarded-by: external
        self._ev_mark = cache.evictions + cache.rejections  # guarded-by: external
        self._fetch_bytes = 0.0  # guarded-by: external — EWMA bytes/fetch
        self._fetch_blocks = 0.0  # guarded-by: external — EWMA blocks/fetch
        self._wait_ewma = 0.0  # guarded-by: external — EWMA s/physical read
        self._wait_mark = 0.0  # guarded-by: external — EWMA at last decision
        # latched by a genuine downward shift (wait fell from >= floor to
        # under it); storage that was ALWAYS fast never sets it, so local
        # stores keep the legacy budget/draining behavior
        self._fast_regime = False  # guarded-by: external
        self.latency_grows = 0  # guarded-by: external
        self.latency_shrinks = 0  # guarded-by: external

    def observe(
        self,
        fetch_bytes: float,
        fetch_blocks: int,
        inflight_blocks: int,
        wait_s: float = 0.0,
    ) -> int:
        """Feed one fetch's estimated staged bytes / touched-block count, the
        current in-flight table size and (optionally) the caller's
        per-physical-read wait EWMA; returns the (possibly adjusted)
        depth."""

        def ewma(prev: float, x: float) -> float:
            return x if prev == 0.0 else 0.75 * prev + 0.25 * x

        self._fetch_bytes = ewma(self._fetch_bytes, float(fetch_bytes))
        self._fetch_blocks = ewma(self._fetch_blocks, float(fetch_blocks))
        if wait_s > 0.0:
            self._wait_ewma = float(wait_s)  # caller already smooths it
        self._fetches += 1
        if self._fetches % self.interval:
            return self.depth
        pressure = self.cache.evictions + self.cache.rejections
        evicted = pressure - self._ev_mark
        self._ev_mark = pressure
        wait, mark = self._wait_ewma, self._wait_mark
        self._wait_mark = wait
        if evicted > 0:
            if self.depth > self.min_depth:
                self.depth -= 1
                self.shrinks += 1
            return self.depth
        if 0.0 < wait < self.wait_floor_s:
            # storage went fast: staging hides no latency.  But only a
            # genuine regime shift DOWN (waits FELL from >= floor) engages
            # the drain — storage that was always this fast (local mmap,
            # zero-scale simulations) never saw latency and stays under the
            # legacy budget/draining logic below.
            if mark >= self.wait_floor_s:
                self._fast_regime = True
            if self._fast_regime:
                # step toward min_depth — and do not fall through to the
                # grow branch even once parked there, or the two oscillate
                if self.depth > self.min_depth:
                    self.depth -= 1
                    self.shrinks += 1
                    self.latency_shrinks += 1
                return self.depth
        else:
            self._fast_regime = False
        # budget for the PROSPECTIVE depth: (depth+1) staged fetches + the
        # current fetch + one fetch of straddle slack must fit the cache
        budget_ok = (
            self._fetch_bytes > 0
            and (self.depth + 3) * self._fetch_bytes <= self.cache.max_bytes
        )
        if (
            mark > 0.0
            and wait >= self.wait_shift_factor * mark
            and self.depth < self.max_depth
            and budget_ok
        ):
            # latency regime shift UP: grow immediately without waiting for
            # the draining signal — slower storage is what staging is for
            self.depth += 1
            self.grows += 1
            self.latency_grows += 1
            return self.depth
        # headroom: background reads are draining — the in-flight table stays
        # within the window already scheduled (plus one fetch of slack)
        draining = inflight_blocks <= (self.depth + 1) * max(
            1.0, self._fetch_blocks
        )
        if budget_ok and draining and self.depth < self.max_depth:
            self.depth += 1
            self.grows += 1
        return self.depth

    def epoch_boundary(self) -> None:
        """Start the next epoch's decisions from a fresh pressure window (a
        regime change at the boundary should not be charged to the old
        depth).  The depth itself persists — storage did not change."""
        self._ev_mark = self.cache.evictions + self.cache.rejections
        self._fetches = 0

    def snapshot(self) -> dict:
        return {
            "depth": self.depth,
            "min_depth": self.min_depth,
            "max_depth": self.max_depth,
            "grows": self.grows,
            "shrinks": self.shrinks,
            "latency_grows": self.latency_grows,
            "latency_shrinks": self.latency_shrinks,
            "fetch_bytes_ewma": self._fetch_bytes,
            "wait_ewma_s": self._wait_ewma,
        }
