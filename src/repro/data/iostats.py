"""I/O instrumentation and a calibratable storage-latency model.

The container's filesystem (page-cached mmap on a VM disk) does not expose the
SATA-SSD random-access penalty the paper measures, so every backend threads an
:class:`IOStats` through its reads.  It records the quantities the paper's
cost argument is built on — number of backend calls, number of *random runs*
(distinct contiguous extents touched = seeks), and bytes moved — and can
optionally *simulate* a storage regime by sleeping ``seek_s`` per run and
``1/bw_Bps`` per byte.  Benchmarks report both measured wall-clock and the
modeled time so the reproduction is explicit about what is real and what is
calibrated (see DESIGN.md §2).

:func:`span` marks the loader's phases (``scdataset.fetch``, ``.plan``,
``.read``, ``.assemble``, ``.split``, ``.to_dense``, ``.put_batch``) as
``jax.profiler.TraceAnnotation``s, so that a profiler trace shows them on
the device trace's clock, beside the device's ops.  The counters stay here.
"""
from __future__ import annotations

import contextlib
import dataclasses
import sys
import threading
import time
from typing import Iterator, Optional

__all__ = ["IOStats", "PendingIO", "StorageModel", "SATA_SSD", "NVME_SSD", "CLOUD_OBJECT",
           "span"]

_NO_SPAN = contextlib.nullcontext()


def span(name: str, **args: int):
    """A ``jax.profiler.TraceAnnotation`` named ``name``, with ``args`` (counts
    known when it opens) as its trace stats; a no-op context where JAX is not
    imported.

    The loader imports no JAX itself: a profiler runs only in a process that
    has imported it, so where JAX is absent there is nothing to record.  With
    no profiler running an annotation costs well under a microsecond.
    """
    jax = sys.modules.get("jax")
    if jax is None:
        return _NO_SPAN
    return jax.profiler.TraceAnnotation(name, **args)


@dataclasses.dataclass
class StorageModel:
    """Per-run (seek/request) latency and streaming bandwidth."""

    name: str
    seek_s: float  # cost of one random access / request round-trip
    bw_Bps: float  # sequential streaming bandwidth

    def seconds(self, runs: int, bytes_read: int) -> float:
        return runs * self.seek_s + bytes_read / self.bw_Bps


# Calibrated so that ~20 samples/sec emerge for one-random-row-per-sample reads
# of ~50KB sparse rows, matching the paper's AnnLoader baseline on SATA SSD
# (paper §1: ~20 samples/sec, §4.1).  0.05s/seek is the effective per-call
# HDF5+SATA latency implied by that number; raw device seek is lower but the
# paper's figure folds in HDF5 chunk decode per call.
SATA_SSD = StorageModel("sata_ssd_hdf5", seek_s=0.048, bw_Bps=450e6)
NVME_SSD = StorageModel("nvme_ssd", seek_s=0.0008, bw_Bps=3.2e9)
CLOUD_OBJECT = StorageModel("cloud_object", seek_s=0.030, bw_Bps=1.0e9)


@dataclasses.dataclass
class PendingIO:
    """One fetch execution's counters, captured before they reach the shared
    totals.  Produced by :meth:`IOStats.deferred`; merged back — into the
    main counters or the ``spec_*`` duplicate counters — by
    :meth:`IOStats.commit` once the caller knows whether the execution's
    result was delivered or dropped as a speculative duplicate.
    """

    calls: int = 0  # guarded-by: _lock
    runs: int = 0  # guarded-by: _lock
    rows: int = 0  # guarded-by: _lock
    bytes_read: int = 0  # guarded-by: _lock
    cache_hits: int = 0  # guarded-by: _lock
    cache_misses: int = 0  # guarded-by: _lock
    prefetched: int = 0  # guarded-by: _lock
    requests: int = 0  # guarded-by: _lock
    adm_bypassed: int = 0  # guarded-by: _lock
    adm_rejected: int = 0  # guarded-by: _lock
    retries: int = 0  # guarded-by: _lock
    hedges_issued: int = 0  # guarded-by: _lock
    hedges_won: int = 0  # guarded-by: _lock
    breaker_opens: int = 0  # guarded-by: _lock
    breaker_closes: int = 0  # guarded-by: _lock
    reissued_fetches: int = 0  # guarded-by: _lock
    shared_rank_hits: int = 0  # guarded-by: _lock
    blocks_cut: int = 0  # guarded-by: _lock
    div_batches: int = 0  # guarded-by: _lock
    div_entropy_sum: float = 0.0  # guarded-by: _lock
    div_entropy_min: float = 0.0  # guarded-by: _lock — valid only when div_batches > 0
    wall_s: float = 0.0  # guarded-by: _lock
    modeled_s: float = 0.0  # guarded-by: _lock
    request_wait_s: float = 0.0  # guarded-by: _lock
    retry_wait_s: float = 0.0  # guarded-by: _lock

    def __post_init__(self):
        # a deferred fetch's pool-thread reads may record requests into this
        # buffer concurrently (IOStats.borrowed_pending); not a field, so
        # asdict/eq are unaffected
        self._lock = threading.Lock()


#: counters :meth:`IOStats.commit` merges by MIN instead of sum, mapped to
#: the gate counter that marks them valid (min over zero observations is
#: meaningless, so a buffer contributes its minimum only when its gate > 0)
_MIN_MERGE = {"div_entropy_min": "div_batches"}


@dataclasses.dataclass
class IOStats:
    """Counters threaded through backend reads.

    ``simulate`` — if set, reads sleep according to the model (scaled by
    ``simulate_scale`` so CI stays fast while ratios are preserved).

    The main counters describe work whose result was (or will be) delivered.
    ``spec_*`` counters hold fetch executions whose completion was *dropped*
    (a speculative straggler re-issue lost the race): the I/O genuinely
    happened, but folding it into the main counters would corrupt
    runs-per-sample and ``cache_hit_rate`` relative to delivered data.
    ``prefetched`` counts blocks a fetch obtained by waiting on an in-flight
    background read (readahead rendezvous) — served without a new physical
    read, but not a cache hit either.

    ``requests`` counts per-request storage operations (one object-store GET
    each), recorded by request-semantics adapters (``cloud://``) via
    :meth:`record_request` — a *subset view* of ``runs``: every request is a
    run, but local backends issue runs that are not requests.
    ``request_wait_s`` accumulates each request's full duration as observed
    by its calling thread (first-byte latency + bandwidth + queueing for an
    in-flight slot); concurrent requests overlap, so this can exceed wall
    time.

    ``adm_bypassed`` / ``adm_rejected`` count cache-admission decisions made
    by the planner: insertions skipped outright by a bypassing policy
    (``admission="never"`` or the stream-detector bypass) versus candidates
    that lost the TinyLFU frequency duel against the LRU victim
    (``admission="auto"`` once the working set exceeds the cache budget).
    Neither changes delivered data — they explain hit-rate shape.

    The resilience counters describe fault recovery: ``retries`` counts
    failed read attempts that were re-issued (``retry_wait_s`` sums their
    backoff sleeps, overlappable like ``request_wait_s``), ``hedges_issued``
    / ``hedges_won`` count duplicate tail-latency reads and how many beat
    their primary, and ``breaker_opens`` / ``breaker_closes`` count
    per-shard circuit-breaker transitions.  None of them change delivered
    data — under a seeded fault profile delivered epochs stay bitwise
    identical to the fault-free run; these counters are how that recovery
    work is made visible.

    The elastic counters make the multi-host fabric's work visible:
    ``reissued_fetches`` counts suspect-rank fetches re-issued idempotently
    by the supervisor (each rides the rendezvous table, so a block already
    in flight costs zero extra physical reads) and ``shared_rank_hits``
    counts blocks one rank obtained from another co-located rank's read —
    the RINAS-style cross-rank dedup win, measurable against ``requests``.

    ``blocks_cut`` counts missed blocks a demand fetch cut out of its read
    extents as values of their own: those a rendezvous claimant waits on,
    and those the block cache still holds when the fetch ends.  The rest
    of a fetch's missed rows go from the extents straight into the batch,
    so ``blocks_cut / cache_misses`` is the share of missed blocks that
    paid for a copy.  Readahead staging cuts every block it reads and is
    not counted.

    The diversity counters are the loader's live §3.4 observatory:
    ``div_batches`` counts minibatches whose label entropy was observed
    (a :class:`~repro.core.dataset.ScDataset` built with ``diversity_obs``
    calls :meth:`record_diversity` once per materialized batch),
    ``div_entropy_sum`` accumulates their per-batch plug-in entropies in
    bits (mean = sum / batches), and ``div_entropy_min`` tracks the worst
    batch seen — meaningful only while ``div_batches > 0``, and merged by
    MIN (not sum) in :meth:`commit`.  Pure observation: recording entropy
    never changes delivered bytes, and speculative duplicate fetches'
    observations land in the ``spec_*`` mirrors via the same deferred
    capture as every other counter.
    """

    calls: int = 0  # guarded-by: _lock
    runs: int = 0  # guarded-by: _lock — contiguous extents == random accesses
    rows: int = 0  # guarded-by: _lock
    bytes_read: int = 0  # guarded-by: _lock
    cache_hits: int = 0  # guarded-by: _lock — planner block-cache hits
    cache_misses: int = 0  # guarded-by: _lock
    prefetched: int = 0  # guarded-by: _lock — readahead-rendezvous blocks
    requests: int = 0  # guarded-by: _lock — per-request ops (cloud:// GETs)
    adm_bypassed: int = 0  # guarded-by: _lock — bypassing-admission skips
    adm_rejected: int = 0  # guarded-by: _lock — TinyLFU duels lost
    retries: int = 0  # guarded-by: _lock — failed read attempts retried
    hedges_issued: int = 0  # guarded-by: _lock — duplicate tail-latency reads
    hedges_won: int = 0  # guarded-by: _lock — hedges that beat the primary
    breaker_opens: int = 0  # guarded-by: _lock — shard breakers tripped open
    breaker_closes: int = 0  # guarded-by: _lock — breakers closed by a probe
    reissued_fetches: int = 0  # guarded-by: _lock — suspect-rank fetches re-issued
    shared_rank_hits: int = 0  # guarded-by: _lock — blocks served by another rank's read
    blocks_cut: int = 0  # guarded-by: _lock — missed blocks given a value of their own
    div_batches: int = 0  # guarded-by: _lock — batches with observed entropy
    div_entropy_sum: float = 0.0  # guarded-by: _lock — summed batch bits
    div_entropy_min: float = 0.0  # guarded-by: _lock — worst batch; valid iff div_batches > 0
    request_wait_s: float = 0.0  # guarded-by: _lock — summed, overlappable
    retry_wait_s: float = 0.0  # guarded-by: _lock — summed backoff sleeps
    wall_s: float = 0.0  # guarded-by: _lock
    simulate: Optional[StorageModel] = None  # set once at construction
    simulate_scale: float = 1.0
    modeled_s: float = 0.0  # guarded-by: _lock
    # speculative-duplicate executions (dropped from delivery)
    spec_calls: int = 0  # guarded-by: _lock
    spec_runs: int = 0  # guarded-by: _lock
    spec_rows: int = 0  # guarded-by: _lock
    spec_bytes_read: int = 0  # guarded-by: _lock
    spec_cache_hits: int = 0  # guarded-by: _lock
    spec_cache_misses: int = 0  # guarded-by: _lock
    spec_prefetched: int = 0  # guarded-by: _lock
    spec_requests: int = 0  # guarded-by: _lock
    spec_adm_bypassed: int = 0  # guarded-by: _lock
    spec_adm_rejected: int = 0  # guarded-by: _lock
    spec_retries: int = 0  # guarded-by: _lock
    spec_hedges_issued: int = 0  # guarded-by: _lock
    spec_hedges_won: int = 0  # guarded-by: _lock
    spec_breaker_opens: int = 0  # guarded-by: _lock
    spec_breaker_closes: int = 0  # guarded-by: _lock
    spec_reissued_fetches: int = 0  # guarded-by: _lock
    spec_shared_rank_hits: int = 0  # guarded-by: _lock
    spec_blocks_cut: int = 0  # guarded-by: _lock
    spec_div_batches: int = 0  # guarded-by: _lock
    spec_div_entropy_sum: float = 0.0  # guarded-by: _lock
    spec_div_entropy_min: float = 0.0  # guarded-by: _lock
    spec_request_wait_s: float = 0.0  # guarded-by: _lock
    spec_retry_wait_s: float = 0.0  # guarded-by: _lock
    spec_wall_s: float = 0.0  # guarded-by: _lock
    spec_modeled_s: float = 0.0  # guarded-by: _lock

    def __post_init__(self):
        # Concurrent PrefetchPool workers record() through one shared
        # IOStats; the bare `+=` read-modify-writes would lose updates.
        # Not a dataclass field, so asdict/eq/replace are unaffected.
        self._lock = threading.Lock()
        self._tl = threading.local()

    def record(
        self,
        *,
        runs: int,
        rows: int,
        bytes_read: int,
        wall_s: float,
        cache_hits: int = 0,
        cache_misses: int = 0,
        prefetched: int = 0,
        adm_bypassed: int = 0,
        adm_rejected: int = 0,
        shared_rank_hits: int = 0,
        blocks_cut: int = 0,
        calls: int = 1,
        slept: bool = False,
    ) -> None:
        """Account one planner/backend call.

        ``calls=0`` — background readahead work that is not a consumer-facing
        fetch.  ``slept=True`` — the caller already slept the simulated
        latency per physical read (the planner's read path does this so
        concurrent reads overlap); modeled time still accumulates here.
        """
        dt = self.simulate.seconds(runs, bytes_read) if self.simulate is not None else 0.0
        pend: Optional[PendingIO] = getattr(self._tl, "pending", None)
        if pend is not None:
            with pend._lock:
                pend.calls += calls
                pend.runs += runs
                pend.rows += rows
                pend.bytes_read += bytes_read
                pend.cache_hits += cache_hits
                pend.cache_misses += cache_misses
                pend.prefetched += prefetched
                pend.adm_bypassed += adm_bypassed
                pend.adm_rejected += adm_rejected
                pend.shared_rank_hits += shared_rank_hits
                pend.blocks_cut += blocks_cut
                pend.wall_s += wall_s
                pend.modeled_s += dt
        elif getattr(self._tl, "scope", None) is not None:
            self._tl.scope.record(
                runs=runs, rows=rows, bytes_read=bytes_read, wall_s=wall_s,
                cache_hits=cache_hits, cache_misses=cache_misses,
                prefetched=prefetched, adm_bypassed=adm_bypassed,
                adm_rejected=adm_rejected, shared_rank_hits=shared_rank_hits,
                blocks_cut=blocks_cut, calls=calls, slept=slept,
            )
            return  # the scoped child slept the simulated latency already
        else:
            with self._lock:
                self.calls += calls
                self.runs += runs
                self.rows += rows
                self.bytes_read += bytes_read
                self.cache_hits += cache_hits
                self.cache_misses += cache_misses
                self.prefetched += prefetched
                self.adm_bypassed += adm_bypassed
                self.adm_rejected += adm_rejected
                self.shared_rank_hits += shared_rank_hits
                self.blocks_cut += blocks_cut
                self.wall_s += wall_s
                self.modeled_s += dt
        # sleep OUTSIDE the lock: simulated latency must overlap across
        # workers exactly like real storage would
        if not slept and self.simulate is not None and self.simulate_scale > 0:
            time.sleep(dt * self.simulate_scale)

    def record_request(self, n: int = 1, *, wait_s: float = 0.0) -> None:
        """Account ``n`` per-request storage operations (object-store GETs).

        Called by request-semantics adapters from the reading thread — one
        call per physical ``read_range``, so requests the planner never
        issued (cache hits, rendezvous-deduped blocks) are never counted.
        Respects :meth:`deferred` capture like :meth:`record` does, so a
        speculative duplicate's requests land in ``spec_requests``.
        """
        pend: Optional[PendingIO] = getattr(self._tl, "pending", None)
        if pend is not None:
            with pend._lock:
                pend.requests += n
                pend.request_wait_s += wait_s
        elif getattr(self._tl, "scope", None) is not None:
            self._tl.scope.record_request(n, wait_s=wait_s)
        else:
            with self._lock:
                self.requests += n
                self.request_wait_s += wait_s

    def record_resilience(
        self,
        *,
        retries: int = 0,
        retry_wait_s: float = 0.0,
        hedges_issued: int = 0,
        hedges_won: int = 0,
        breaker_opens: int = 0,
        breaker_closes: int = 0,
    ) -> None:
        """Account fault-recovery events (retry engine / hedger / breaker).

        ``retries`` counts failed read attempts that were re-issued (with
        ``retry_wait_s`` summing their backoff sleeps); ``hedges_issued`` /
        ``hedges_won`` count duplicate tail-latency reads and how many beat
        their primary; breaker transitions count per-shard circuit state
        changes.  Honors :meth:`deferred` capture like :meth:`record`, so a
        speculative duplicate's recovery work lands in the ``spec_*``
        mirrors rather than polluting the delivered-data totals.
        """
        pend: Optional[PendingIO] = getattr(self._tl, "pending", None)
        if pend is not None:
            with pend._lock:
                pend.retries += retries
                pend.retry_wait_s += retry_wait_s
                pend.hedges_issued += hedges_issued
                pend.hedges_won += hedges_won
                pend.breaker_opens += breaker_opens
                pend.breaker_closes += breaker_closes
        elif getattr(self._tl, "scope", None) is not None:
            self._tl.scope.record_resilience(
                retries=retries, retry_wait_s=retry_wait_s,
                hedges_issued=hedges_issued, hedges_won=hedges_won,
                breaker_opens=breaker_opens, breaker_closes=breaker_closes,
            )
        else:
            with self._lock:
                self.retries += retries
                self.retry_wait_s += retry_wait_s
                self.hedges_issued += hedges_issued
                self.hedges_won += hedges_won
                self.breaker_opens += breaker_opens
                self.breaker_closes += breaker_closes

    def record_elastic(
        self,
        *,
        reissued_fetches: int = 0,
        shared_rank_hits: int = 0,
    ) -> None:
        """Account elastic-fabric events.

        ``reissued_fetches`` counts suspect-rank fetches the
        :class:`~repro.distributed.elastic.ElasticSupervisor` re-issued
        idempotently through the rendezvous table; ``shared_rank_hits``
        counts blocks one rank obtained from another co-located rank's
        physical read (also recordable inline via :meth:`record`).  Neither
        changes delivered data — re-issue rides the in-flight dedup and
        costs zero extra reads for blocks already in flight.  Honors
        :meth:`deferred` capture like every other recorder.
        """
        pend: Optional[PendingIO] = getattr(self._tl, "pending", None)
        if pend is not None:
            with pend._lock:
                pend.reissued_fetches += reissued_fetches
                pend.shared_rank_hits += shared_rank_hits
        elif getattr(self._tl, "scope", None) is not None:
            self._tl.scope.record_elastic(
                reissued_fetches=reissued_fetches,
                shared_rank_hits=shared_rank_hits,
            )
        else:
            with self._lock:
                self.reissued_fetches += reissued_fetches
                self.shared_rank_hits += shared_rank_hits

    def record_diversity(self, entropy_bits: float) -> None:
        """Account one delivered minibatch's label entropy (bits).

        Called by :class:`~repro.core.dataset.ScDataset` once per batch it
        materializes when built with ``diversity_obs`` — a streaming
        histogram, no batch data is retained.  ``div_entropy_min`` is the
        running worst batch and only meaningful while ``div_batches > 0``
        (an entropy of 0.0 is a legal observation — a single-class batch —
        so "no observations yet" is gated on the count, not the value).
        Honors :meth:`deferred` capture like :meth:`record`, so a dropped
        speculative duplicate's observations land in the ``spec_*``
        mirrors instead of double-counting delivered batches.
        """
        h = float(entropy_bits)
        pend: Optional[PendingIO] = getattr(self._tl, "pending", None)
        if pend is not None:
            with pend._lock:
                if pend.div_batches == 0 or h < pend.div_entropy_min:
                    pend.div_entropy_min = h
                pend.div_batches += 1
                pend.div_entropy_sum += h
        elif getattr(self._tl, "scope", None) is not None:
            self._tl.scope.record_diversity(h)
        else:
            with self._lock:
                if self.div_batches == 0 or h < self.div_entropy_min:
                    self.div_entropy_min = h
                self.div_batches += 1
                self.div_entropy_sum += h

    def sleep_for(self, runs: int, bytes_read: int) -> None:
        """Sleep the simulated latency of one physical read, in the reading
        thread — concurrent reads overlap their modeled latency exactly like
        real storage.  No counters are touched; pair with
        ``record(..., slept=True)``."""
        if self.simulate is not None and self.simulate_scale > 0:
            time.sleep(self.simulate.seconds(runs, bytes_read) * self.simulate_scale)

    def current_pending(self) -> Optional[PendingIO]:
        """This thread's active :meth:`deferred` buffer, if any — pass it to
        :meth:`borrowed_pending` on worker threads doing this fetch's reads."""
        return getattr(self._tl, "pending", None)

    @contextlib.contextmanager
    def borrowed_pending(self, pend: Optional[PendingIO]) -> Iterator[None]:
        """Install another thread's capture buffer for the duration.

        A deferred (possibly speculative) fetch executes its miss extents on
        the shared I/O pool; reads that record per-thread (the ``cloud://``
        request counters) would otherwise escape the capture and pollute the
        delivered-data totals.  No-op when ``pend`` is None or this thread
        is already capturing (the consumer thread reading its own spans).
        """
        if pend is None or getattr(self._tl, "pending", None) is not None:
            yield
            return
        self._tl.pending = pend
        try:
            yield
        finally:
            self._tl.pending = None

    @contextlib.contextmanager
    def deferred(self) -> Iterator[PendingIO]:
        """Capture this thread's ``record()`` calls into a :class:`PendingIO`
        instead of the shared totals.  The caller decides afterwards via
        :meth:`commit` whether the execution was delivered (main counters) or
        a dropped speculative duplicate (``spec_*``).  An uncommitted pending
        buffer is simply discarded."""
        if getattr(self._tl, "pending", None) is not None:
            raise RuntimeError("nested IOStats.deferred() on one thread")
        pend = PendingIO()
        self._tl.pending = pend
        try:
            yield pend
        finally:
            self._tl.pending = None

    def commit(self, pend: PendingIO, *, speculative: bool = False) -> None:
        # every PendingIO field has both a main and a spec_ counterpart, so
        # new counters added there are committed automatically
        scope: Optional["IOStats"] = getattr(self._tl, "scope", None)
        if scope is not None:
            # the committing thread is inside scoped(): the fetch belongs to
            # that scope's owner (a serve tenant), so its counters do too
            scope.commit(pend, speculative=speculative)
            return
        prefix = "spec_" if speculative else ""
        with self._lock:
            # min-merged counters need the target's PRE-merge validity gate:
            # div_batches may be summed into the target before the loop
            # reaches div_entropy_min, so capture "had observations" first
            had_div = getattr(self, prefix + "div_batches") > 0
            for f in dataclasses.fields(PendingIO):
                name = prefix + f.name
                if f.name in _MIN_MERGE:
                    # a minimum, not a sum: only meaningful when the buffer
                    # actually observed batches (its gate counter is > 0)
                    if getattr(pend, _MIN_MERGE[f.name]) > 0:
                        v = getattr(pend, f.name)
                        cur = getattr(self, name)
                        setattr(self, name, min(cur, v) if had_div else v)
                else:
                    setattr(self, name, getattr(self, name) + getattr(pend, f.name))

    def merge(self, other: "IOStats") -> None:
        """Fold another IOStats' totals into this one.

        Sums every counter — main *and* ``spec_*`` mirrors — generically
        over ``dataclasses.fields(PendingIO)``, with the same MIN semantics
        for :data:`_MIN_MERGE` counters that :meth:`commit` applies (a
        source's ``div_entropy_min`` only participates when its gate
        counter says it actually observed batches).  The source is read via
        one consistent :meth:`snapshot` *before* this object's lock is
        taken, so two IOStats locks are never held at once (no lock-order
        edge between sibling stats).  The source is left untouched:
        aggregation never double counts as long as each event was recorded
        into exactly one stats object — which is what :meth:`scoped`
        guarantees for serve tenants.
        """
        snap = other.snapshot()
        with self._lock:
            for prefix in ("", "spec_"):
                # capture the target's PRE-merge validity gate first, as in
                # commit(): div_batches is summed before the min is merged
                had_div = getattr(self, prefix + "div_batches") > 0
                for f in dataclasses.fields(PendingIO):
                    name = prefix + f.name
                    if f.name in _MIN_MERGE:
                        if snap[prefix + _MIN_MERGE[f.name]] > 0:
                            v = snap[name]
                            cur = getattr(self, name)
                            setattr(self, name, min(cur, v) if had_div else v)
                    else:
                        setattr(self, name, getattr(self, name) + snap[name])

    def child(self) -> "IOStats":
        """A fresh scoped child sharing this object's storage model.

        Children accumulate independently; route a thread's recordings into
        one with :meth:`scoped`, then build an aggregate view by
        :meth:`merge`-ing the children into a copy of the base.  The child
        is *not* registered anywhere — the caller owns its lifetime (the
        serve layer keeps one per tenant).
        """
        return IOStats(simulate=self.simulate, simulate_scale=self.simulate_scale)

    @contextlib.contextmanager
    def scoped(self, child: Optional["IOStats"]) -> Iterator[None]:
        """Route this thread's recordings into ``child`` for the duration.

        While active, :meth:`record` / :meth:`record_request` /
        :meth:`record_resilience` / :meth:`record_diversity` and
        :meth:`commit` calls made *by this thread* against this (shared)
        stats object land in ``child`` instead of the shared totals — an
        active :meth:`deferred` capture still wins, and its later
        :meth:`commit` follows the scope, so per-fetch speculative
        accounting is preserved per tenant.  Pool threads doing this
        fetch's reads are unaffected (they record through
        :meth:`borrowed_pending` into the capture buffer, which commits
        here).  No-op when ``child`` is None.  Reentrant: an inner scope
        shadows the outer one for its duration.
        """
        if child is None:
            yield
            return
        prev = getattr(self._tl, "scope", None)
        self._tl.scope = child
        try:
            yield
        finally:
            self._tl.scope = prev

    def reset(self) -> None:
        with self._lock:
            self.calls = self.runs = self.rows = self.bytes_read = 0
            self.cache_hits = self.cache_misses = self.prefetched = 0
            self.requests = 0
            self.adm_bypassed = self.adm_rejected = 0
            self.retries = self.hedges_issued = self.hedges_won = 0
            self.breaker_opens = self.breaker_closes = 0
            self.reissued_fetches = self.shared_rank_hits = 0
            self.blocks_cut = 0
            self.div_batches = 0
            self.div_entropy_sum = self.div_entropy_min = 0.0
            self.wall_s = self.modeled_s = self.request_wait_s = 0.0
            self.retry_wait_s = 0.0
            self.spec_calls = self.spec_runs = self.spec_rows = 0
            self.spec_bytes_read = 0
            self.spec_cache_hits = self.spec_cache_misses = 0
            self.spec_prefetched = self.spec_requests = 0
            self.spec_adm_bypassed = self.spec_adm_rejected = 0
            self.spec_retries = self.spec_hedges_issued = 0
            self.spec_hedges_won = 0
            self.spec_breaker_opens = self.spec_breaker_closes = 0
            self.spec_reissued_fetches = self.spec_shared_rank_hits = 0
            self.spec_blocks_cut = 0
            self.spec_div_batches = 0
            self.spec_div_entropy_sum = self.spec_div_entropy_min = 0.0
            self.spec_request_wait_s = self.spec_retry_wait_s = 0.0
            self.spec_wall_s = self.spec_modeled_s = 0.0

    @property
    def cache_hit_rate(self) -> float:
        # under _lock: hits and misses must come from one consistent state,
        # or a rate read mid-record can exceed 1.0 / go negative in deltas
        with self._lock:
            total = self.cache_hits + self.cache_misses
            return self.cache_hits / total if total else 0.0

    def snapshot(self) -> dict:
        # one consistent cut of every counter: without the lock a snapshot
        # taken mid-record can pair e.g. the new `runs` with the old
        # `bytes_read` and downstream deltas (autotune probes) go skewed
        with self._lock:
            return {
                "calls": self.calls,
                "runs": self.runs,
                "rows": self.rows,
                "bytes_read": self.bytes_read,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "prefetched": self.prefetched,
                "requests": self.requests,
                "adm_bypassed": self.adm_bypassed,
                "adm_rejected": self.adm_rejected,
                "retries": self.retries,
                "hedges_issued": self.hedges_issued,
                "hedges_won": self.hedges_won,
                "breaker_opens": self.breaker_opens,
                "breaker_closes": self.breaker_closes,
                "reissued_fetches": self.reissued_fetches,
                "shared_rank_hits": self.shared_rank_hits,
                "blocks_cut": self.blocks_cut,
                "div_batches": self.div_batches,
                "div_entropy_sum": self.div_entropy_sum,
                "div_entropy_min": self.div_entropy_min,
                "request_wait_s": self.request_wait_s,
                "retry_wait_s": self.retry_wait_s,
                "wall_s": self.wall_s,
                "modeled_s": self.modeled_s,
                "spec_calls": self.spec_calls,
                "spec_runs": self.spec_runs,
                "spec_rows": self.spec_rows,
                "spec_bytes_read": self.spec_bytes_read,
                "spec_cache_hits": self.spec_cache_hits,
                "spec_cache_misses": self.spec_cache_misses,
                "spec_prefetched": self.spec_prefetched,
                "spec_requests": self.spec_requests,
                "spec_adm_bypassed": self.spec_adm_bypassed,
                "spec_adm_rejected": self.spec_adm_rejected,
                "spec_retries": self.spec_retries,
                "spec_hedges_issued": self.spec_hedges_issued,
                "spec_hedges_won": self.spec_hedges_won,
                "spec_breaker_opens": self.spec_breaker_opens,
                "spec_breaker_closes": self.spec_breaker_closes,
                "spec_reissued_fetches": self.spec_reissued_fetches,
                "spec_shared_rank_hits": self.spec_shared_rank_hits,
                "spec_blocks_cut": self.spec_blocks_cut,
                "spec_div_batches": self.spec_div_batches,
                "spec_div_entropy_sum": self.spec_div_entropy_sum,
                "spec_div_entropy_min": self.spec_div_entropy_min,
                "spec_request_wait_s": self.spec_request_wait_s,
                "spec_retry_wait_s": self.spec_retry_wait_s,
                "spec_wall_s": self.spec_wall_s,
                "spec_modeled_s": self.spec_modeled_s,
            }

    def total_seconds(self) -> float:
        """Wall time plus any un-slept modeled time (simulate_scale < 1)."""
        with self._lock:
            if self.simulate is None:
                return self.wall_s
            return self.wall_s + self.modeled_s * max(
                0.0, 1.0 - self.simulate_scale
            )
