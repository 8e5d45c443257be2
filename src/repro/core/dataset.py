"""ScDataset — block sampling with batched fetching (paper Algorithm 1).

The JAX-native adaptation of the paper's PyTorch ``IterableDataset``:

- A :class:`~repro.core.sampling.SamplingStrategy` emits the deterministic
  global index sequence for the epoch (Alg. 1 lines 1–4).
- The sequence is split into *fetches* of ``batch_size * fetch_factor``
  indices (line 5).
- Fetches are assigned round-robin across ``world_size`` ranks and, within a
  rank, across prefetch workers (paper Appendix B) — every rank computes the
  same global sequence from the shared seed, so no coordination is needed.
- Per fetch: indices are sorted ascending (line 7) so the storage backend can
  coalesce reads, data is loaded in ONE backend call (line 8), reshuffled in
  memory (line 9), split into ``fetch_factor`` minibatches (line 10), and
  yielded (lines 11–12).

State is three integers (epoch, fetch cursor, seed): checkpointable,
restartable mid-epoch, identical across ranks.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Iterator, Optional

import numpy as np

from ..data.iostats import span
from .callbacks import Callbacks, MultiIndexable, default_batch_callback
from .sampling import BlockShuffling, SamplingStrategy, epoch_rng

__all__ = ["ScDataset", "LoaderState", "DiversityMonitor"]


class DiversityMonitor:
    """Streaming per-batch label-entropy telemetry over one obs column.

    The live half of the §3.4 theory: ``observe`` computes the plug-in
    entropy (bits) of one minibatch's labels — a single ``bincount`` over
    pre-resolved integer codes, no batch data retained — and records it
    into the collection's :class:`~repro.data.iostats.IOStats` diversity
    counters (``div_batches`` / ``div_entropy_sum`` / ``div_entropy_min``)
    when the collection carries stats.  Pure observation: it never touches
    the delivered stream, and an observation made inside a speculative
    duplicate fetch lands in the ``spec_*`` mirrors via the stats'
    deferred capture, exactly like the I/O counters.

    Codes resolve lazily on first observation (``np.unique`` over the full
    obs column — one pass, cached), so building a loader with
    ``diversity_obs`` costs nothing until it iterates.
    """

    def __init__(self, collection: Any, obs: str):
        if not hasattr(collection, "obs_column"):
            raise ValueError(
                f"diversity_obs={obs!r} needs a collection with obs columns "
                f"(obs_column); got {type(collection).__name__}"
            )
        self.obs = str(obs)
        self._collection = collection
        self._codes: Optional[np.ndarray] = None  # guarded-by: _lock
        self._num_classes = 0  # guarded-by: _lock — set with _codes
        # concurrent PrefetchPool workers may race the lazy resolve; the
        # column pass is idempotent but large, so do it exactly once
        self._lock = threading.Lock()

    def _resolve(self) -> np.ndarray:
        codes = self._codes  # unlocked-ok: racy fast path on an immutable-once-cached value
        if codes is not None:
            return codes
        with self._lock:
            if self._codes is None:
                values = np.asarray(self._collection.obs_column(self.obs))
                uniq, inv = np.unique(values, return_inverse=True)
                self._num_classes = int(len(uniq))
                self._codes = inv.astype(np.int64, copy=False)
            return self._codes

    @property
    def num_classes(self) -> int:
        self._resolve()
        return self._num_classes  # unlocked-ok: immutable once _resolve returned

    def class_probs(self) -> np.ndarray:
        """Empirical label distribution p over the whole collection — the
        H(p) reference the entropy-floor autotune predicts against."""
        codes = self._resolve()
        counts = np.bincount(codes, minlength=self._num_classes)  # unlocked-ok: immutable once _resolve returned
        return counts / max(1, len(codes))

    def observe(self, global_rows: np.ndarray) -> float:
        """Record (and return) the label entropy of one delivered batch."""
        from .theory import batch_entropy

        codes = self._resolve()
        h = batch_entropy(codes[np.asarray(global_rows)], self._num_classes)  # unlocked-ok: immutable once _resolve returned
        stats = getattr(self._collection, "iostats", None)
        if stats is not None and hasattr(stats, "record_diversity"):
            stats.record_diversity(h)
        return h


@dataclasses.dataclass
class LoaderState:
    """Everything needed to resume sampling exactly where it stopped.

    ``fetch_cursor`` indexes THIS RANK's fetch list; ``batch_cursor`` counts
    minibatches already delivered from the current fetch, so a checkpoint
    taken mid-fetch resumes on the exact next minibatch (no replay, no skip —
    the bitwise-restart test depends on this).

    The v2 fields make the state GLOBAL — sufficient to re-home the stream
    on a different rank/world (the elastic fabric, :mod:`repro.distributed.
    elastic`): ``world_size`` is the world the cursor was minted under,
    ``global_cursor`` is the global fetch id of the NEXT fetch this rank
    would execute (None once its epoch share is exhausted), and
    ``remaining`` is the explicit list of ``(global_fetch_id, skip_batches)``
    entries still owed — every epoch position is a pure function of
    ``(seed, epoch, global_fetch_id)``, so the union of ``remaining`` across
    ranks IS the not-yet-delivered stream, independent of who delivers it.
    All three are None on states minted by older checkpoints (the round-
    robin derivation from ``fetch_cursor`` still applies there).

    ``fingerprint`` — when the loader was built through the Pipeline API
    (:mod:`repro.pipeline`), the spec's content hash rides here so
    ``DataPipeline.load_state`` can REFUSE to resume against a drifted spec.
    None for hand-wired loaders (the low-level surface only checks the seed).
    """

    seed: int
    epoch: int
    fetch_cursor: int
    batch_cursor: int = 0
    fingerprint: Optional[str] = None
    world_size: Optional[int] = None
    global_cursor: Optional[int] = None
    remaining: Optional[tuple] = None  # ((global_fetch_id, skip_batches), ...)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_dict(d: dict) -> "LoaderState":
        rem = d.get("remaining")
        if rem is not None:  # JSON round-trips tuples as lists
            rem = tuple((int(g), int(s)) for g, s in rem)
        ws = d.get("world_size")
        gc = d.get("global_cursor")
        return LoaderState(int(d["seed"]), int(d["epoch"]),
                           int(d["fetch_cursor"]), int(d.get("batch_cursor", 0)),
                           d.get("fingerprint"),
                           None if ws is None else int(ws),
                           None if gc is None else int(gc),
                           rem)


class ScDataset:
    """Iterable over minibatches drawn quasi-randomly from an on-disk collection.

    Parameters mirror the paper: ``batch_size`` = m, ``fetch_factor`` = f, and
    the block size lives inside the strategy.  ``rank``/``world_size`` give
    DDP semantics; ``num_workers`` controls the prefetch pool (see
    :mod:`repro.core.prefetch` for the threaded executor — iteration here is
    synchronous and deterministic, the pool wraps it).
    """

    def __init__(
        self,
        collection: Any,
        strategy: Optional[SamplingStrategy] = None,
        *,
        batch_size: int = 64,
        fetch_factor: int = 1,
        seed: int = 0,
        rank: int = 0,
        world_size: int = 1,
        drop_last: bool = True,
        callbacks: Optional[Callbacks] = None,
        fetch_callback: Optional[Callable] = None,
        fetch_transform: Optional[Callable] = None,
        batch_callback: Optional[Callable] = None,
        batch_transform: Optional[Callable] = None,
        prefetch_callback: Optional[Callable] = None,
        sort_fetch_indices: bool = True,
        cross_epoch_prefetch: bool = False,
        diversity_obs: Optional[str] = None,
    ):
        if batch_size <= 0 or fetch_factor <= 0:
            raise ValueError("batch_size and fetch_factor must be positive")
        if not (0 <= rank < world_size):
            raise ValueError(f"rank {rank} out of range for world_size {world_size}")
        self.collection = collection
        self.strategy = strategy or BlockShuffling(block_size=16)
        self.batch_size = int(batch_size)
        self.fetch_factor = int(fetch_factor)
        self.seed = int(seed)
        self.rank = int(rank)
        self.world_size = int(world_size)
        self.drop_last = bool(drop_last)
        self.sort_fetch_indices = bool(sort_fetch_indices)
        self.cross_epoch_prefetch = bool(cross_epoch_prefetch)
        self.diversity_obs = diversity_obs
        self._div = (
            DiversityMonitor(collection, diversity_obs)
            if diversity_obs is not None else None
        )
        if callbacks is not None and any(
            cb is not None
            for cb in (fetch_callback, fetch_transform, batch_callback,
                       batch_transform, prefetch_callback)
        ):
            raise ValueError("pass either a Callbacks bundle or individual hooks, not both")
        self.callbacks = callbacks or Callbacks(
            fetch_callback, fetch_transform, batch_callback, batch_transform,
            prefetch_callback,
        )
        self._state = LoaderState(seed=self.seed, epoch=0, fetch_cursor=0)  # guarded-by: external
        # explicit fetch plan for the CURRENT epoch only — (gid, skip) entries
        # installed by repartition()/load_state() after an elastic resize;
        # None means the default round-robin derivation.  Cleared at the
        # epoch boundary: from the next epoch on, plain round-robin over the
        # (possibly new) world is again exactly-once globally.
        self._fetch_plan: Optional[list] = None  # guarded-by: external
        # epoch -> materialized order; holds at most TWO epochs (current +
        # next) so cross-epoch prefetch at the tail does not evict the order
        # the remaining fetches of this epoch still slice from
        self._order_lock = threading.Lock()
        self._order_cache: dict[int, np.ndarray] = {}  # guarded-by: _order_lock
        # Stamped by the Pipeline builder (repro.pipeline) with the spec's
        # content hash; surfaces in plan_epoch.  None for hand-wired loaders.
        self.spec_fingerprint: Optional[str] = None
        self._tuned_model = None  # guarded-by: external — autotune caller's
        self._tuned_base = None  # guarded-by: external — IOStats probe base
        self._tuned_ra_mark = 0  # guarded-by: external — ra depth-shift mark
        self._tuned_entropy = None  # guarded-by: external — predicted E[H] of the last rec

    # ------------------------------------------------------------------ sizes
    def __len__(self) -> int:
        """Minibatches yielded by THIS RANK in the CURRENT epoch — tail-exact.

        With ``drop_last=False`` the LAST global fetch may hold fewer than
        ``fetch_size`` rows and therefore yields ``ceil(rows/m)`` (not
        ``fetch_factor``) minibatches; whichever rank owns it yields fewer
        batches.  The old ``n_fetches * fetch_factor`` overcounted exactly
        there (and undercounted the final ragged batch itself).  Counted
        against the epoch's MATERIALIZED order (cached; weighted strategies
        draw blocks with replacement, so their order length — and hence the
        tail — varies per epoch while ``epoch_len`` is only the nominal
        size :meth:`fetch` ids are derived from).
        """
        order_len = len(self._epoch_order(self._state.epoch))
        return sum(
            max(0, self._fetch_num_batches(g, order_len) - skip)
            for g, skip in self._fetch_entries()
        )

    def _fetch_num_batches(self, global_fetch_id: int, order_len: int) -> int:
        """Minibatches fetch ``global_fetch_id`` yields (mirrors :meth:`fetch`)."""
        rows = min(self.fetch_size, order_len - global_fetch_id * self.fetch_size)
        if rows <= 0:
            return 0
        m = self.batch_size
        return rows // m if self.drop_last else (rows + m - 1) // m

    @property
    def n(self) -> int:
        return len(self.collection)

    @property
    def fetch_size(self) -> int:
        return self.batch_size * self.fetch_factor

    # -------------------------------------------------------------- plan
    def _epoch_order(self, epoch: int) -> np.ndarray:
        """Epoch index sequence, cached — pure function of (strategy, seed,
        epoch).  The cache keeps two epochs: the one just computed plus the
        cached epoch NEAREST to it (ties to the lower — the iterating epoch
        precedes its cross-epoch prefetch target), so an epoch's remaining
        tail fetches never evict their own order by prefetching the next
        one, even after a backward ``set_epoch``.  Locked: concurrent
        PrefetchPool workers hitting a cold epoch must not each materialize
        the full index array (hundreds of MB at atlas scale), and the
        keep-two eviction must act on a consistent dict."""
        order = self._order_cache.get(epoch)  # unlocked-ok: racy fast path on an immutable-once-cached value
        if order is not None:
            return order
        with self._order_lock:
            order = self._order_cache.get(epoch)
            if order is None:
                order = self.strategy.epoch_indices(self.n, self.seed, epoch)
                kept = {epoch: order}
                if self._order_cache:
                    near = min(
                        self._order_cache, key=lambda e: (abs(e - epoch), e)
                    )
                    kept[near] = self._order_cache[near]
                self._order_cache = kept
            return order

    def _global_fetch_count(self) -> int:
        total = self.strategy.epoch_len(self.n)
        if self.drop_last:
            return total // self.fetch_size
        return (total + self.fetch_size - 1) // self.fetch_size

    def _rank_fetch_slices(self) -> list[int]:
        """Global fetch ids owned by this rank (round-robin, Appendix B)."""
        g = self._global_fetch_count()
        return list(range(self.rank, g, self.world_size))

    def _fetch_entries(self) -> list:
        """This rank's epoch fetch list as ``(gid, skip_batches)`` entries —
        the explicit plan when one is installed, round-robin otherwise."""
        if self._fetch_plan is not None:
            return list(self._fetch_plan)
        return [(g, 0) for g in self._rank_fetch_slices()]

    def plan_epoch(self, epoch: Optional[int] = None) -> dict:
        """Introspection: the epoch's fetch plan without touching data.

        Surfaces the FULL stream geometry — sampling, batching, placement,
        and (when the collection is a planned one) the I/O-side async knobs
        plus the Pipeline spec fingerprint — so one dict answers "what will
        this rank read and yield this epoch, through what configuration".
        """
        epoch = self._state.epoch if epoch is None else epoch
        order = self._epoch_order(epoch)
        g = self._global_fetch_count()
        entries = self._fetch_entries()
        col = self.collection
        return {
            "epoch": epoch,
            "order_len": len(order),
            "global_fetches": g,
            "rank_fetches": [gid for gid, _ in entries],
            "explicit_plan": self._fetch_plan is not None,
            "fetch_size": self.fetch_size,
            "rank_batches": sum(
                max(0, self._fetch_num_batches(gid, len(order)) - skip)
                for gid, skip in entries
            ),
            "batch_size": self.batch_size,
            "fetch_factor": self.fetch_factor,
            "drop_last": self.drop_last,
            "sort_fetch_indices": self.sort_fetch_indices,
            "seed": self.seed,
            "rank": self.rank,
            "world_size": self.world_size,
            "io_workers": int(getattr(col, "io_workers", 1) or 1),
            "readahead": int(getattr(col, "readahead", 0) or 0),
            "readahead_auto": bool(getattr(col, "readahead_auto", False)),
            "admission": getattr(col, "admission", None),
            "cross_epoch_prefetch": self.cross_epoch_prefetch,
            "diversity_obs": self.diversity_obs,
            "fingerprint": self.spec_fingerprint,
        }

    # ----------------------------------------------------------- autotune
    def autotune(
        self,
        *,
        mem_budget_bytes: float = 2e9,
        drift_threshold: float = 0.5,
        num_classes: int = 14,
        entropy_slack_bits: float = 0.1,
        throughput_slack: float = 0.0,
        entropy_floor: Optional[float] = None,
        probes: int = 3,
        probe_rows: int = 512,
        apply: bool = False,
        force: bool = False,
    ):
        """Probe this loader's collection and recommend ``(b, f)`` in-process.

        Wires :func:`repro.core.autotune.probe_collection` +
        :func:`~repro.core.autotune.recommend` behind one call (the ROADMAP
        convenience).  The fitted cost model is cached; subsequent calls
        re-probe only when the collection's live :class:`IOStats` have
        DRIFTED from the fitted model by more than ``drift_threshold``
        (:func:`~repro.core.autotune.model_drift` — e.g. the cache stopped
        absorbing redraws, or an epoch switched from streaming to scattered
        access), or when ``force=True``.

        ``apply=True`` adopts the recommendation onto this loader:
        ``fetch_factor`` always, and the strategy's ``block_size`` when it
        has one.  Apply only at an epoch boundary — it changes the stream.
        Returns the :class:`~repro.core.autotune.Recommendation`.

        With ``entropy_floor`` set (bits), the recommendation is the leanest
        feasible cell whose PREDICTED E[H] clears the floor (§3.4 model);
        when the loader has a :class:`DiversityMonitor`, its empirical class
        distribution replaces the uniform ``num_classes`` prior, and the
        predicted entropy of the adopted recommendation feeds back into the
        drift check — measured batch entropy (``div_*`` counters) falling
        short of the prediction counts as model drift and triggers a
        re-probe on the next call.
        """
        from .autotune import model_drift, probe_collection, recommend_from

        col = self.collection
        if not (hasattr(col, "iostats") and hasattr(col, "cache")):
            raise TypeError(
                "autotune() needs a planned collection (open_collection); "
                f"got {type(col).__name__}"
            )
        # readahead depth changes since the last probe count as drift too:
        # the controller moving means the I/O regime the model was fitted
        # under no longer holds (see model_drift's ra_shifts)
        ctl = getattr(col, "_ra_controller", None)
        ra_now = (ctl.grows + ctl.shrinks) if ctl is not None else 0
        model = self._tuned_model
        if model is None or force or model_drift(
            model,
            col.iostats,
            base=self._tuned_base,
            ra_shifts=max(0, ra_now - self._tuned_ra_mark),
            expected_entropy=self._tuned_entropy,
        ) > drift_threshold:
            model = probe_collection(col, probes=probes, probe_rows=probe_rows)
            self._tuned_model = model
            # drift is measured on counter deltas from HERE, so a late
            # regime change is not diluted by lifetime totals
            self._tuned_base = col.iostats.snapshot()
            self._tuned_ra_mark = (
                (ctl.grows + ctl.shrinks) if ctl is not None else 0
            )
        rec = recommend_from(
            model,
            batch_size=self.batch_size,
            budget=mem_budget_bytes,
            num_classes=num_classes,
            entropy_slack_bits=entropy_slack_bits,
            throughput_slack=throughput_slack,
            class_probs=(
                self._div.class_probs() if self._div is not None else None
            ),
            entropy_floor=entropy_floor,
        )
        if apply:
            self._tuned_entropy = rec.predicted_entropy
            self.fetch_factor = int(rec.fetch_factor)
            if hasattr(self.strategy, "block_size"):
                self.strategy = dataclasses.replace(
                    self.strategy, block_size=int(rec.block_size)
                )
            with self._order_lock:
                self._order_cache = {}  # geometry changed; re-derive the order
        return rec

    # -------------------------------------------------------------- state
    def remaining_fetches(self) -> list:
        """The ``(global_fetch_id, skip_batches)`` entries this rank still
        owes the CURRENT epoch — the first entry carries the in-progress
        fetch's ``batch_cursor`` so a mid-fetch handover neither replays nor
        skips a minibatch.  The union of this list across ranks is exactly
        the not-yet-delivered remainder of the epoch's global stream; the
        elastic fabric merges and re-partitions it on a resize."""
        s = self._state
        entries = self._fetch_entries()
        out = []
        for i, (gid, skip) in enumerate(entries[s.fetch_cursor:]):
            if i == 0:
                skip = max(skip, s.batch_cursor)
            out.append((int(gid), int(skip)))
        return out

    def state(self) -> LoaderState:
        """Snapshot, v2: the rank-local cursor plus the global view
        (``world_size`` / ``global_cursor`` / ``remaining``) that lets a
        DIFFERENT loader — any rank of any world — continue this stream."""
        rem = self.remaining_fetches()
        return dataclasses.replace(
            self._state,
            world_size=self.world_size,
            global_cursor=rem[0][0] if rem else None,
            remaining=tuple(rem),
        )

    def load_state(self, state: LoaderState) -> None:
        if state.seed != self.seed:
            raise ValueError(
                f"checkpointed loader seed {state.seed} != configured seed {self.seed}; "
                "resuming with a different seed would silently change the data order"
            )
        if state.remaining is not None:
            # v2 state: the remaining list is authoritative — install it as
            # an explicit plan so resumption is bitwise regardless of this
            # loader's own rank/world_size (per-entry skips carry the
            # mid-fetch position; cursors restart at zero over the plan)
            self._fetch_plan = [(int(g), int(s)) for g, s in state.remaining]
            self._state = LoaderState(self.seed, state.epoch, 0, 0,
                                      state.fingerprint)
        else:
            self._fetch_plan = None
            self._state = dataclasses.replace(state)

    def repartition(
        self, rank: int, world_size: int, plan: Optional[list] = None
    ) -> None:
        """Re-home this loader as ``rank`` of ``world_size`` mid-epoch.

        With ``plan`` (a list of ``(global_fetch_id, skip_batches)``
        entries, e.g. one share of :func:`repro.distributed.elastic.
        partition`), the loader delivers exactly those fetches for the rest
        of the CURRENT epoch; from the next epoch on it reverts to plain
        round-robin under the new world.  Without ``plan`` the round-robin
        derivation applies immediately (a fresh-epoch join).  Cursors reset;
        the entries' skips carry any mid-fetch position.
        """
        if not (0 <= rank < world_size):
            raise ValueError(f"rank {rank} out of range for world_size {world_size}")
        self.rank = int(rank)
        self.world_size = int(world_size)
        if plan is None:
            self._fetch_plan = None
        else:
            g = self._global_fetch_count()
            norm = [(int(gid), int(skip)) for gid, skip in plan]
            bad = [gid for gid, _ in norm if not (0 <= gid < g)]
            if bad:
                raise ValueError(
                    f"plan contains global fetch ids {bad} outside [0, {g}) "
                    f"for this epoch's geometry"
                )
            self._fetch_plan = norm
        self._state = LoaderState(self.seed, self._state.epoch, 0, 0)

    def set_epoch(self, epoch: int) -> None:
        self._fetch_plan = None
        self._state = LoaderState(self.seed, int(epoch), 0)
        self._notify_epoch_boundary()

    def _notify_epoch_boundary(self) -> None:
        """Tell the collection an epoch boundary passed (the access regime
        may change): planned collections reset their stream detector and
        open a fresh readahead-controller window.  Plain collections (no
        ``epoch_boundary``) are unaffected."""
        eb = getattr(self.collection, "epoch_boundary", None)
        if eb is not None:
            eb()

    # -------------------------------------------------------------- fetch
    def _issue_prefetch(self, order: np.ndarray, global_fetch_id: int) -> bool:
        """Issue ONE fetch's read plan in the background (shared by the
        in-epoch and cross-epoch readahead windows); False when the fetch
        holds no rows."""
        lo = global_fetch_id * self.fetch_size
        idx = order[lo : min(lo + self.fetch_size, len(order))]
        if len(idx) == 0:
            return False
        self.callbacks.prefetch_callback(
            self.collection,
            np.sort(idx, kind="stable") if self.sort_fetch_indices else idx,
        )
        return True

    def fetch(self, epoch: int, global_fetch_id: int) -> list:
        """Materialize ONE fetch: Alg. 1 lines 7–10.  Returns f minibatches.

        Deterministic in ``(seed, epoch, global_fetch_id)`` alone — this is
        what makes work stealing and straggler re-issue idempotent.
        """
        order = self._epoch_order(epoch)
        lo = global_fetch_id * self.fetch_size
        hi = min(lo + self.fetch_size, len(order))
        fetch_idx = order[lo:hi]
        if len(fetch_idx) == 0:
            return []
        with span("scdataset.fetch", epoch=int(epoch), fetch=int(global_fetch_id),
                  rows=len(fetch_idx)):
            cbs = self.callbacks

            if self.sort_fetch_indices:
                sort_perm = np.argsort(fetch_idx, kind="stable")  # line 7
                sorted_idx = fetch_idx[sort_perm]
            else:
                sorted_idx = fetch_idx

            # Double buffering: issue the NEXT fetches' read plans (non-blocking)
            # BEFORE blocking on this fetch's I/O, so background planner reads
            # overlap this fetch's reads, assembly, and consumption.  Repeat
            # issues are cheap no-ops (cached / in-flight blocks are skipped), so
            # idempotent re-execution of a fetch stays safe.  ``readahead`` is
            # consulted per fetch on purpose: under readahead="auto" the
            # collection's controller moves the depth while we iterate.
            ra = int(getattr(self.collection, "readahead", 0) or 0)
            if ra > 0:
                g = self._global_fetch_count()
                if self._fetch_plan is not None:
                    # explicit plan (post-resize): the upcoming gids are the plan
                    # entries after THIS one, not a round-robin stride — guessing
                    # the stride would stage blocks this rank will never fetch
                    gids = [gid for gid, _ in self._fetch_plan]
                    try:
                        pos = gids.index(global_fetch_id)
                        upcoming = gids[pos + 1 : pos + 1 + ra]
                    except ValueError:
                        upcoming = []
                else:
                    upcoming = [
                        global_fetch_id + k * self.world_size
                        for k in range(1, ra + 1)
                    ]
                issued = 0
                for nxt in upcoming:
                    if nxt >= g or not self._issue_prefetch(order, nxt):
                        break
                    issued += 1
                if self.cross_epoch_prefetch and issued < ra:
                    # Epoch tail: the in-epoch window ran out, so fill the rest
                    # from epoch e+1's FIRST fetches of this rank — the epoch
                    # boundary stops draining the pipeline.  Same rendezvous
                    # table, so epoch e+1's first fetch finds its blocks staged
                    # (or in flight) instead of cold.  Next epoch's order is a
                    # pure function of (seed, epoch+1) and lands in the 2-slot
                    # order cache this epoch's remaining fetches don't need.
                    order2 = self._epoch_order(epoch + 1)
                    for j in range(ra - issued):
                        nxt2 = self.rank + j * self.world_size
                        if nxt2 >= g or not self._issue_prefetch(order2, nxt2):
                            break

            fetched = cbs.fetch_callback(self.collection, sorted_idx)  # line 8 — the ONLY disk I/O
            m = self.batch_size
            n = len(sorted_idx)
            nb = n // m if self.drop_last else (n + m - 1) // m
            with span("scdataset.split", batches=nb):
                fetched = cbs.fetch_transform(fetched)

                rng = epoch_rng(self.seed, epoch, 0xF37C, global_fetch_id)
                perm = rng.permutation(len(sorted_idx))  # line 9 — in-memory reshuffle

                batches = []
                for j in range(nb):  # line 10
                    rows = perm[j * m : (j + 1) * m]
                    if len(rows) == 0:
                        continue
                    if self._div is not None:
                        # global row ids of this minibatch — telemetry only, the
                        # delivered stream is untouched (see DiversityMonitor)
                        self._div.observe(sorted_idx[rows])
                    batch = cbs.batch_callback(fetched, rows)
                    batches.append(cbs.batch_transform(batch))
            return batches

    # -------------------------------------------------------------- iterate
    def __iter__(self) -> Iterator:
        """Yield minibatches, resuming from the checkpointed cursor.

        State is updated BEFORE each yield (to the position of the next
        batch) so a checkpoint taken while the consumer holds batch j
        resumes at batch j+1 even though this generator is suspended.
        """
        epoch = self._state.epoch
        entries = self._fetch_entries()
        cursor = self._state.fetch_cursor
        resume_skip = self._state.batch_cursor
        while cursor < len(entries):
            gid, base_skip = entries[cursor]
            # a plan entry's own skip marks batches another rank already
            # delivered before the handover; the resume cursor (>= it once
            # anything was delivered here) marks our own progress
            skip = max(base_skip, resume_skip)
            batches = self.fetch(epoch, gid)
            for j, batch in enumerate(batches):
                if j < skip:
                    continue
                if j + 1 < len(batches):
                    self._state = LoaderState(self.seed, epoch, cursor, j + 1)
                else:
                    self._state = LoaderState(self.seed, epoch, cursor + 1, 0)
                yield batch
            resume_skip = 0
            cursor += 1
        # epoch finished -> advance (an explicit resize plan covered the
        # CURRENT epoch only; round-robin under the current world resumes)
        self._fetch_plan = None
        self._state = LoaderState(self.seed, epoch + 1, 0, 0)
        self._notify_epoch_boundary()

    def epochs(self, num_epochs: int) -> Iterator:
        for _ in range(num_epochs):
            yield from iter(self)
