"""On-disk CSR cell-by-gene store — the AnnData-equivalent substrate.

An AnnData .h5ad holds X as CSR (data / indices / indptr) plus obs metadata.
Without h5py in this container we store the same three arrays as raw ``.npy``
files opened with ``mmap_mode='r'`` — identical asymptotics: per-call
overhead, random-extent penalty, contiguous-read advantage.  The store is the
``collection`` an :class:`repro.core.ScDataset` indexes.

Two key classes:

- :class:`CSRStore` — one shard (= one "plate file" in Tahoe-100M terms).
- :class:`ShardedCSRStore` — lazy concatenation of shards, mirroring
  ``anndata.experimental.AnnCollection`` over the 14 Tahoe plate files.

Indexing ``store[rows]`` (rows sorted or not) performs run-coalesced reads:
sorted rows are grouped into maximal contiguous runs, each run is ONE slice
read of the memmaps.  ``IOStats.runs`` therefore counts exactly the random
accesses of the paper's cost model, and block sampling reduces it by
construction.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import threading
import time
from concurrent import futures
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Sequence

import numpy as np

from .iostats import IOStats, span
from .readplan import coalesce_rows

__all__ = [
    "BufferPool",
    "CSRBatch",
    "CSRStore",
    "ShardedCSRStore",
    "gather_rows",
    "gathered_nbytes",
    "write_csr_shard",
]


# glibc's largest mmap threshold on 64-bit: an array this large is always a
# fresh mapping, whose pages fault on first touch
DENSE_POOL_BYTES = 32 << 20

# ``to_dense`` gives each thread at least this much output to zero and fill,
# so an output below twice this stays on the calling thread
DENSE_CHUNK_BYTES = 2 << 20
# the most threads, the calling one included, that densify one batch
DENSE_WORKERS = 8

# Reuse by reference count needs a count that only the GIL keeps exact.
_REFCOUNTS_SHOW_USE = sys.implementation.name == "cpython" and getattr(
    sys, "_is_gil_enabled", lambda: True
)()


class BufferPool:
    """Large host arrays, handed out again once nothing refers to them.

    A fresh page costs a fault on first touch, and glibc returns an array
    above its mmap threshold (32 MiB at most) to the OS as soon as it is
    freed, so a fetch that reads its extents and gathers its batch into
    fresh arrays pays those faults again every time: on the TPU v5e host
    (gVisor) filling 256 MiB takes ~290 ms fresh against ~124 ms warm.  The
    pool keeps such buffers, and the large dense batches
    :meth:`CSRBatch.to_dense` makes of a fetch's rows.  A buffer is free
    again when CPython's reference count shows that no array is a view of
    it (every view of a view refers to the buffer itself), so callers never
    give anything back.  That assumes CPython with the GIL, and that
    whatever reads a batch's memory holds a Python reference to it, as
    NumPy views and the buffer protocol do; on any other interpreter the
    pool hands out fresh arrays.

    Bounds: the free buffers never hold more bytes than were ever in use
    at once, and :meth:`trim`, which ends a window, cuts them to the most
    in use at once during that window or the one before; the least
    recently handed out go first.  The planner trims at the end of every
    fetch (:meth:`~repro.data.backend.StorageAdapter.end_fetch`), so a
    collection keeps the peak of its last two fetches, not the largest it
    ever made.
    """

    MIN_BYTES = 1 << 20  # below this malloc's heap serves arrays warm already

    def __init__(self):
        self._lock = threading.Lock()
        # a buffer only this list refers to: what _refcounts reads for a free one
        self._bufs: list[np.ndarray] = [np.empty(0, np.uint8)]  # guarded-by: _lock
        (self._idle,) = self._refcounts()
        self._bufs = []  # least recently handed out first
        self._peak = 0  # guarded-by: _lock — most bytes in use at once this window
        self._last_peak = 0  # guarded-by: _lock — the same, the window before
        self._max_peak = 0  # guarded-by: _lock — the same, ever

    def _refcounts(self) -> list[int]:
        return [sys.getrefcount(b) for b in self._bufs]  # unlocked-ok: callers hold _lock or own the pool

    @staticmethod
    def _bounded(bufs: list, free: list[bool], bound: int) -> list:
        """``bufs`` less the least recently handed out free buffers beyond
        ``bound`` bytes."""
        spare = sum(b.nbytes for b, f in zip(bufs, free) if f)
        keep = []
        for b, f in zip(bufs, free):
            if f and spare > bound:
                spare -= b.nbytes
            else:
                keep.append(b)
        return keep

    def empty(self, n: int, dtype) -> np.ndarray:
        """An uninitialised ``(n,)`` array of ``dtype``."""
        dtype = np.dtype(dtype)
        nbytes = int(n) * dtype.itemsize
        if nbytes < self.MIN_BYTES or not _REFCOUNTS_SHOW_USE:
            return np.empty(n, dtype)
        with self._lock:
            free = [r <= self._idle for r in self._refcounts()]
            fits = [(b.nbytes, i) for i, (b, f) in enumerate(zip(self._bufs, free))
                    if f and b.nbytes >= nbytes]
            if fits:
                i = min(fits)[1]
                buf = self._bufs.pop(i)
                free.pop(i)
            else:
                # capacity rounded up to an eighth of the next power of two,
                # so a slightly larger extent next time still fits
                step = 1 << max(0, (nbytes - 1).bit_length() - 3)
                buf = np.empty(-(-nbytes // step) * step, np.uint8)
            self._bufs.append(buf)
            free.append(False)
            in_use = sum(b.nbytes for b, f in zip(self._bufs, free) if not f)
            self._peak = max(self._peak, in_use)
            self._max_peak = max(self._max_peak, in_use)
            self._bufs = self._bounded(self._bufs, free, self._max_peak)
        return buf[:nbytes].view(dtype)

    def trim(self) -> None:
        """End a window: keep free buffers of at most the most bytes in use
        at once during it or the window before."""
        with self._lock:
            free = [r <= self._idle for r in self._refcounts()]
            in_use = sum(b.nbytes for b, f in zip(self._bufs, free) if not f)
            self._bufs = self._bounded(self._bufs, free, max(self._peak, self._last_peak))
            self._last_peak, self._peak = self._peak, in_use

    def copy(self, arr: np.ndarray) -> np.ndarray:
        """``np.array(arr)`` (a memmap slice read into RAM) in a pool buffer."""
        out = self.empty(len(arr), arr.dtype)
        np.copyto(out, arr)
        return out


@dataclasses.dataclass
class CSRBatch:
    """A materialized batch of sparse rows (local CSR) + aligned obs columns.

    Supports row indexing so it can flow through ScDataset's in-memory
    reshuffle/batching (Algorithm 1 lines 9–10) without densification;
    ``to_dense`` is the fetch_transform hot-spot.  Densify runs on the host
    today, a large batch's rows split over threads that each zero and fill
    their own; the on-chip path (``to_ell`` + repro.kernels.csr_to_dense) is
    checked on the TPU only by ``chip_smoke.py``.
    """

    data: np.ndarray  # (nnz,) float32
    indices: np.ndarray  # (nnz,) int32 gene ids
    indptr: np.ndarray  # (rows+1,) int64
    n_var: int
    obs: dict  # column -> (rows,) array
    # where ``to_dense`` takes its output from; the batch a fetch gathers,
    # and the rows taken from it, carry their adapter's pool
    pool: Optional["BufferPool"] = dataclasses.field(default=None, repr=False, compare=False)

    def __len__(self) -> int:
        return len(self.indptr) - 1

    def __getitem__(self, rows) -> "CSRBatch":
        rows = np.asarray(rows)
        if rows.dtype == bool:
            rows = np.flatnonzero(rows)
        starts = self.indptr[rows]
        ends = self.indptr[rows + 1]
        lens = ends - starts
        new_indptr = np.zeros(len(rows) + 1, dtype=np.int64)
        np.cumsum(lens, out=new_indptr[1:])
        gather = _ranges_concat(starts, lens)
        return CSRBatch(
            data=self.data[gather],
            indices=self.indices[gather],
            indptr=new_indptr,
            n_var=self.n_var,
            obs={k: v[rows] for k, v in self.obs.items()},
            pool=self.pool,
        )

    def to_dense(self) -> np.ndarray:
        """Dense (rows, n_var) float32.  Assumes canonical CSR (unique
        columns per row, as AnnData guarantees) — duplicate columns would
        overwrite, the last one winning, not accumulate; ``to_ell`` + the
        Pallas kernel accumulate.

        The rows are split into contiguous chunks of at least
        :data:`DENSE_CHUNK_BYTES` of output, one a thread, up to
        :data:`DENSE_WORKERS` and the CPUs this process may run on; each
        thread zeroes its chunk and scatters the chunk's nonzeros into it
        while it is still in cache.  A smaller output stays on the calling
        thread.  The span's ``workers`` says how many threads took part.

        An output of :data:`DENSE_POOL_BYTES` or more (256 rows of 62,710
        genes are 64 MB) is one of :attr:`pool`'s buffers, where the batch
        has a pool: glibc maps every array that large afresh, so a fresh
        output would pay a page fault per page on every call.  A smaller
        one comes back warm from malloc's heap."""
        n, n_var = len(self), self.n_var
        workers = _dense_workers(n, n * n_var * 4)
        with span("scdataset.to_dense", rows=n, workers=workers):
            if self.pool is None or n * n_var * 4 < DENSE_POOL_BYTES:
                out = np.empty((n, n_var), np.float32)
            else:
                out = self.pool.empty(n * n_var, np.float32).reshape(n, n_var)
            # the workers reach the output only through this list, which is
            # emptied before returning: a work item the executor has not yet
            # let go of must not keep the pool's buffer in use
            job = [out.reshape(-1), self.data, self.indices, self.indptr, n_var]
            bounds = [n * k // workers for k in range(workers + 1)]
            done = [_dense_executor().submit(_fill_rows, job, lo, hi)
                    for lo, hi in zip(bounds[1:-1], bounds[2:])]
            try:
                _fill_rows(job, bounds[0], bounds[1])
            finally:
                futures.wait(done)
                job.clear()
            for f in done:
                f.result()
            return out

    def to_ell(self, k_max: Optional[int] = None) -> tuple[np.ndarray, np.ndarray]:
        """Pad to ELL format (rows, K): (values, cols) with col=-1 padding.

        This is the TPU-friendly layout consumed by the csr_to_dense Pallas
        kernel.  ``k_max=None`` takes K from this batch's longest row; pass
        the store's :attr:`ShardedCSRStore.ell_width` instead so the device
        shape stays fixed across batches.  A ``k_max`` below the longest
        row raises: a narrower slab would drop nonzeros.
        """
        lens = np.diff(self.indptr).astype(np.int64)
        longest = int(lens.max()) if len(lens) else 0
        K = longest if k_max is None else int(k_max)
        if K < longest:
            raise ValueError(
                f"k_max={K} is below the batch's longest row ({longest} "
                "nonzeros): the ELL slab would drop data"
            )
        r = len(self)
        vals = np.zeros((r, K), dtype=np.float32)
        cols = np.full((r, K), -1, dtype=np.int32)
        row_ids = np.repeat(np.arange(r), lens)
        pos = _within_run_positions(lens)
        src = _ranges_concat(self.indptr[:-1], lens)
        vals[row_ids, pos] = self.data[src]
        cols[row_ids, pos] = self.indices[src]
        return vals, cols

    @property
    def nbytes(self) -> int:
        return int(self.data.nbytes + self.indices.nbytes + self.indptr.nbytes)


def _fill_rows(job: list, lo: int, hi: int) -> None:
    """Zero rows ``[lo, hi)`` of ``to_dense``'s flat output, then scatter
    their nonzeros into them by flat offset (``row * n_var + col``)."""
    out, data, indices, indptr, n_var = job
    part = out[lo * n_var:hi * n_var]
    part.fill(0)
    s, e = int(indptr[lo]), int(indptr[hi])
    cols = indices[s:e]
    if e > s and not (cols.min() >= 0 and cols.max() < n_var):
        raise IndexError(f"a column index of rows [{lo}, {hi}) is outside [0, {n_var})")
    offsets = cols.astype(np.int64)
    offsets += np.repeat(np.arange(0, (hi - lo) * n_var, n_var, dtype=np.int64),
                         np.diff(indptr[lo:hi + 1]))
    part[offsets] = data[s:e]


def _dense_workers(rows: int, nbytes: int) -> int:
    """Threads that densify an output of ``rows`` rows and ``nbytes``."""
    workers = min(DENSE_WORKERS, rows, nbytes // DENSE_CHUNK_BYTES)
    if workers > 1:
        workers = min(workers, len(os.sched_getaffinity(0)))
    return max(1, workers)


_dense_lock = threading.Lock()
_dense_pool: Optional[ThreadPoolExecutor] = None


def _dense_executor() -> ThreadPoolExecutor:
    """The one thread pool every ``to_dense`` shares, made on first use."""
    global _dense_pool
    with _dense_lock:
        if _dense_pool is None:
            _dense_pool = ThreadPoolExecutor(
                DENSE_WORKERS - 1, thread_name_prefix="scdataset-densify")
        return _dense_pool


def _forget_dense_executor() -> None:
    """In a forked child: the parent's threads are not there."""
    global _dense_lock, _dense_pool
    _dense_lock = threading.Lock()
    _dense_pool = None


os.register_at_fork(after_in_child=_forget_dense_executor)


def _ranges_concat(starts: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """Concatenate [s, s+len) ranges — vectorized (no per-row python loop)."""
    lens = lens.astype(np.int64)
    total = int(lens.sum())
    if total == 0:
        return np.empty(0, dtype=np.int64)
    # classic trick: cumulative offsets with resets at range boundaries
    out = np.ones(total, dtype=np.int64)
    ends = np.cumsum(lens)
    out[0] = starts[0]
    nz = lens > 0
    first_pos = np.concatenate(([0], ends[:-1]))[nz]
    starts_nz = starts[nz]
    prev_end = starts_nz[:-1] + lens[nz][:-1]
    out[first_pos[0]] = starts_nz[0]
    if len(starts_nz) > 1:
        out[first_pos[1:]] = starts_nz[1:] - prev_end + 1
    return np.cumsum(out)


def _ell_width(row_nnz: np.ndarray) -> int:
    """ELL K that fits every row: the largest row nonzero count, rounded up
    to a whole number of 128-wide TPU lanes.  One K per dataset keeps the
    device program's shapes fixed across batches (no recompiles)."""
    longest = int(row_nnz.max()) if len(row_nnz) else 0
    return max(1, -(-longest // 128)) * 128


def _within_run_positions(lens: np.ndarray) -> np.ndarray:
    """[0..l0), [0..l1), ... concatenated."""
    lens = lens.astype(np.int64)
    total = int(lens.sum())
    if total == 0:
        return np.empty(0, dtype=np.int64)
    ids = np.repeat(np.arange(len(lens)), lens)
    offsets = np.concatenate(([0], np.cumsum(lens)[:-1]))
    return np.arange(total) - offsets[ids]


class CSRStore:
    """One on-disk CSR shard: data.npy / indices.npy / indptr.npy / obs.npz / meta.json."""

    def __init__(self, path: str, iostats: Optional[IOStats] = None):
        self.path = path
        with open(os.path.join(path, "meta.json")) as f:
            self.meta = json.load(f)
        self.n_obs = int(self.meta["n_obs"])
        self.n_var = int(self.meta["n_var"])
        self._data = np.load(os.path.join(path, "data.npy"), mmap_mode="r")
        self._indices = np.load(os.path.join(path, "indices.npy"), mmap_mode="r")
        self._indptr = np.load(os.path.join(path, "indptr.npy"))  # small; in RAM
        obs_npz = np.load(os.path.join(path, "obs.npz"), allow_pickle=False)
        self._obs = {k: obs_npz[k] for k in obs_npz.files}
        self.iostats = iostats if iostats is not None else IOStats()
        self._row_bytes = (
            (self._data.nbytes + self._indices.nbytes) / max(1, self.n_obs)
        )

    def __len__(self) -> int:
        return self.n_obs

    @property
    def obs(self) -> dict:
        return self._obs

    @property
    def avg_row_bytes(self) -> float:
        return self._row_bytes

    @property
    def ell_width(self) -> int:
        """Dataset-level ELL K (see :func:`_ell_width`)."""
        return _ell_width(np.diff(self._indptr))

    def read_range(self, start: int, stop: int, pool: BufferPool) -> CSRBatch:
        """Raw contiguous read of local rows ``[start, stop)`` — ONE extent,
        into ``pool``'s buffers.

        No IOStats recording: this is the physical-read primitive the shared
        read planner (:mod:`repro.data.readplan`) executes; the planner does
        the accounting so runs/bytes are counted once per fetch, uniformly
        across backends.
        """
        lo, hi = int(self._indptr[start]), int(self._indptr[stop])
        # a copy, not a view: a memmap slice is a no-copy view, and the
        # planner CACHES what we return — a cached view would still fault
        # pages from disk on "hits" and occupy no budgetable RAM.
        return CSRBatch(
            data=pool.copy(self._data[lo:hi]),
            indices=pool.copy(self._indices[lo:hi]),
            indptr=np.asarray(self._indptr[start : stop + 1], dtype=np.int64) - lo,
            n_var=self.n_var,
            obs={k: v[start:stop] for k, v in self._obs.items()},
        )

    def __getitem__(self, rows) -> CSRBatch:
        """Run-coalesced batched read (Algorithm 1 line 8).

        One memmap slice copy per contiguous run; IOStats.runs counts them.
        Rows may be unsorted or contain duplicates (weighted sampling); data
        is returned in the order given.
        """
        t0 = time.perf_counter()
        rows = np.asarray(rows, dtype=np.int64)
        if rows.ndim == 0:
            rows = rows[None]
        order = np.argsort(rows, kind="stable")
        srows = rows[order]
        uniq = np.unique(srows)
        runs = coalesce_rows(uniq)

        # Read each run once (the only disk I/O), concatenating into one buffer.
        run_data, run_idx = [], []
        run_buf_off = np.zeros(len(runs), dtype=np.int64)  # run -> offset in buf
        run_lo = np.zeros(len(runs), dtype=np.int64)  # run -> indptr offset of run start
        bytes_read = 0
        cum = 0
        for k, (a, b) in enumerate(runs):
            lo, hi = int(self._indptr[a]), int(self._indptr[b])
            d = np.asarray(self._data[lo:hi])
            i = np.asarray(self._indices[lo:hi])
            bytes_read += d.nbytes + i.nbytes
            run_data.append(d)
            run_idx.append(i)
            run_buf_off[k] = cum
            run_lo[k] = lo
            cum += hi - lo
        buf_data = np.concatenate(run_data) if run_data else np.empty(0, self._data.dtype)
        buf_idx = np.concatenate(run_idx) if run_idx else np.empty(0, self._indices.dtype)

        # Vectorized assembly (handles duplicates & arbitrary original order):
        # each requested row maps to a source span inside the run buffer.
        lens_all = np.diff(self._indptr)
        out_lens = lens_all[rows].astype(np.int64)
        out_indptr = np.zeros(len(rows) + 1, dtype=np.int64)
        np.cumsum(out_lens, out=out_indptr[1:])
        run_stops_arr = runs[:, 1]  # coalesce_rows returns (n, 2) spans
        which_run = np.searchsorted(run_stops_arr, rows, side="right")
        src_starts = run_buf_off[which_run] + (self._indptr[rows] - run_lo[which_run])
        gather = _ranges_concat(src_starts, out_lens)
        data = buf_data[gather]
        indices = buf_idx[gather]

        obs = {k: v[rows] for k, v in self._obs.items()}
        self.iostats.record(
            runs=len(runs), rows=len(rows), bytes_read=bytes_read,
            wall_s=time.perf_counter() - t0,
        )
        return CSRBatch(data=data, indices=indices, indptr=out_indptr,
                        n_var=self.n_var, obs=obs)


class ShardedCSRStore:
    """Lazy concatenation of CSR shards (the 14 Tahoe plate files).

    Global row ids map to (shard, local row); a batched read dispatches each
    shard's rows in one call, preserving the caller's row order on return.
    """

    def __init__(self, shard_paths: Sequence[str], iostats: Optional[IOStats] = None):
        if not shard_paths:
            raise ValueError("need at least one shard")
        self.iostats = iostats if iostats is not None else IOStats()
        self.shards = [CSRStore(p, iostats=self.iostats) for p in shard_paths]
        n_vars = {s.n_var for s in self.shards}
        if len(n_vars) != 1:
            raise ValueError(f"shards disagree on n_var: {n_vars}")
        self.n_var = n_vars.pop()
        sizes = np.array([len(s) for s in self.shards], dtype=np.int64)
        self.offsets = np.concatenate(([0], np.cumsum(sizes)))
        self.n_obs = int(self.offsets[-1])

    def __len__(self) -> int:
        return self.n_obs

    @property
    def avg_row_bytes(self) -> float:
        return float(np.mean([s.avg_row_bytes for s in self.shards]))

    @property
    def ell_width(self) -> int:
        """Dataset-level ELL K over every shard (see :func:`_ell_width`)."""
        return max(s.ell_width for s in self.shards)

    @property
    def obs_keys(self) -> list[str]:
        return list(self.shards[0].obs.keys())

    def obs_column(self, key: str) -> np.ndarray:
        """Materialize a full metadata column across shards (small)."""
        return np.concatenate([s.obs[key] for s in self.shards])

    def __getitem__(self, rows) -> CSRBatch:
        rows = np.asarray(rows, dtype=np.int64)
        if rows.ndim == 0:
            rows = rows[None]
        shard_ids = np.searchsorted(self.offsets, rows, side="right") - 1
        batches: list[Optional[CSRBatch]] = [None] * len(self.shards)
        back_perm = np.empty(len(rows), dtype=np.int64)
        cursor = 0
        for sid in np.unique(shard_ids):
            mask = shard_ids == sid
            local = rows[mask] - self.offsets[sid]
            batches[sid] = self.shards[sid][local]
            back_perm[np.flatnonzero(mask)] = np.arange(cursor, cursor + mask.sum())
            cursor += int(mask.sum())
        got = [b for b in batches if b is not None]
        merged = _concat_batches(got, self.n_var)
        # restore original order
        return merged[back_perm]


def gather_rows(
    sources: Sequence[tuple[CSRBatch, np.ndarray]], n_var: int, pool: BufferPool
) -> CSRBatch:
    """``_concat_batches([piece[rows] for piece, rows in sources])``, in one copy.

    Each run of consecutive rows of a source (``r, r+1, ...`` in that
    order) is one slice of its ``data`` and ``indices``; the slices are
    copied once, by ``np.concatenate``, into arrays allocated once from
    ``pool``, and ``indptr`` comes from the rows' lengths.  Rows may repeat and come in any order: a repeat or a step
    back starts a new run.
    """
    first = sources[0][0]
    data, indices = [first.data[:0]], [first.indices[:0]]
    lens = [np.empty(0, dtype=np.int64)]
    obs = {k: [v[:0]] for k, v in first.obs.items()}
    for piece, rows in sources:
        rows = np.asarray(rows, dtype=np.int64)
        if len(rows) == 0:
            continue
        ip = piece.indptr
        lens.append(ip[rows + 1] - ip[rows])
        cut = np.flatnonzero(np.diff(rows) != 1) + 1
        run_lo = ip[rows[np.concatenate(([0], cut))]].tolist()
        run_hi = ip[rows[np.concatenate((cut, [len(rows)])) - 1] + 1].tolist()
        data += [piece.data[a:z] for a, z in zip(run_lo, run_hi)]
        indices += [piece.indices[a:z] for a, z in zip(run_lo, run_hi)]
        for k, parts in obs.items():
            parts.append(piece.obs[k][rows])
    lens_all = np.concatenate(lens)
    indptr = np.zeros(len(lens_all) + 1, dtype=np.int64)
    np.cumsum(lens_all, out=indptr[1:])

    def join(parts):
        dtype = np.result_type(*(p.dtype for p in parts))
        return np.concatenate(parts, out=pool.empty(int(indptr[-1]), dtype))

    return CSRBatch(
        data=join(data),
        indices=join(indices),
        indptr=indptr,
        n_var=n_var,
        obs={k: np.concatenate(parts) for k, parts in obs.items()},
        pool=pool,
    )


def gathered_nbytes(ranges: Sequence[tuple[CSRBatch, int, int]]) -> int:
    """``gather_rows(...).nbytes`` for rows ``[lo, hi)`` of each ``(piece,
    lo, hi)``, read off the pieces' ``indptr`` without copying."""
    nnz = sum(int(p.indptr[hi] - p.indptr[lo]) for p, lo, hi in ranges)
    rows = sum(hi - lo for _, lo, hi in ranges)
    first = ranges[0][0]
    return nnz * (first.data.itemsize + first.indices.itemsize) + (
        rows + 1
    ) * np.dtype(np.int64).itemsize


def _concat_batches(batches: Sequence[CSRBatch], n_var: int) -> CSRBatch:
    if len(batches) == 1:
        return batches[0]
    data = np.concatenate([b.data for b in batches])
    indices = np.concatenate([b.indices for b in batches])
    lens = np.concatenate([np.diff(b.indptr) for b in batches])
    indptr = np.zeros(len(lens) + 1, dtype=np.int64)
    np.cumsum(lens, out=indptr[1:])
    keys = batches[0].obs.keys()
    obs = {k: np.concatenate([b.obs[k] for b in batches]) for k in keys}
    return CSRBatch(data=data, indices=indices, indptr=indptr, n_var=n_var, obs=obs)


def write_csr_shard(
    path: str,
    data: np.ndarray,
    indices: np.ndarray,
    indptr: np.ndarray,
    n_var: int,
    obs: dict,
    extra_meta: Optional[dict] = None,
) -> None:
    """Write one shard to disk (atomically enough for tests: tmp dir + rename)."""
    tmp = path + ".tmp"
    os.makedirs(tmp, exist_ok=True)
    np.save(os.path.join(tmp, "data.npy"), np.asarray(data, dtype=np.float32))
    np.save(os.path.join(tmp, "indices.npy"), np.asarray(indices, dtype=np.int32))
    np.save(os.path.join(tmp, "indptr.npy"), np.asarray(indptr, dtype=np.int64))
    np.savez(os.path.join(tmp, "obs.npz"), **{k: np.asarray(v) for k, v in obs.items()})
    meta = {"n_obs": int(len(indptr) - 1), "n_var": int(n_var)}
    meta.update(extra_meta or {})
    with open(os.path.join(tmp, "meta.json"), "w") as f:
        json.dump(meta, f)
    if os.path.exists(path):
        import shutil

        shutil.rmtree(path)
    os.rename(tmp, path)
