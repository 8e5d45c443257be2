"""On-disk CSR store: correctness vs dense reference, run counting, sharding."""
import sys
import threading

import numpy as np
import pytest

from repro.data import CSRBatch, CSRStore, ShardedCSRStore, write_csr_shard
from repro.data import csr_store
from repro.data.csr_store import BufferPool, _ranges_concat, _within_run_positions

TAHOE_GENES = 62_710


def _random_csr(rng, n, g, max_nnz=12):
    """Canonical CSR: unique sorted column indices per row (AnnData semantics)."""
    lens = rng.integers(0, max_nnz, n)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(lens, out=indptr[1:])
    total = int(indptr[-1])
    data = rng.normal(0, 1, total).astype(np.float32)
    indices = np.empty(total, np.int32)
    for i in range(n):
        k = int(lens[i])
        indices[indptr[i]:indptr[i + 1]] = np.sort(
            rng.choice(g, size=k, replace=False)).astype(np.int32)
    dense = np.zeros((n, g), np.float32)
    for i in range(n):
        for j in range(indptr[i], indptr[i + 1]):
            dense[i, indices[j]] += data[j]
    return data, indices, indptr, dense


@pytest.fixture(scope="module")
def shard(tmp_path_factory):
    rng = np.random.default_rng(0)
    n, g = 500, 64
    data, indices, indptr, dense = _random_csr(rng, n, g)
    path = str(tmp_path_factory.mktemp("csr") / "s0")
    obs = {"plate": np.full(n, 7, np.int32), "row": np.arange(n, dtype=np.int32)}
    write_csr_shard(path, data, indices, indptr, g, obs)
    return CSRStore(path), dense


def test_single_rows_match_dense(shard):
    store, dense = shard
    rng = np.random.default_rng(1)
    rows = rng.integers(0, len(store), 50)
    got = store[rows].to_dense()
    assert np.allclose(got, dense[rows])


def test_duplicates_and_order_preserved(shard):
    store, dense = shard
    rows = np.array([5, 3, 5, 499, 0, 3])
    got = store[rows]
    assert np.allclose(got.to_dense(), dense[rows])
    assert np.array_equal(got.obs["row"], rows)


def _wide_batch(rng, n, g, max_nnz=300, empty=(), duplicates=False, pool=None):
    """A batch of ``n`` rows of ``g`` columns, the rows in ``empty`` without
    nonzeros; with ``duplicates`` a row may name a column more than once."""
    lens = rng.integers(1, max_nnz, n)
    lens[list(empty)] = 0
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(lens, out=indptr[1:])
    indices = np.concatenate(
        [np.empty(0, np.int64)]
        + [rng.choice(g, int(k), replace=duplicates) for k in lens]).astype(np.int32)
    data = rng.normal(0, 1, int(indptr[-1])).astype(np.float32)
    return CSRBatch(data, indices, indptr, g, {}, pool=pool)


def _dense_row_by_row(b):
    """The dense batch, one nonzero at a time: the last of a column wins."""
    out = np.zeros((len(b), b.n_var), np.float32)
    for i in range(len(b)):
        for j in range(b.indptr[i], b.indptr[i + 1]):
            out[i, b.indices[j]] = b.data[j]
    return out


@pytest.mark.parametrize("rows, g, chunk_bytes, empty, duplicates, pooled, workers", [
    pytest.param(1, TAHOE_GENES, None, (), False, False, 1, id="1-row"),
    pytest.param(3, 32, None, (), False, False, 1, id="3-rows"),
    pytest.param(3, TAHOE_GENES, 256 << 10, (), False, False, 2, id="3-rows-in-2-chunks"),
    pytest.param(64, TAHOE_GENES, None, (), False, False, 7, id="64-rows"),
    pytest.param(257, TAHOE_GENES, None, (), False, False, 8, id="257-rows"),
    pytest.param(257, TAHOE_GENES, None, (), False, True, 8, id="257-rows-pooled"),
    pytest.param(40, TAHOE_GENES, None, (0, 39, *range(10, 20)), False, False, 4,
                 id="empty-rows"),
    pytest.param(16, TAHOE_GENES, None, range(16), False, False, 1, id="all-rows-empty"),
    pytest.param(0, TAHOE_GENES, None, (), False, False, 1, id="no-rows"),
    pytest.param(12, 50, 200, (), True, False, 8, id="duplicate-columns"),
])
def test_to_dense_equals_a_row_by_row_reference(monkeypatch, rows, g, chunk_bytes, empty,
                                                duplicates, pooled, workers):
    """``to_dense`` on one thread or on several, from the heap or from a
    pool, is bit for bit the batch built one nonzero at a time."""
    monkeypatch.setattr(csr_store.os, "sched_getaffinity", lambda pid: set(range(8)))
    monkeypatch.setattr(csr_store, "DENSE_WORKERS", 8)
    if chunk_bytes is not None:
        monkeypatch.setattr(csr_store, "DENSE_CHUNK_BYTES", chunk_bytes)
    rng = np.random.default_rng(rows)
    b = _wide_batch(rng, rows, g, max_nnz=min(300, g), empty=empty,
                    duplicates=duplicates, pool=BufferPool() if pooled else None)
    assert csr_store._dense_workers(rows, rows * g * 4) == workers
    got = b.to_dense()
    assert got.dtype == np.float32 and got.shape == (rows, g)
    assert (got.base is not None) == pooled
    if pooled:
        assert got.nbytes >= csr_store.DENSE_POOL_BYTES
    np.testing.assert_array_equal(got.view(np.uint32), _dense_row_by_row(b).view(np.uint32))


@pytest.mark.parametrize("chunk_bytes", [None, 64], ids=["one-thread", "four-threads"])
@pytest.mark.parametrize("col", [-1, 50])
def test_to_dense_refuses_a_column_outside_the_batch(monkeypatch, chunk_bytes, col):
    """A flat offset would put such a column into a neighbouring row."""
    monkeypatch.setattr(csr_store.os, "sched_getaffinity", lambda pid: set(range(4)))
    if chunk_bytes is not None:
        monkeypatch.setattr(csr_store, "DENSE_CHUNK_BYTES", chunk_bytes)
    b = _wide_batch(np.random.default_rng(4), 8, 50, max_nnz=20)
    b.indices[b.indptr[4]] = col  # the first nonzero of the third chunk's first row
    with pytest.raises(IndexError, match="outside"):
        b.to_dense()


def test_threaded_to_dense_hands_its_pool_buffer_back_every_time(monkeypatch):
    """No worker keeps a view of a pooled output: each call, once the last
    result is gone, gets the same buffer back, zeroed afresh by the workers."""
    monkeypatch.setattr(csr_store.os, "sched_getaffinity", lambda pid: set(range(4)))
    rows = -(-csr_store.DENSE_POOL_BYTES // (TAHOE_GENES * 4))
    b = _wide_batch(np.random.default_rng(5), rows, TAHOE_GENES, pool=BufferPool())
    assert csr_store._dense_workers(rows, rows * TAHOE_GENES * 4) == 4
    want = _dense_row_by_row(b)
    x = b.to_dense()
    base = id(x.base)  # an id: a reference would keep the buffer in use
    for _ in range(20):
        np.testing.assert_array_equal(x, want)
        x.fill(np.nan)
        del x
        x = b.to_dense()
        assert id(x.base) == base


def test_to_dense_from_many_threads_at_once(monkeypatch):
    """Threads that densify pooled batches at once, more of them than the
    CPUs, share the one executor and the pool: every result is its own
    batch's, and stays so while the thread's next call runs."""
    monkeypatch.setattr(csr_store.os, "sched_getaffinity", lambda pid: set(range(4)))
    monkeypatch.setattr(csr_store, "DENSE_POOL_BYTES", 4 << 20)
    pool = BufferPool()
    batches = [_wide_batch(np.random.default_rng(100 + t), 24, TAHOE_GENES, pool=pool)
               for t in range(12)]
    wants = [_dense_row_by_row(b) for b in batches]
    wrong = []

    def densify(t):
        last = None
        for _ in range(6):
            x = batches[t].to_dense()
            if last is not None and not np.array_equal(last, wants[t]):
                wrong.append(t)
            if not np.array_equal(x, wants[t]):
                wrong.append(t)
            last = x

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=densify, args=(t,)) for t in range(12)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads)
    assert wrong == []


def test_run_counting(shard):
    store, _ = shard
    store.iostats.reset()
    store[np.arange(100, 200)]
    assert store.iostats.runs == 1
    store.iostats.reset()
    store[np.array([0, 2, 4, 6])]
    assert store.iostats.runs == 4
    store.iostats.reset()
    store[np.array([10, 11, 12, 50, 51, 400])]
    assert store.iostats.runs == 3


def test_batch_row_indexing(shard):
    store, dense = shard
    b = store[np.arange(40)]
    sub = b[[3, 1, 3]]
    assert np.allclose(sub.to_dense(), dense[[3, 1, 3]])


def test_ell_roundtrip(shard):
    store, dense = shard
    rows = np.arange(64)
    b = store[rows]
    vals, cols = b.to_ell()
    R, K = vals.shape
    out = np.zeros((R, store.n_var), np.float32)
    for r in range(R):
        for k in range(K):
            if cols[r, k] >= 0:
                out[r, cols[r, k]] += vals[r, k]
    assert np.allclose(out, dense[rows])


def test_to_ell_rejects_k_below_longest_row(shard):
    """A narrow k_max used to clip rows and drop nonzeros without a word."""
    store, dense = shard
    b = store[np.arange(64)]
    longest = int(np.diff(b.indptr).max())
    with pytest.raises(ValueError, match="drop data"):
        b.to_ell(k_max=longest - 1)
    vals, cols = b.to_ell(k_max=longest + 5)  # wider than needed: padding only
    assert vals.shape == (64, longest + 5)
    assert (cols[:, longest:] == -1).all()


def test_ell_width_is_dataset_level_and_lane_aligned(tmp_path):
    """One K for the whole store: every batch's ELL has the same shape."""
    rng = np.random.default_rng(3)
    paths, longest = [], 0
    for s, max_nnz in enumerate((12, 140)):
        data, indices, indptr, _ = _random_csr(rng, 50, 300, max_nnz=max_nnz)
        longest = max(longest, int(np.diff(indptr).max()))
        p = str(tmp_path / f"s{s}")
        write_csr_shard(p, data, indices, indptr, 300, {"plate": np.full(50, s)})
        paths.append(p)
    store = ShardedCSRStore(paths)
    assert store.ell_width == -(-longest // 128) * 128 == 256
    assert store.shards[0].ell_width == 128
    shapes = {store[rows].to_ell(k_max=store.ell_width)[0].shape
              for rows in (np.arange(8), np.arange(50, 58), np.arange(92, 100))}
    assert shapes == {(8, 256)}


def test_sharded_concat(tmp_path):
    rng = np.random.default_rng(2)
    denses, paths = [], []
    for s in range(3):
        n = 100 + 30 * s
        data, indices, indptr, dense = _random_csr(rng, n, 32)
        p = str(tmp_path / f"s{s}")
        write_csr_shard(p, data, indices, indptr, 32,
                        {"plate": np.full(n, s, np.int32)})
        denses.append(dense)
        paths.append(p)
    store = ShardedCSRStore(paths)
    full = np.concatenate(denses)
    assert len(store) == full.shape[0]
    rows = np.array([0, 99, 100, 229, 230, 359, 5, 130])  # cross-shard, unordered
    got = store[rows]
    assert np.allclose(got.to_dense(), full[rows])
    expect_plate = np.array([0, 0, 1, 1, 2, 2, 0, 1])
    assert np.array_equal(got.obs["plate"], expect_plate)


def test_ranges_concat_vectorized():
    rng = np.random.default_rng(3)
    for _ in range(20):
        k = rng.integers(1, 10)
        starts = rng.integers(0, 1000, k).astype(np.int64)
        lens = rng.integers(0, 6, k).astype(np.int64)
        if lens.sum() == 0:
            continue
        expect = np.concatenate([np.arange(s, s + l) for s, l in zip(starts, lens)])
        got = _ranges_concat(starts, lens)
        assert np.array_equal(got, expect), (starts, lens)
        pos = _within_run_positions(lens)
        expect_pos = np.concatenate([np.arange(l) for l in lens])
        assert np.array_equal(pos, expect_pos)
