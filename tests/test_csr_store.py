"""On-disk CSR store: correctness vs dense reference, run counting, sharding."""
import numpy as np
import pytest

from repro.data import CSRStore, ShardedCSRStore, write_csr_shard
from repro.data.csr_store import _ranges_concat, _within_run_positions


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
