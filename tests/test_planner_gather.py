"""The planner's one-copy gather and its cache-aware cut.

A fetch's rows go from the read extents (and the cached blocks it hits)
into the batch in one ``StorageAdapter.gather``; a missed block is cut out
of its extent only for a rendezvous claimant or for the cache, when the
cache still holds it at the fetch's end.  Checked here: the CSR gather
against ``take`` + ``_concat_batches`` array for array, fetches through the
planner against an independent read of the same rows, the cache after
every fetch against a plain LRU fed every missed block as a whole, and
``blocks_cut`` against the blocks actually cut.
"""
import json
import os

import numpy as np
import pytest

from repro.core import BlockShuffling, ScDataset
from repro.data import (
    generate_token_corpus,
    open_collection,
    write_chunked_store,
    write_csr_shard,
    write_h5ad,
)
from repro.data.backend import PlannedCollection, StorageAdapter, open_adapter
from repro.data import csr_store
from repro.data.csr_store import BufferPool, ShardedCSRStore
from repro.data.h5ad import _HAVE_H5PY
from repro.data.readplan import BlockCache, SegmentedBlockCache

SIZES = (700, 333, 901)  # shard edges at 700 and 1033: not block aligned
N_VAR = 64
B = 32  # cache block rows in the planner tests
DRIVERS = ("shim", "h5py") if _HAVE_H5PY else ("shim",)


def _random_csr(rng, n):
    lens = rng.integers(0, 12, n)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(lens, out=indptr[1:])
    data = rng.normal(size=int(indptr[-1])).astype(np.float32)
    indices = np.concatenate(
        [np.sort(rng.choice(N_VAR, size=int(k), replace=False)) for k in lens]
    ).astype(np.int32)
    return data, indices, indptr


@pytest.fixture(scope="module")
def atlas(tmp_path_factory):
    """The same three plates as ``sharded-csr://`` shards and as
    ``sharded-h5ad://`` files; returns ``{name: uri}`` and the shard paths."""
    rng = np.random.default_rng(7)
    root = tmp_path_factory.mktemp("gather_atlas")
    csr, h5 = root / "csr", root / "h5ad"
    os.makedirs(h5)
    shards, files = [], []
    for i, n in enumerate(SIZES):
        data, indices, indptr = _random_csr(rng, n)
        obs = {"plate": np.full(n, i, np.int32),
               "score": rng.normal(size=n).astype(np.float64)}
        shards.append(str(csr / f"plate_{i}"))
        write_csr_shard(shards[-1], data, indices, indptr, N_VAR, obs)
        files.append(f"plate_{i}.h5ad")
        write_h5ad(str(h5 / files[-1]), data, indices, indptr, N_VAR, obs)
    with open(csr / "manifest.json", "w") as f:
        json.dump({"shards": [os.path.basename(s) for s in shards]}, f)
    with open(h5 / "manifest.json", "w") as f:
        json.dump({"shards": files}, f)
    uris = {"sharded-csr": f"sharded-csr://{csr}"}
    for d in DRIVERS:
        uris[f"sharded-h5ad-{d}"] = f"sharded-h5ad://{h5}?driver={d}"
    return uris, shards


@pytest.fixture(scope="module")
def store(atlas):
    """An independent reader of the same rows: the shards' own run-coalesced
    ``__getitem__``, which never goes through the planner."""
    return ShardedCSRStore(atlas[1])


def _assert_same(a, b):
    for name in ("data", "indices", "indptr"):
        x, y = getattr(a, name), getattr(b, name)
        assert x.dtype == y.dtype, name
        np.testing.assert_array_equal(x, y, err_msg=name)
    assert a.n_var == b.n_var
    assert list(a.obs) == list(b.obs)
    for k in a.obs:
        assert a.obs[k].dtype == b.obs[k].dtype, k
        np.testing.assert_array_equal(a.obs[k], b.obs[k], err_msg=k)


def _default_gather(adapter):
    """The adapter with the contract's default ``gather``: ``take`` of each
    source, then ``concat``."""
    adapter.gather = lambda sources: StorageAdapter.gather(adapter, sources)
    return adapter


# ------------------------------------------------------------ the gather
GATHER_CASES = {
    "sorted": [(0, [3, 4, 5, 9, 10, 30]), (1, [0, 1, 2, 200, 201])],
    "unsorted": [(0, [10, 3, 4, 5, 2]), (2, [7, 6, 5, 0, 1])],
    "duplicates": [(0, [4, 4, 5, 5, 6, 4]), (1, [9, 9, 9])],
    "single_row": [(1, [17])],
    "one_run_per_piece": [(0, list(range(300))), (1, list(range(400))),
                          (2, list(range(333)))],
    "empty_source": [(0, []), (1, [3, 2, 2])],
}


@pytest.mark.parametrize("case", sorted(GATHER_CASES))
@pytest.mark.parametrize("name", ["sharded-csr", *(f"sharded-h5ad-{d}" for d in DRIVERS)])
def test_gather_matches_take_and_concat(atlas, name, case):
    adapter = open_adapter(atlas[0][name])
    # three extents: part of plate 0, the rest of it, and all of plate 1
    pieces = [adapter.read_range(0, 300), adapter.read_range(300, 700),
              adapter.read_range(700, 1033)]
    sources = [(pieces[k], np.asarray(rows, dtype=np.int64))
               for k, rows in GATHER_CASES[case]]
    _assert_same(adapter.gather(sources), StorageAdapter.gather(adapter, sources))


# ------------------------------------------------------- fetches through it
FETCH_CASES = {
    "straddles_a_block": np.arange(20, 50),
    "straddles_an_extent_split": np.arange(40, 75),
    "straddles_a_shard_edge": np.arange(690, 712),
    "sorted_runs": np.concatenate([np.arange(a, a + 16) for a in (0, 64, 700, 1020, 1900)]),
    "unsorted": np.array([1500, 3, 4, 5, 699, 700, 1032, 1033, 41]),
    "duplicates": np.array([5, 5, 6, 700, 700, 699, 5, 1932]),
    "single_row": np.array([1033]),
}


@pytest.mark.parametrize("case", sorted(FETCH_CASES))
@pytest.mark.parametrize("name", ["sharded-csr", *(f"sharded-h5ad-{d}" for d in DRIVERS)])
def test_fetch_matches_an_independent_read(atlas, store, name, case):
    """Runs across a cache block, a ``max_extent_rows`` split (every 48
    rows, so splits fall inside blocks too) and a shard edge come out as the
    shards' own reader gives them, and as ``take`` + ``concat`` assemble
    them, on a cold cache and again, mixed with hits, on a warm one."""
    rows = FETCH_CASES[case]
    kw = dict(block_rows=B, max_extent_rows=48)
    coll = open_collection(atlas[0][name], **kw)
    ref = PlannedCollection(_default_gather(open_adapter(atlas[0][name])), **kw)
    want = store[rows]
    _assert_same(coll.fetch(rows), want)
    _assert_same(ref.fetch(rows), want)
    # warm: half the rows' blocks are now hits, the rest misses
    mixed = np.concatenate([rows, rows + 37]) % len(store)
    got = coll.fetch(mixed)
    assert 0 < coll.iostats.cache_hits < coll.iostats.cache_hits + coll.iostats.cache_misses
    _assert_same(got, store[mixed])
    _assert_same(got, ref.fetch(mixed))


def test_non_csr_adapter_keeps_take_and_concat(tmp_path):
    """``chunked://`` has no gather of its own: the default still delivers
    exactly ``store[rows]``, cold and warm."""
    rng = np.random.default_rng(3)
    X = rng.normal(size=(1000, 12)).astype(np.float32)
    path = write_chunked_store(str(tmp_path / "chunks"), X, chunk_rows=100)
    coll = open_collection(f"chunked://{path}", block_rows=B, cache_bytes=6 * B * 12 * 4)
    assert type(coll.adapter).gather is StorageAdapter.gather
    for rows in (np.arange(90, 130), np.array([999, 5, 5, 250, 101, 100]),
                 np.arange(80, 140)):
        np.testing.assert_array_equal(coll.fetch(rows), X[rows])


# ------------------------------------------------------- the cache-aware cut
def _state(cache):
    # the LRU order has no public accessor: the entries' keys, oldest first
    return (list(cache._entries), cache.cur_bytes, cache.insertions,
            cache.evictions, cache.hits, cache.misses, cache.bypasses)


def _fetches(n_rows, seed):
    """Block-sampled fetches of 200 rows in runs of 8, then one that repeats
    part of the first (hits beside misses)."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(5):
        starts = rng.choice(n_rows // 8, size=25, replace=False) * 8
        out.append(np.sort((starts[:, None] + np.arange(8)).ravel()))
    out.append(np.sort(np.concatenate([out[0][:80], out[3][-40:]])))
    return out


def _block_bytes(store):
    n = len(store)
    return [store[np.arange(b * B, min((b + 1) * B, n))].nbytes
            for b in range(-(-n // B))]


@pytest.fixture
def cut_spy(monkeypatch):
    calls = []
    cut = PlannedCollection._cut

    def spy(self, bb, spans, pieces):
        calls.append(bb)
        return cut(self, bb, spans, pieces)

    monkeypatch.setattr(PlannedCollection, "_cut", spy)
    return calls


@pytest.mark.parametrize("max_extent_rows", [32768, 48])
@pytest.mark.parametrize("budget", ["zero", "one_block", "ten_blocks", "all"])
@pytest.mark.parametrize("name", ["sharded-csr", f"sharded-h5ad-{DRIVERS[-1]}"])
def test_cache_matches_a_plain_lru_after_every_fetch(atlas, store, cut_spy, name,
                                                     budget, max_extent_rows):
    sizes = _block_bytes(store)
    max_bytes = {"zero": 0, "one_block": max(sizes),
                 "ten_blocks": 10 * int(np.mean(sizes)), "all": 2 * sum(sizes)}[budget]
    coll = open_collection(atlas[0][name], cache_bytes=max_bytes, block_rows=B,
                           max_extent_rows=max_extent_rows)
    ref = BlockCache(max_bytes)
    n = len(store)
    for rows in _fetches(n, seed=11):
        cut0, stats0 = len(cut_spy), coll.iostats.snapshot()
        got = coll.fetch(rows)
        # the reference: look every block up, then cut each missed block
        # whole and put it, in block order
        missed = [b for b in np.unique(rows // B).tolist() if ref.get(b) is None]
        for b in missed:
            val = store[np.arange(b * B, min((b + 1) * B, n))]
            ref.put(b, val, val.nbytes)
        assert _state(coll.cache) == _state(ref)
        # and the same reads: the missed blocks' extents, whole
        spans = coll._spans_for_blocks(np.asarray(missed, dtype=np.int64))
        read = coll.iostats.snapshot()
        assert read["runs"] - stats0["runs"] == len(spans)
        assert read["bytes_read"] - stats0["bytes_read"] == sum(
            store[np.arange(lo, hi)].nbytes for lo, hi in spans.tolist())
        _assert_same(got, store[rows])
        for b, (val, nbytes) in coll.cache._entries.items():
            _assert_same(val, store[np.arange(b * B, min((b + 1) * B, n))])
            assert nbytes == val.nbytes
        stats1 = coll.iostats.snapshot()
        cut = stats1["blocks_cut"] - stats0["blocks_cut"]
        misses = stats1["cache_misses"] - stats0["cache_misses"]
        assert cut == len(cut_spy) - cut0
        assert len(set(cut_spy[cut0:])) == cut
        if budget == "zero":
            assert cut == 0
        elif budget == "all":
            assert cut == misses
        elif budget == "ten_blocks" and misses > 10:
            assert 0 < cut <= 10 < misses


def test_every_block_is_cut_where_admission_decides(atlas, cut_spy):
    """TinyLFU admission and the segmented cache learn their outcome only by
    trying: every missed block is cut, as before."""
    uri = atlas[0]["sharded-csr"]
    for kw in ({"admission": "auto"}, {"cache_policy": "wtinylfu"}):
        coll = open_collection(uri, cache_bytes=1 << 14, block_rows=B, **kw)
        for rows in _fetches(len(coll), seed=5):
            coll.fetch(rows)
        snap = coll.iostats.snapshot()
        assert snap["blocks_cut"] == snap["cache_misses"] > 0


def test_bypassing_admission_cuts_nothing(atlas, cut_spy):
    coll = open_collection(atlas[0]["sharded-csr"], block_rows=B, admission="never")
    for rows in _fetches(len(coll), seed=5):
        coll.fetch(rows)
    snap = coll.iostats.snapshot()
    assert snap["cache_misses"] > 0 and snap["blocks_cut"] == 0 == len(cut_spy)
    assert coll.cache.bypasses == snap["cache_misses"] and len(coll.cache) == 0


def test_survivors_are_the_newest_values_that_fit():
    cache = BlockCache(100)
    assert cache.survivors([30, 30, 30, 30]) == [False, True, True, True]
    assert cache.survivors([60, 500, 40]) == [True, False, True]  # 500: never cached
    assert cache.survivors([10, 95, 10]) == [False, False, True]
    assert BlockCache(0).survivors([1, 0]) == [False, False]
    with pytest.raises(ValueError):
        cache.put_many([(1, None, 40)])  # the newest value always survives
    cache.put_many([(1, None, 60), (2, "b", 60), (3, "c", 40)])
    assert list(cache._entries) == [2, 3]
    assert (cache.cur_bytes, cache.insertions, cache.evictions) == (100, 3, 1)
    assert [v for v, _ in cache._entries.values()] == ["b", "c"]
    # the segmented cache cannot tell without its duels: any value it can
    # take at all may survive
    seg = SegmentedBlockCache(100)
    assert seg.survivors([30, 500, 30]) == [True, False, True]
    seg.put_many([(1, "a", 30), (2, None, 500)])
    assert (seg.insertions, len(seg)) == (1, 1)
    with pytest.raises(ValueError):
        seg.put_many([(3, None, 30)])


@pytest.mark.parametrize("name", ["sharded-csr", f"sharded-h5ad-{DRIVERS[-1]}"])
def test_async_delivery_is_unchanged(atlas, name):
    """Claimed blocks are still cut for their waiters: with ``io_workers=4,
    readahead=2`` and a cache of a few blocks, every batch of two epochs is
    the synchronous path's."""
    uri = atlas[0][name]

    def epochs(**kw):
        coll = open_collection(uri, block_rows=B, cache_bytes=6_000, **kw)
        ds = ScDataset(coll, BlockShuffling(8), batch_size=16, fetch_factor=4, seed=9)
        out = [b for _ in range(2) for b in ds]
        coll.close()
        return out, coll.iostats.snapshot()

    want, _ = epochs()
    got, snap = epochs(io_workers=4, readahead=2)
    assert len(got) == len(want) > 0
    for a, b in zip(got, want):
        _assert_same(a, b)
    assert snap["prefetched"] > 0


# ------------------------------------------------------------ the buffers
def test_buffer_pool_reuses_a_buffer_only_once_nothing_refers_to_it():
    pool = BufferPool()
    MB = 1 << 20
    a = pool.empty(3 * MB, np.uint8)
    a[:] = 7
    base = id(a.base)  # an id: a reference would keep the buffer in use
    b = pool.empty(3 * MB, np.uint8)
    assert id(b.base) != base  # `a` is alive: its buffer is not handed out
    view = a[10:20].view(np.int16)  # a view of a view refers to the buffer too
    del a
    c = pool.empty(MB, np.float32)
    assert id(c.base) != base and (view == 7 * 257).all()
    del view
    d = pool.empty(MB // 4, np.float64)  # the smallest free buffer that fits
    assert id(d.base) == base and d.nbytes == 2 * MB
    small = pool.empty(10, np.int32)  # small arrays are not pooled
    assert small.base is None


def test_buffer_pool_keeps_no_more_free_bytes_than_were_in_use():
    pool = BufferPool()
    MB = 1 << 20
    held = [pool.empty(4 * MB, np.uint8) for _ in range(3)]
    del held
    for _ in range(20):  # a working set of one buffer at a time
        pool.empty(2 * MB, np.uint8)
    assert sum(b.nbytes for b in pool._bufs) <= 12 * MB
    pool.empty(40 * MB, np.uint8)  # in use at once: at most 12 MB before
    assert sum(b.nbytes for b in pool._bufs) <= 40 * MB + 12 * MB


def test_buffer_pool_keeps_the_peak_of_its_last_two_windows():
    pool = BufferPool()
    MB = 1 << 20

    def total():
        return sum(x.nbytes for x in pool._bufs)

    big = [pool.empty(8 * MB, np.uint8) for _ in range(3)]  # 24 MB at once
    del big
    pool.trim()
    assert total() == 24 * MB
    a = pool.empty(2 * MB, np.uint8)  # a window that needs 8 MB at most
    del a
    pool.trim()  # the window before still needed 24 MB
    assert total() == 24 * MB
    b = pool.empty(2 * MB, np.uint8)
    last = id(b.base)  # an id: a reference would keep the buffer in use
    del b
    pool.trim()  # two windows of 8 MB: the least recently used go
    assert total() == 8 * MB and id(pool._bufs[0]) == last
    held = pool.empty(8 * MB, np.uint8)
    pool.trim()
    pool.trim()
    assert pool._bufs == [held.base]  # a buffer in use is never dropped


def test_buffer_pool_hands_out_fresh_arrays_without_the_gil(monkeypatch):
    monkeypatch.setattr("repro.data.csr_store._REFCOUNTS_SHOW_USE", False)
    pool = BufferPool()
    a = pool.empty(4 << 20, np.uint8)
    assert a.base is None and pool._bufs == []


@pytest.mark.parametrize("wrap", ["{}", "fault://{}", "cloud://{}?latency_scale=0"])
def test_every_fetch_ends_a_pool_window(atlas, wrap, monkeypatch):
    """The planner calls ``end_fetch`` after each fetch, through wrappers,
    and the CSR adapter trims its pool there."""
    trims = []
    monkeypatch.setattr(BufferPool, "trim", lambda self: trims.append(self))
    coll = open_collection(wrap.format(atlas[0]["sharded-csr"]), block_rows=B)
    for rows in (np.arange(10, 90), np.arange(700, 720), np.array([5])):
        coll.fetch(rows)
    inner = coll.adapter
    while hasattr(inner, "inner"):
        inner = inner.inner
    assert len(trims) == 3 and all(t is inner.pool for t in trims)


def test_gather_nbytes_is_what_the_cache_is_charged(atlas, tmp_path):
    """Each adapter's ``gather_nbytes`` equals the bytes of the batch
    ``gather`` builds, as the contract's default measures them."""
    rng = np.random.default_rng(3)
    write_chunked_store(str(tmp_path / "dense"), rng.normal(size=(200, 5)).astype(np.float32),
                        chunk_rows=64)
    generate_token_corpus(str(tmp_path / "tok"), n_tokens=4096, vocab_size=64,
                          n_sources=3, seed=2)
    for uri in (atlas[0]["sharded-csr"], atlas[0][f"sharded-h5ad-{DRIVERS[-1]}"],
                f"chunked://{tmp_path / 'dense'}", f"tokens://{tmp_path / 'tok'}?seq_len=16"):
        adapter = open_adapter(uri)
        pieces = [adapter.read_range(0, 60), adapter.read_range(60, 64)]
        ranges = [(pieces[0], 3, 41), (pieces[1], 0, 4), (pieces[0], 59, 60)]
        assert adapter.gather_nbytes(ranges) == StorageAdapter.gather_nbytes(adapter, ranges), uri


def test_fetched_batches_survive_later_fetches(atlas, store):
    """The batches and cached blocks a fetch hands out live in pooled
    buffers; later fetches never write over one that is still held."""
    coll = open_collection(atlas[0]["sharded-csr"], block_rows=B, cache_bytes=40_000)
    rows = [np.sort(np.random.default_rng(s).choice(len(store), 300, replace=False))
            for s in range(6)]
    got = [coll.fetch(r) for r in rows]
    for r, g in zip(rows, got):
        _assert_same(g, store[r])


@pytest.fixture(scope="module")
def wide(tmp_path_factory):
    """Two plates wide enough that their extents and batches come from the
    adapter's buffer pool (arrays of 1 MiB and more)."""
    rng = np.random.default_rng(5)
    root = tmp_path_factory.mktemp("gather_wide")
    names = []
    for i, n in enumerate((700, 500)):
        lens = rng.integers(600, 1000, n)
        indptr = np.zeros(n + 1, np.int64)
        np.cumsum(lens, out=indptr[1:])
        indices = np.concatenate([np.sort(rng.choice(4000, int(k), replace=False))
                                  for k in lens]).astype(np.int32)
        write_csr_shard(str(root / f"p{i}"), rng.normal(size=int(indptr[-1])).astype(np.float32),
                        indices, indptr, 4000, {"plate": np.full(n, i, np.int32)})
        names.append(f"p{i}")
    with open(root / "manifest.json", "w") as f:
        json.dump({"shards": names}, f)
    return str(root)


def test_pooled_buffers_never_overwrite_what_is_held(wide):
    """Every batch a fetch returned, and every block the cache holds, still
    reads as the store's rows after later fetches reused the pool."""
    store = ShardedCSRStore([os.path.join(wide, p) for p in ("p0", "p1")])
    coll = open_collection(f"sharded-csr://{wide}", block_rows=B, cache_bytes=2 << 20)
    fetches = [np.sort(np.random.default_rng(s).choice(len(store), 400, replace=False))
               for s in range(8)]
    held = [coll.fetch(rows) for rows in fetches]
    assert any(b.nbytes >= BufferPool.MIN_BYTES for b in coll.adapter.pool._bufs)
    for rows, got in zip(fetches, held):
        _assert_same(got, store[rows])
    for b, (val, _) in coll.cache._entries.items():
        _assert_same(val, store[np.arange(b * B, min((b + 1) * B, len(store)))])
    del held  # now the pool hands the batches' buffers out again
    for rows in fetches[:3]:
        _assert_same(coll.fetch(rows), store[rows])


def test_dense_batches_come_from_the_pool_and_are_zeroed(wide, monkeypatch):
    """``to_dense`` of a fetched batch takes a large output from the
    adapter's pool, never a buffer still held, and zeroes a reused one;
    a small one, or one of a batch without a pool, is a fresh array."""
    store = ShardedCSRStore([os.path.join(wide, p) for p in ("p0", "p1")])
    coll = open_collection(f"sharded-csr://{wide}", block_rows=B, cache_bytes=2 << 20)
    rows = np.sort(np.random.default_rng(9).choice(len(store), 900, replace=False))
    fetched = coll.fetch(rows)
    assert fetched.pool is coll.adapter.pool
    parts = [np.arange(k, 900, 3) for k in range(3)]  # 300 rows: 4.8 MB dense
    assert fetched[parts[0]].to_dense().base is None  # below DENSE_POOL_BYTES
    monkeypatch.setattr(csr_store, "DENSE_POOL_BYTES", 4 << 20)
    a = fetched[parts[0]].to_dense()
    b = fetched[parts[1]].to_dense()
    assert a.base is not None and a.base is not b.base
    base = id(a.base)  # an id: a reference would keep the buffer in use
    np.testing.assert_array_equal(a, store[rows[parts[0]]].to_dense())
    del a
    c = fetched[parts[2]].to_dense()
    assert id(c.base) == base
    np.testing.assert_array_equal(c, store[rows[parts[2]]].to_dense())
    np.testing.assert_array_equal(b, store[rows[parts[1]]].to_dense())
    assert store[rows[:300]].to_dense().base is None  # a batch without a pool
