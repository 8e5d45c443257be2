"""The loader's ``scdataset.*`` spans, read back from a profiler trace on the
CPU: their nesting, their args, one ``scdataset.read`` per physical read, and
a loader that still imports and fetches where JAX is absent."""
import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
from jax.profiler import ProfileData  # noqa: E402

from repro.core import BlockShuffling, ScDataset  # noqa: E402
from repro.data import CSRBatch, IOStats, open_collection, span, write_csr_shard  # noqa: E402
from repro.data.synth import write_h5ad  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_SHARD, G = 300, 32


def _csr(rng, n):
    lens = rng.integers(1, 6, n)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(lens, out=indptr[1:])
    indices = np.concatenate([np.sort(rng.choice(G, int(k), replace=False))
                              for k in lens]).astype(np.int32)
    data = rng.integers(1, 9, int(indptr[-1])).astype(np.float32)
    return data, indices, indptr


@pytest.fixture(scope="module")
def uris(tmp_path_factory):
    """Two plates, as ``sharded-csr`` shards and as ``sharded-h5ad`` files."""
    rng = np.random.default_rng(13)
    root = tmp_path_factory.mktemp("spans")
    (root / "h5ad").mkdir()
    csr, names = [], []
    for s in range(2):
        data, indices, indptr = _csr(rng, N_SHARD)
        obs = {"cell_id": np.arange(s * N_SHARD, (s + 1) * N_SHARD, dtype=np.int64)}
        path = str(root / f"plate{s}")
        write_csr_shard(path, data, indices, indptr, G, obs)
        csr.append(path)
        names.append(f"plate{s}.h5ad")
        write_h5ad(str(root / "h5ad" / names[-1]), data, indices, indptr, G, obs)
    with open(root / "h5ad" / "manifest.json", "w") as f:
        json.dump({"shards": names}, f)
    return {"sharded-csr": "sharded-csr://" + ",".join(csr),
            "sharded-h5ad": f"sharded-h5ad://{root / 'h5ad'}"}


def _traced(tmp_path, fn):
    """Run ``fn`` under the profiler; returns its result and the loader's
    spans as (name, thread, start_ns, end_ns, args), by start."""
    out = str(tmp_path / "trace")
    with jax.profiler.trace(out):
        result = fn()
    path = glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb"))[0]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):  # one line per thread, all named "python"
            for e in line.events:
                if e.name.startswith("scdataset."):
                    start = int(e.start_ns)
                    spans.append((e.name, f"{plane.name}/{i}", start,
                                  start + int(e.duration_ns), dict(e.stats)))
    return result, sorted(spans, key=lambda s: (s[2], -s[3]))


def _inside(child, parent) -> bool:
    return child[1] == parent[1] and parent[2] <= child[2] and child[3] <= parent[3]


def _dataset(uri, stats, **kw):
    coll = open_collection(uri, iostats=stats, block_rows=32, **kw)
    return ScDataset(coll, BlockShuffling(block_size=4), batch_size=16, fetch_factor=8,
                     seed=5)


@pytest.mark.parametrize("scheme", ["sharded-csr", "sharded-h5ad"])
def test_fetch_spans_nest_and_count_the_reads(tmp_path, uris, scheme):
    stats = IOStats()
    ds = _dataset(uris[scheme], stats)
    runs0 = stats.snapshot()["runs"]
    batches, spans = _traced(tmp_path, lambda: ds.fetch(0, 2))
    runs = stats.snapshot()["runs"] - runs0
    assert len(batches) == 8

    def named(name):
        return [s for s in spans if s[0] == name]

    (fetch,) = named("scdataset.fetch")
    assert fetch[4] == {"epoch": 0, "fetch": 2, "rows": 128}
    (plan,) = named("scdataset.plan")
    assert plan[4] == {"rows": 128} and _inside(plan, fetch)
    (assemble,) = named("scdataset.assemble")
    assert _inside(assemble, plan)
    reads = named("scdataset.read")
    assert len(reads) == runs > 0
    assert all(_inside(r, plan) and r[3] <= assemble[2] for r in reads)
    assert all(0 <= r[4]["start"] < r[4]["stop"] <= 2 * N_SHARD for r in reads)
    (split,) = named("scdataset.split")
    assert split[4] == {"batches": 8}
    assert _inside(split, fetch) and split[2] >= plan[3]


def test_every_read_has_one_span_on_the_io_pool(tmp_path, uris):
    """Reads issued on the I/O pool's threads are each spanned once, on the
    thread that reads."""
    stats = IOStats()
    ds = _dataset(uris["sharded-csr"], stats, io_workers=4, max_extent_rows=64)
    runs0 = stats.snapshot()["runs"]
    _, spans = _traced(tmp_path, lambda: ds.fetch(0, 0))
    reads = [s for s in spans if s[0] == "scdataset.read"]
    assert len(reads) == stats.snapshot()["runs"] - runs0 > 1
    (plan,) = [s for s in spans if s[0] == "scdataset.plan"]
    assert {r[1] for r in reads} - {plan[1]}  # some ran on a pool thread
    assert len({(r[4]["start"], r[4]["stop"]) for r in reads}) == len(reads)


def test_to_dense_and_put_batch_spans(tmp_path):
    from repro.distributed.dataio import put_batch
    from repro.distributed.sharding import RULES_TRAIN

    rng = np.random.default_rng(2)
    data, indices, indptr = _csr(rng, 6)
    batch = CSRBatch(data, indices, indptr, G, {})
    mesh = jax.make_mesh((1,), ("data",))

    def run():
        x = batch.to_dense()
        return x, put_batch({"x": x}, mesh, RULES_TRAIN)

    (x, dev), spans = _traced(tmp_path, run)
    assert [(s[0], s[4]) for s in spans] == [
        ("scdataset.to_dense", {"rows": 6, "workers": 1}),
        ("scdataset.put_batch", {"devices": 1, "bytes": x.nbytes})]
    np.testing.assert_array_equal(np.asarray(dev["x"]), x)
    assert x.sum() == data.sum()


def test_to_dense_span_counts_the_threads_of_a_large_batch(tmp_path, monkeypatch):
    """A batch of 64 rows of Tahoe's 62,710 genes (16 MB) is densified on
    several threads, and the span says how many."""
    from repro.data import csr_store

    monkeypatch.setattr(csr_store.os, "sched_getaffinity", lambda pid: set(range(4)))
    rng = np.random.default_rng(3)
    n, g = 64, 62_710
    indptr = np.arange(n + 1, dtype=np.int64) * 100
    indices = np.concatenate([np.sort(rng.choice(g, 100, replace=False))
                              for _ in range(n)]).astype(np.int32)
    data = rng.integers(1, 9, n * 100).astype(np.float32)
    x, spans = _traced(tmp_path, CSRBatch(data, indices, indptr, g, {}).to_dense)
    assert [(s[0], s[4]) for s in spans] == [
        ("scdataset.to_dense", {"rows": 64, "workers": 4})]
    assert x.sum() == data.sum()


def test_put_batch_span_counts_the_devices_of_a_four_device_mesh(tmp_path):
    """On a mesh of four devices the span says four, and the bytes are the
    global batch's, which the four devices share."""
    script = f"""
import glob, json, sys
sys.path[:0] = [{os.path.join(ROOT, "src")!r}]
import jax
import numpy as np
from jax.profiler import ProfileData
from repro.distributed.dataio import put_batch
from repro.distributed.sharding import RULES_TRAIN
mesh = jax.make_mesh((4,), ("data",))
batch = {{"x": np.ones((8, 5), np.float32), "y": np.arange(8, dtype=np.int32)}}
with jax.profiler.trace({str(tmp_path)!r}):
    dev = put_batch(batch, mesh, RULES_TRAIN)
path = glob.glob({str(tmp_path)!r} + "/plugins/profile/*/*.xplane.pb")[0]
args = [dict(e.stats) for p in ProfileData.from_file(path).planes for l in p.lines
        for e in l.events if e.name == "scdataset.put_batch"]
print(json.dumps({{"args": args, "devices": len(dev["x"].sharding.device_set)}}))
"""
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", script], env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    assert json.loads(p.stdout.strip().splitlines()[-1]) == {
        "args": [{"devices": 4, "bytes": 8 * 5 * 4 + 8 * 4}], "devices": 4}


def test_span_is_a_trace_annotation_once_jax_is_imported():
    assert isinstance(span("scdataset.read", start=0, stop=4),
                      jax.profiler.TraceAnnotation)


def test_loader_runs_without_jax(uris):
    """With JAX unimportable the loader still imports, and a fetch through
    every span site delivers, each span a no-op."""
    script = f"""
import sys
sys.modules["jax"] = None
sys.path.insert(0, {os.path.join(ROOT, "src")!r})
import numpy as np
import repro.data
from repro.core import BlockShuffling, ScDataset
from repro.data import open_collection, span
with span("scdataset.read", start=0, stop=1):
    pass
ds = ScDataset(open_collection({uris["sharded-csr"]!r}, block_rows=32),
               BlockShuffling(block_size=4), batch_size=16, fetch_factor=8, seed=5)
batches = ds.fetch(0, 0)
assert len(batches) == 8 and batches[0].to_dense().shape == (16, {G})
assert sys.modules["jax"] is None
print("ok")
"""
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "-c", script], env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.strip() == "ok"
