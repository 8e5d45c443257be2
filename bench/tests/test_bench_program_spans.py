"""The program's ``scdataset.*`` spans beside the run's ``bench.*`` spans: the
readers of the new per-layer metrics on a hand-made trace with known
answers, the naming of idle gaps, the bench numbers left as they were, and
each cell end to end at a tiny size on the CPU, and a fetch recorded on
a v5e."""
import gzip
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src"), HERE) if p not in sys.path]

from bench import harness, program_spans  # noqa: E402
from bench.program_spans import ProgramTrace  # noqa: E402
from bench.tracing import Trace  # noqa: E402
from bench_sizes import tiny  # noqa: E402
from test_bench_tracing import HAND, OTHER, _span  # noqa: E402

# one fetch inside the first bench.loader span (10-110), the densify and the
# copy inside their bench spans, a read on another thread, and a fetch
# before the window
PROGRAM = [
    _span("scdataset.fetch", -95, -60),
    _span("scdataset.fetch", 15, 105),
    _span("scdataset.plan", 20, 80),
    _span("scdataset.read", 25, 45),
    _span("scdataset.read", 50, 60),
    _span("scdataset.assemble", 65, 78),
    _span("scdataset.split", 82, 100),
    _span("scdataset.read", 410, 430, OTHER),
    _span("scdataset.to_dense", 125, 195),
    _span("scdataset.put_batch", 205, 285),
]
NEW = ["loader.fetch_read_ms", "loader.read_gbps", "loader.fetch_cache_ms",
       "loader.fetch_assemble_ms", "loader.fetch_split_ms", "feed.to_dense_ms",
       "feed.put_batch_ms"]
BENCH_SPANS = ["bench.loader", "bench.feed", "bench.densify", "bench.h2d", "bench.step",
               "bench.drain"]


class Reading:
    def __init__(self, events, n_batches=2, bytes_read=1000):
        self.trace = Trace(events)
        self.program_spans = ProgramTrace(events)
        self.n_batches = n_batches
        self.counters = {"bytes_read": bytes_read, "rows": 128}


def _read(name, r):
    return harness.load_module("metrics", name).read(r)


def test_bench_numbers_stay_as_they_were():
    """The program's spans nested inside the run's move no number the
    run's trace reduction gives."""
    with_program, alone = Trace(HAND + PROGRAM), Trace(HAND)
    assert with_program.busy_s == alone.busy_s
    assert with_program.breakdown() == alone.breakdown()
    for name in BENCH_SPANS:
        assert with_program.self_times(name) == alone.self_times(name)
        # and the family-aware reduction gives the run's spans the same self times
        assert ProgramTrace(HAND + PROGRAM).self_times(name) == alone.self_times(name)


def test_program_self_times_subtract_their_own_family():
    s = ProgramTrace(HAND + PROGRAM)
    assert s.count("scdataset.fetch") == 1  # the one before the window is left out
    assert s.self_times("scdataset.fetch") == pytest.approx([12e-9])  # 90 - 60 - 18
    assert s.self_times("scdataset.plan") == pytest.approx([17e-9])  # 60 - 20 - 10 - 13
    assert sorted(s.self_times("scdataset.read")) == pytest.approx([10e-9, 20e-9, 20e-9])
    assert s.self_times("scdataset.assemble") == pytest.approx([13e-9])
    assert s.self_times("scdataset.split") == pytest.approx([18e-9])
    assert s.self_times("scdataset.to_dense") == pytest.approx([70e-9])
    assert s.self_times("scdataset.put_batch") == pytest.approx([80e-9])


def test_readers_give_known_answers():
    r = Reading(HAND + PROGRAM)
    assert _read("loader.fetch_read_ms", r) == pytest.approx(50e-6)
    assert _read("loader.read_gbps", r) == pytest.approx(1000 / 50e-9 / 1e9)
    assert _read("loader.fetch_cache_ms", r) == pytest.approx(17e-6)
    assert _read("loader.fetch_assemble_ms", r) == pytest.approx(13e-6)
    assert _read("loader.fetch_split_ms", r) == pytest.approx(18e-6)
    assert _read("feed.to_dense_ms", r) == pytest.approx(70e-6 / 2)
    assert _read("feed.put_batch_ms", r) == pytest.approx(80e-6 / 2)


def test_readers_find_nothing_without_program_spans():
    """A program that records no ``scdataset.*`` span (an older one): every
    new reader returns None and none raises."""
    r = Reading(HAND)
    assert [_read(m, r) for m in NEW] == [None] * len(NEW)


def test_idle_gap_inside_a_program_span_is_named_by_it():
    s = ProgramTrace(HAND + PROGRAM)
    assert s.breakdown()["idle_gaps"] == [["bench.drain", pytest.approx(350e-9)],
                                          ["none", pytest.approx(250e-9)],
                                          ["scdataset.read", pytest.approx(100e-9)]]
    assert s.breakdown()["device_ops"] == Trace(HAND).breakdown()["device_ops"]
    assert Trace(HAND + PROGRAM).breakdown()["idle_gaps"][-1][0] == "bench.loader"
    assert s.span_at(81) == "scdataset.fetch" and s.span_at(90) == "scdataset.split"
    assert s.span_at(20) == "scdataset.plan" and s.span_at(420) == "bench.loader"


def test_find_reads_the_harness_trace_of_the_same_window(tmp_path, monkeypatch):
    """Without spans of its own, a reading's are found among the trace
    files under the harness's directory by its window, and nowhere else."""
    monkeypatch.setattr(program_spans, "TRACE_DIR", str(tmp_path))
    monkeypatch.setattr(program_spans, "_FOUND", {})

    def from_xplane(cls, path, planes=None):
        if "broken" in path:
            raise ValueError("a file half written")
        return cls(HAND + PROGRAM if "mine" in path else [_span("bench.window", 5, 9)])

    monkeypatch.setattr(ProgramTrace, "from_xplane", classmethod(from_xplane))
    for d in ("other", "mine", "broken"):
        p = tmp_path / d / "plugins" / "profile" / "1" / "host.xplane.pb"
        p.parent.mkdir(parents=True)
        p.write_bytes(b"")

    class R:
        trace = Trace(HAND)

    found = program_spans.find(R())
    assert found is not None and found.count("scdataset.fetch") == 1
    R.trace = Trace([e if e[2] != "bench.window" else _span("bench.window", 0, 999)
                     for e in HAND])
    assert program_spans.find(R()) is None


@pytest.mark.parametrize("cell", ["tahoe-h5ad.block16-f256", "tahoe-csr.block16-f256"])
def test_cell_traced_reports_program_spans(tmp_path, cell):
    """A traced run at a tiny size, its trace written where the harness
    writes it: every new metric is read, and the fetch's parts fit inside
    the loader's time."""
    trace_dir = os.path.join(program_spans.TRACE_DIR, f"test-{cell}")
    try:
        # the seed of test_bench_cells.py, whose window reads (the tiny atlas
        # fits the block cache, so a window may read nothing at all)
        r = harness.run(cell, 2**31 + 11, 0.5, True, data_dir=str(tmp_path / "data"),
                        trace_dir=trace_dir, overrides=tiny())
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    assert r["correct"] is True
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert set(NEW) <= set(m) and all(m[k] > 0 for k in NEW)
    assert r["metrics"]["loader.read_gbps"]["unit"] == "GB/s"
    fetch_factor = tiny()["traffic"]["fetch_factor"]
    parts = sum(m[k] for k in NEW[:1] + NEW[2:5])
    assert parts < m["loader.next_ms"] * fetch_factor
    assert m["feed.to_dense_ms"] <= m["feed.densify_host_ms"]
    assert m["feed.put_batch_ms"] <= m["feed.h2d_ms"]


RECORDED = os.path.join(HERE, "data", "tahoe-h5ad_v5e_fetch.json.gz")


def test_recorded_v5e_fetch():
    """2.04 s of the window of a traced ``tahoe-h5ad.block16-f256`` run on one
    v5e, around its longest fetch (14 plate reads), with the numbers the
    readers gave when it was recorded."""
    with gzip.open(RECORDED, "rt") as f:
        events = json.load(f)
    r = Reading(events, n_batches=10)
    t, s = r.trace, r.program_spans
    assert t.window_s == pytest.approx(2.040077728)
    assert t.busy_s == pytest.approx(0.011573729, rel=1e-9)
    assert len(t.self_times("bench.step")) == len(t.module_times("jit_fig5_step")) == 10
    assert s.count("scdataset.fetch") == 1 and s.count("scdataset.read") == 14
    assert _read("loader.fetch_read_ms", r) == pytest.approx(209.969445, rel=1e-9)
    assert _read("loader.fetch_cache_ms", r) == pytest.approx(626.97083, rel=1e-9)
    assert _read("loader.fetch_assemble_ms", r) == pytest.approx(884.219815, rel=1e-9)
    assert _read("loader.fetch_split_ms", r) == pytest.approx(198.380868, rel=1e-9)
    assert _read("feed.to_dense_ms", r) == pytest.approx(3.6895134, rel=1e-9)
    assert _read("feed.put_batch_ms", r) == pytest.approx(1.9636908, rel=1e-9)
    for name in BENCH_SPANS:
        assert s.self_times(name) == t.self_times(name)
    # the fetch's parts and its own self time make up the loader's stall
    (fetch,) = [x for x in s.spans if x[0] == "scdataset.fetch"]
    parts = sum(sum(s.self_times(n)) for n in ("scdataset.fetch", "scdataset.plan",
                                               "scdataset.read", "scdataset.assemble",
                                               "scdataset.split"))
    assert parts == pytest.approx((fetch[3] - fetch[2]) / 1e9)
    assert parts > 0.97 * max(t.self_times("bench.loader"))
    # the run's reduction names the longest idle gap by its own span, the
    # program's by the phase the loader was in
    assert t.breakdown()["idle_gaps"][0] == ["bench.loader", pytest.approx(1.970087092)]
    assert s.breakdown()["idle_gaps"][0] == ["scdataset.assemble", pytest.approx(1.970087092)]
