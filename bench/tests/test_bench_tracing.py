"""The reduction from trace events to busy, idle, self times and breakdown,
on a hand-made trace with known answers and on one recorded on a v5e."""
import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src")) if p not in sys.path]

from bench.tracing import Trace  # noqa: E402

HOST, DEV = ("/host:CPU", "python"), "/device:TPU:0"
OTHER = ("/host:CPU", "worker")


def _span(name, s, e, thread=HOST):
    return [thread[0], thread[1], name, s, e - s]


def _op(name, s, e, line="XLA Ops"):
    return [DEV, line, name, s, e - s]


HAND = [
    _span("bench.window", 0, 1000),
    _span("bench.loader", -100, -50),  # before the window: left out
    _span("bench.loader", 10, 110),
    _span("bench.feed", 110, 300),
    _span("bench.densify", 120, 200),
    _span("bench.h2d", 200, 290),
    _span("bench.step", 300, 320),
    _span("bench.loader", 400, 450),
    _span("bench.densify", 410, 430, OTHER),  # another thread: not a child
    _span("bench.drain", 700, 1000),
    ["/host:CPU", "python", "PjRtClient::Execute", 300, 5],  # not ours: dropped
    _op("%A = f32[2] fusion(...)", 100, 200),
    _op("%B = f32[2] fusion(...)", 150, 250),
    _op("%C = f32[2] fusion(...)", 500, 600),
    _op("%D = f32[2] copy(...)", 950, 1100),  # runs past the window's end
    _op("jit_fig5_step(123)", 100, 260, "XLA Modules"),
    _op("jit_fig5_step(123)", 500, 610, "XLA Modules"),
    _op("jit_other(9)", 940, 1100, "XLA Modules"),
]


def test_hand_made_trace_gives_known_numbers():
    t = Trace(HAND)
    assert t.window_s == pytest.approx(1000e-9)
    # union of [100,250], [500,600], [950,1000]
    assert t.busy_s == pytest.approx(300e-9)
    assert t.idle_gaps() == [(0, 100), (250, 500), (600, 950)]
    assert t.self_times("bench.loader") == pytest.approx([100e-9, 50e-9])
    assert t.self_times("bench.feed") == pytest.approx([20e-9])  # 190 - 80 - 90
    assert sorted(t.self_times("bench.densify")) == pytest.approx([20e-9, 80e-9])
    assert t.self_times("bench.h2d") == pytest.approx([90e-9])
    assert t.module_times("jit_fig5_step") == pytest.approx([160e-9, 110e-9])
    b = t.breakdown()
    assert b["idle_gaps"] == [["bench.drain", pytest.approx(350e-9)],
                              ["none", pytest.approx(250e-9)],
                              ["bench.loader", pytest.approx(100e-9)]]
    assert dict((k, v) for k, v in b["device_ops"]) == {
        "jit_fig5_step/A": pytest.approx(100e-9), "jit_fig5_step/B": pytest.approx(100e-9),
        "jit_fig5_step/C": pytest.approx(100e-9), "jit_other/D": pytest.approx(150e-9)}


def test_trace_keeps_the_cells_own_devices():
    """A device outside the cell's adds no busy time and no ops: busy_s is
    averaged over the cell's devices alone."""
    other = [["/device:TPU:1", line, name, s, d] for _, line, name, s, d in HAND
             if _[:7] == "/device"]
    t = Trace(HAND + other, planes={DEV})
    assert sorted(t.ops) == [DEV]
    assert t.busy_s == pytest.approx(300e-9)
    assert t.module_times("jit_fig5_step") == pytest.approx([160e-9, 110e-9])
    assert len(Trace(HAND + other).module_times("jit_fig5_step")) == 4


def test_trace_needs_one_window():
    with pytest.raises(ValueError, match="one bench.window"):
        Trace([e for e in HAND if e[2] != "bench.window"])


RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "tahoe-csr_v5e_400ms.json.gz")


def test_recorded_v5e_trace():
    """400 ms of the window of a traced ``tahoe-csr.block16-f256`` run on one
    v5e, with the numbers its reduction gave when it was recorded."""
    with gzip.open(RECORDED, "rt") as f:
        t = Trace(json.load(f))
    gaps = t.idle_gaps()
    assert t.busy_s + sum(e - s for s, e in gaps) / 1e9 == pytest.approx(t.window_s)
    steps = t.module_times("jit_fig5_step")
    loader = t.self_times("bench.loader")
    assert len(steps) == len(t.self_times("bench.step")) == 55  # the slice cuts one loader
    assert len(loader) == len(t.self_times("bench.feed")) == 54  # and feed span at its start
    assert t.window_s == pytest.approx(0.4)
    assert t.busy_s == pytest.approx(0.063559062, rel=1e-9)
    assert sum(loader) == pytest.approx(0.000579277, rel=1e-6)
    assert sum(t.self_times("bench.densify")) == pytest.approx(0.201610906, rel=1e-9)
    assert sum(t.self_times("bench.h2d")) == pytest.approx(0.10254489, rel=1e-9)
    b = t.breakdown()
    assert b["device_ops"][0] == ["jit_fig5_step/divide_subtract_fusion",
                                  pytest.approx(0.040519181, rel=1e-6)]
    assert [name for name, _ in b["idle_gaps"]] == ["bench.densify"] * 10
