"""Each cell end to end at a tiny size on the CPU, through ``harness.run``
(the path ``bench/run.py`` drives once it has found its chips)."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src"), os.path.dirname(__file__))
                if p not in sys.path]

from bench import harness  # noqa: E402
from bench_sizes import tiny  # noqa: E402

CELLS = ["tahoe-h5ad.block16-f256", "tahoe-csr.block16-f256"]
SEED = 2**31 + 11


def _run(tmp_path, cell, trace=False, **kw):
    overrides = kw.pop("overrides", None) or tiny()
    return harness.run(cell, SEED, 0.5, trace, data_dir=str(tmp_path / "data"),
                       trace_dir=str(tmp_path / "trace"), overrides=overrides, **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_end_to_end(tmp_path, cell):
    r = _run(tmp_path, cell)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"cells_per_s", "batch_wait_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1
    assert list(r)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())
    assert not os.path.exists(tmp_path / "data")  # the atlas goes with the run


@pytest.mark.parametrize("cell", CELLS)
def test_cell_traced(tmp_path, cell):
    r = _run(tmp_path, cell, trace=True)
    assert r["correct"] is True
    # the CPU has no device plane: the device readers find nothing to read
    assert set(r["metrics"]) == {"loader.next_ms", "loader.runs_per_cell",
                                 "loader.read_bytes_per_cell",
                                 "feed.densify_host_ms", "feed.h2d_ms"}
    assert 0 < r["metrics"]["loader.runs_per_cell"]["value"] < 1
    assert r["device"]["window_s"] > 0 and r["device"]["busy_s"] == 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_cell_keeps_to_its_chips(tmp_path):
    """On a host with more devices than the cell asks for, the batch goes to
    the cell's one device, and the run says so."""
    script = f"""
import json, sys
sys.path[:0] = {[ROOT, os.path.join(ROOT, "src"), os.path.dirname(__file__)]!r}
import jax
from bench import harness
from bench_sizes import tiny
assert jax.device_count() == 4
seen = set()
class Spy:
    def __init__(self, feed):
        self.feed = feed
    def host(self, b):
        return self.feed.host(b)
    def device(self, h):
        out = self.feed.device(h)
        seen.update(d.id for a in jax.tree.leaves(out) for d in a.devices())
        return out
real = harness.load_module
def load_module(kind, name):
    mod = real(kind, name)
    if kind == "feeds":
        class M:
            Feed = staticmethod(lambda *a: Spy(mod.Feed(*a)))
        return M
    return mod
harness.load_module = load_module
r = harness.run({CELLS[1]!r}, 7, 0.5, False, data_dir={str(tmp_path / "data")!r},
                overrides=tiny())
print(json.dumps({{"seen": sorted(seen), "count": r["device"]["count"],
                  "correct": r["correct"]}}))
"""
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", script], env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    assert json.loads(p.stdout.strip().splitlines()[-1]) == {
        "seen": [0], "count": 1, "correct": True}


def test_ell_device_feed(tmp_path):
    """The on-chip densify feed, through the jnp reference off the TPU: the
    values on the device still equal the reference's dense rows."""
    r = _run(tmp_path, CELLS[1], trace=True, overrides=tiny(feed="ell_device"))
    assert r["correct"] is True and r["checks"]["values_mismatched"]["value"] == 0


def test_densify_roofline_reader():
    reader = harness.load_module("metrics", "densify_roofline")

    class T:
        def module_times(self, prefix):
            assert prefix == "jit_densify"
            return [2e-3, 4e-3]

    class R:
        trace = T()
        feed_shapes = {"rows": 64, "k": 3456, "n_genes": 62710}
        device_kind = "TPU v5 lite"

    moved = 64 * 3456 * 8 + 64 * 62710 * 4
    assert reader.densify_bytes(64, 3456, 62710) == moved
    assert reader.read(R()) == pytest.approx(100 * moved / 819e9 / 3e-3)
    R.device_kind = "TPU v9"
    with pytest.raises(KeyError, match="no published peaks"):
        reader.read(R())
    R.feed_shapes = None
    assert reader.read(R()) is None
