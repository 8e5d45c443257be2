"""The four-chip cell on four virtual CPU devices, the data-parallel Fig. 5
step against the float64 reference and against one device, and the readers
of the cross-chip collectives on a hand-made four-chip trace."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src"), os.path.dirname(__file__))
                if p not in sys.path]

from bench import harness  # noqa: E402
from bench.tracing import Trace  # noqa: E402

CELL = "tahoe-csr-dp4.block16-f256"

SCRIPT = """
import json, sys, types
sys.path[:0] = {paths!r}
import jax
import numpy as np
from bench import harness
from bench_sizes import tiny
from repro.data import csr_store
from repro.distributed.dataio import put_batch
from repro.distributed.sharding import RULES_TRAIN
assert jax.device_count() == 4
out = {{}}

# the cell end to end, and which devices its batches and heads live on
seen = {{"batch": set(), "dense": []}}
class Spy:
    def __init__(self, feed):
        self.feed = feed
    def host(self, b):
        h = self.feed.host(b)
        seen["dense"].append(None if h["x"].base is None else id(h["x"].base))
        return h
    def device(self, h):
        dev = self.feed.device(h)
        seen["batch"].update(d.id for a in jax.tree.leaves(dev) for d in a.devices())
        return dev
real = harness.load_module
def load_module(kind, name):
    mod = real(kind, name)
    if kind == "feeds":
        class M:
            Feed = staticmethod(lambda *a: Spy(mod.Feed(*a)))
        return M
    if kind == "consumers":
        def make_step(variant="program"):
            step = mod.make_step(variant)
            def spied(heads, opt, batch):
                heads, opt, loss = step(heads, opt, batch)
                seen["heads"] = sorted(d.id for d in heads["drug"]["w"].devices())
                return heads, opt, loss
            return spied
        return types.SimpleNamespace(**{{**vars(mod), "make_step": make_step}})
    return mod
harness.load_module = load_module
for fault in (None, "wrong_row"):
    r = harness.run({cell!r}, 2**31 + 17, 0.5, False, data_dir={data!r},
                    overrides=tiny(batch_size=64), fault=fault)
    out[str(fault)] = {{"correct": r["correct"], "failed": r["failed"],
                        "attempted": r["attempted"], "count": r["device"]["count"],
                        "checks": {{k: c["value"] for k, c in r["checks"].items()}},
                        "batch_devices": sorted(seen["batch"]), "heads_devices": seen["heads"]}}
# dense batches of 1 MiB (64 x 4,096), taken from the loader's pool as the
# cell's 64 MB ones are, reused while the devices may still hold earlier ones
csr_store.DENSE_POOL_BYTES = 1 << 20
seen["dense"].clear()
sizes = tiny(batch_size=64)
sizes["config"]["n_genes"] = 4096
r = harness.run({cell!r}, 2**31 + 29, 0.5, False, data_dir={data!r}, overrides=sizes)
out["pooled"] = {{"correct": r["correct"], "failed": r["failed"], "attempted": r["attempted"],
                  "fresh": seen["dense"].count(None), "batches": len(seen["dense"]),
                  "buffers": len(set(seen["dense"]) - {{None}})}}
harness.load_module = real

# the step, three times on a batch over the four devices and over one
fig5 = harness.load_module("consumers", "fig5")
rng = np.random.default_rng(5)
G, B = 384, 64
batches = [{{"x": rng.poisson(0.3, (B, G)).astype(np.float32),
             **{{t: rng.integers(0, c, B).astype(np.int32) for t, c in fig5.TASKS.items()}}}}
           for _ in range(3)]
runs = {{}}
for n in (4, 1):
    mesh = harness.data_mesh(jax.devices()[:n])
    heads, opt = fig5.init(11, G)
    heads0 = jax.device_get(heads)
    step = fig5.make_step()
    losses = []
    for i, b in enumerate(batches):
        dev = put_batch(b, mesh, RULES_TRAIN)
        heads, opt, loss = step(heads, opt, dev)
        losses.append(float(loss))
        if i == 0:
            opt1 = jax.device_get(opt)
    hlo = step.lower(heads, opt, dev).compile().as_text()
    prog = fig5.program_readings(heads0, opt1, jax.device_get(heads), losses)
    runs[n] = {{"losses": losses, "all_reduce": "all-reduce" in hlo,
                "heads_devices": len(heads["drug"]["w"].sharding.device_set),
                "gaps": fig5.compare(prog, fig5.reference_steps(heads0, batches))}}
out["step"] = {{"dp": runs[4], "one": runs[1], "limits": fig5.LIMITS}}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four(tmp_path_factory):
    """The cell, its ``wrong_row`` fault and the step, in one process that
    sees four CPU devices."""
    data = str(tmp_path_factory.mktemp("dp4") / "data")
    paths = [ROOT, os.path.join(ROOT, "src"), os.path.dirname(__file__)]
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", SCRIPT.format(paths=paths, cell=CELL, data=data)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_cell_end_to_end_on_four_devices(four):
    r = four["None"]
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["count"] == 4
    # one loader's global batch is laid over all four devices, the heads replicated
    assert r["batch_devices"] == [0, 1, 2, 3] and r["heads_devices"] == [0, 1, 2, 3]


def test_wrong_row_is_caught_on_four_devices(four):
    r = four["wrong_row"]
    assert r["correct"] is False and r["failed"] > 0
    assert r["checks"]["rows_mismatched_batches"] > 0


def test_pooled_dense_batches_stay_correct_on_four_devices(four):
    """Dense batches drawn from the loader's pool and reused as soon as
    nothing refers to them reach the four devices unchanged."""
    r = four["pooled"]
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["fresh"] == 0 and 0 < r["buffers"] < r["batches"]


def test_data_parallel_step_matches_reference_and_one_device(four):
    dp, one, limits = four["step"]["dp"], four["step"]["one"], four["step"]["limits"]
    assert dp["all_reduce"] and not one["all_reduce"]
    assert dp["heads_devices"] == 4 and one["heads_devices"] == 1
    assert all(dp["gaps"][k] <= limits[k] for k in limits)
    # the same arithmetic, summed in another order
    assert dp["losses"] == pytest.approx(one["losses"], rel=1e-6)


# ------------------------------------------------------------------ readers
def _device_events(d, allreduce_ns):
    plane = f"/device:TPU:{d}"
    ev = []
    for s in (100, 400):  # two runs of the step in the window
        ev.append([plane, "XLA Modules", "jit_fig5_step(42)", s, 200])
        ev.append([plane, "XLA Ops", "fusion.3", s, 50])
        ev.append([plane, "XLA Ops", "%all-reduce.15 = (f32[50]...) all-reduce(...)",
                   s + 60, allreduce_ns])
    ev.append([plane, "XLA Modules", "jit_other(7)", 700, 100])
    ev.append([plane, "XLA Ops", "all-gather.2", 710, 50])  # not in the step
    ev.append([plane, "XLA Ops", "all-reduce.9", 850, 30])  # in no module
    return ev


def _reading(events):
    class R:
        trace = Trace([["/host:CPU", "python", "bench.window", 0, 1000]] + events)
        step_module = "jit_fig5_step"
    return R()


ALLREDUCE_MS = harness.load_module("metrics", "consumer.allreduce_ms")
ALLREDUCE_GBPS = harness.load_module("metrics", "consumer.allreduce_gbps")


def test_allreduce_ms_is_per_step_and_averaged_over_chips():
    events = [e for d in range(4) for e in _device_events(d, 10 + 10 * d)]
    r = _reading(events)
    assert ALLREDUCE_MS.per_chip_ms(r.trace, "jit_fig5_step") == pytest.approx(
        [10e-6, 20e-6, 30e-6, 40e-6])
    assert ALLREDUCE_MS.read(r) == pytest.approx(25e-6)


def test_allreduce_bytes_of_the_fig5_heads():
    tasks = harness.load_module("consumers", "fig5").TASKS
    assert ALLREDUCE_GBPS.n_genes() == 62710
    moved = ALLREDUCE_GBPS.allreduce_bytes(62710, tasks, 4)
    assert moved == 2 * 3 / 4 * 4 * (62710 * 461 + 461)
    assert moved == pytest.approx(173.5e6, rel=1e-3)
    assert ALLREDUCE_GBPS.allreduce_bytes(62710, tasks, 1) == 0
    r = _reading([e for d in range(4) for e in _device_events(d, 10 + 10 * d)])
    assert ALLREDUCE_GBPS.read(r) == pytest.approx(moved / 25e-9 / 1e9)


def test_allreduce_readers_find_nothing_without_collectives():
    no_collective = [e for d in range(4) for e in _device_events(d, 10)
                     if "all-" not in e[2]]
    one_chip = _device_events(0, 10)
    for events in (no_collective, []):
        assert ALLREDUCE_MS.read(_reading(events)) is None
        assert ALLREDUCE_GBPS.read(_reading(events)) is None
    assert ALLREDUCE_MS.read(_reading(one_chip)) == pytest.approx(10e-6)
    assert ALLREDUCE_GBPS.read(_reading(one_chip)) is None  # one chip moves nothing
