"""One run of one cell: set-up, the measured window, the check, the result.

Everything that belongs to one configuration, traffic mix, feed, consumer,
storage format or per-layer metric sits in a file of its own under
``bench/``, found by the name that ``BENCHMARK.json`` or the traffic file
gives it:

- ``configs/<config>.json``: the atlas's sizes and its storage format;
- ``traffic/<traffic>.json``: sampling, batch, fetch factor, warm-up fetches,
  feed, consumer;
- ``formats/<format>.py``: ``uri``, ``write`` and a plain ``read_plates``;
- ``feeds/<feed>.py``: ``Feed(pipe, labels, mesh)`` with ``host`` and ``device``;
- ``consumers/<consumer>.py``: ``init``, ``make_step``, the float64
  reference, ``compare`` and ``LIMITS``;
- ``metrics/<metric>.py``: ``read(reading)``, a number or None.

A run builds the loader with the program's front door,
``Pipeline.from_uri(...).strategy(...).batch(...).seed(seed).build()``, runs
``warmup_fetches`` fetch cycles through feed and consumer as warm-up, then
asks the loader for a batch, feeds it and dispatches the step (a closed
loop) for ``seconds`` and on to the next fetch boundary, so that the window
holds whole fetch cycles, and blocks once, at the end.  The check then
compares, against the plain reference, the rows of every batch delivered,
the values on the device of a sample of them drawn from the seed, and the
consumer's first three steps.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import statistics
import sys
import time
from typing import Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

from repro.pipeline import Pipeline  # noqa: E402

from bench import reference, tracing  # noqa: E402

SAMPLE_EVERY = 32  # a window batch's values are checked with probability 1/32
SAMPLE_CAP = 96  # at most this many batches are copied back for the check
REFERENCE_STEPS = 3
STALL_S = 0.1  # a wait this long is logged by batch: as a rule, a fetch
FAULTS = ("wrong_row", "altered_value")  # loader-side faults the tests plant


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    per_layer: list  # the per_layer entries of BENCHMARK.json for this cell
    end_to_end: list


def load_cell(name: str, overrides: Optional[dict] = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its config and traffic;
    ``overrides`` (``{"config": {...}, "traffic": {...}}``) resizes it."""
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    config = load_json(os.path.join(BENCH, "configs", f"{w['config']}.json"))
    traffic = load_json(os.path.join(BENCH, "traffic", f"{w['traffic']}.json"))
    for part, d in (overrides or {}).items():
        {"config": config, "traffic": traffic}[part].update(d)

    def mine(m):
        return name in m.get("workloads", [name])

    return Cell(name, int(w["chips"]), config, traffic,
                [m for m in spec["per_layer"] if mine(m)],
                [m for m in spec["end_to_end"] if mine(m)])


def device_info(n: int) -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": n}


def _memory(devices, key: str = "peak_bytes_in_use") -> Optional[int]:
    """``key`` of the devices' memory stats, on the fullest device."""
    values = [(d.memory_stats() or {}).get(key) for d in devices]
    values = [v for v in values if v is not None]
    return max(values) if values else None


def _to_host(tree):
    """A host copy of ``tree``, so that the check holds no device memory."""
    return jax.device_get(tree)


def data_mesh(devices):
    """The 1-D ``("data",)`` mesh over the cell's own chips, and no others."""
    from jax.sharding import AxisType

    return jax.make_mesh((len(devices),), ("data",), axis_types=(AxisType.Auto,),
                         devices=devices)


class _Compiles:
    """Counts traces and compilations while it is on."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **kw):
        if self.on and name in self.EVENTS:
            self.count += 1


_COMPILES = None


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: Optional[float] = None, data_dir: Optional[str] = None,
        trace_dir: Optional[str] = None, overrides: Optional[dict] = None,
        variant: str = "program", fault: Optional[str] = None) -> dict:
    """One run; returns the result object that ``run.py`` prints.

    ``variant`` (the consumer's) and ``fault`` (the loader's, one of
    ``FAULTS``) break the timed path for the limit readings and the tests;
    ``run.py`` never sets them."""
    global _COMPILES
    t_start = time.perf_counter() if t_start is None else t_start
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; expected one of {FAULTS}")
    cell = load_cell(workload, overrides)
    cfg, tr = cell.config, cell.traffic
    fmt = load_module("formats", cfg["format"])
    consumer = load_module("consumers", tr["consumer"])
    feed_mod = load_module("feeds", tr["feed"])
    devices = jax.devices()[:cell.chips]
    if _COMPILES is None:
        _COMPILES = _Compiles()
    def part(name: str, t0: float) -> float:
        now = time.perf_counter()
        log(f"[setup] {name}: {now - t0:.3f} s")
        return now

    t = part("start (interpreter, JAX, devices)", t_start)

    # ---- the atlas, made from the seed and written in the config's format
    data_dir = data_dir or os.path.join(BENCH, ".data", cfg["name"])
    shutil.rmtree(data_dir, ignore_errors=True)
    os.makedirs(data_dir)
    from bench import atlas

    fmt.write(data_dir, atlas.generate(cfg, seed), cfg["n_genes"])
    t = part("generate+write", t)

    # ---- the loader, through the program's front door
    pipe = (Pipeline.from_uri(fmt.uri(data_dir))
            .strategy(tr["strategy"], **tr["strategy_params"])
            .batch(tr["batch_size"], fetch_factor=tr["fetch_factor"])
            .seed(seed)
            .build())
    t = part("open", t)

    labels = list(consumer.TASKS)
    feed = feed_mod.Feed(pipe, labels, data_mesh(devices))
    key_seed = int(np.random.SeedSequence([int(seed), 0xF165]).generate_state(1)[0] >> 1)
    heads, opt = consumer.init(key_seed, int(cfg["n_genes"]))
    step = consumer.make_step(variant)
    # the check's snapshots go to the host as they are taken, so that the
    # device's peak is the loader's and the consumer's alone
    snaps = {"heads0": _to_host(heads)}
    t = part("init", t)

    it = iter(pipe)

    delivered = []  # cell ids of every batch, warm-up and window

    def next_batch():
        nonlocal it
        try:
            batch = next(it)
        except StopIteration:  # epoch boundary
            it = iter(pipe)
            batch = next(it)
        if fault is not None and len(delivered) % 7 == 3:
            if fault == "wrong_row":
                batch = batch[np.r_[1, 1:len(batch)]]
            else:
                batch.data[0] += 1.0
        delivered.append(np.asarray(batch.obs["cell_id"]))
        return batch

    def feed_one(batch):
        return feed.device(feed.host(batch))

    # ---- warm-up: whole fetch cycles through the window's own calls
    losses = []
    for k in range(tr["warmup_fetches"] * tr["fetch_factor"]):
        dev = feed_one(next_batch())
        heads, opt, loss = step(heads, opt, dev)
        if k == 0:
            jax.block_until_ready(loss)
            t = part("first step (compile or cache)", t)
            snaps["opt1"] = _to_host(opt)
        if k < REFERENCE_STEPS:
            losses.append(float(loss))
        if k == REFERENCE_STEPS - 1:
            snaps["heads3"] = _to_host(heads)
    jax.block_until_ready((heads, opt))
    t = part(f"warm-up ({tr['warmup_fetches']} fetch cycles)", t)
    setup_s = time.perf_counter() - t_start
    log(f"[setup] setup_s: {setup_s:.3f} s")
    log(f"[memory] device peak after set-up: {_memory(devices)} B")

    # ---- the measured window
    sample_u = np.random.default_rng(np.random.SeedSequence([int(seed), 0x5A3])).random
    waits, kept = [], []  # kept: (batch index, host copy of its device arrays)
    pending = None  # the last sampled batch, while its copy to the host runs
    stats0 = pipe.stats().get("io", {})
    tracer = tracing.Tracer(trace_dir or os.path.join(BENCH, ".trace", cell.name)) if trace else None
    if tracer:
        tracer.start()
    _COMPILES.count, _COMPILES.on = 0, True
    cycle = int(tr["fetch_factor"])  # the window ends on a fetch boundary
    n = 0
    with TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while True:
            t_ask = time.perf_counter()
            if t_ask - t0 >= seconds and n % cycle == 0:
                break
            with TraceAnnotation("bench.loader"):
                batch = next_batch()
            with TraceAnnotation("bench.feed"):
                dev = feed_one(batch)
            t_ready = time.perf_counter()
            with TraceAnnotation("bench.step"):
                heads, opt, loss = step(heads, opt, dev)
            waits.append(t_ready - t_ask)
            held = len(kept) + (pending is not None)
            if held < SAMPLE_CAP and (n == 0 or sample_u() < 1 / SAMPLE_EVERY):
                # one sampled batch at a time stays on the device, until the
                # next is drawn: its copy to the host has long ended by then
                for a in jax.tree.leaves(dev):
                    a.copy_to_host_async()
                if pending is not None:
                    kept.append((pending[0], _to_host(pending[1])))
                pending = (len(delivered) - 1, dev)
            n += 1
        with TraceAnnotation("bench.drain"):
            jax.block_until_ready((heads, opt, loss))
        t_end = time.perf_counter()
    _COMPILES.on = False
    window_s = t_end - t0
    stats1 = pipe.stats().get("io", {})
    memory_peak = _memory(devices)
    reading = tracer.stop(devices) if tracer else None
    batch_size = int(tr["batch_size"])
    log(f"[window] {n} batches ({n * batch_size} cells) in {window_s:.3f} s; "
        f"{_COMPILES.count} compilations inside it")
    log(f"[memory] device peak after the window: {memory_peak} B; "
        f"in use at its end: {_memory(devices, 'bytes_in_use')} B")
    if pending is not None:
        kept.append((pending[0], _to_host(pending[1])))
    stalls = [(i, w) for i, w in enumerate(waits) if w > STALL_S]
    log(f"[window] waits over {STALL_S * 1e3:.0f} ms (batch, s): "
        + ", ".join(f"{i}: {w:.3f}" for i, w in stalls))

    # ---- the check, once the window has closed and the program's state is freed
    final_loss = float(loss)
    del heads, opt, loss, dev, batch, pending
    pipe.close()
    del pipe, it
    t = time.perf_counter()
    checks, failed = _check(cell, consumer, fmt, data_dir, seed, delivered, kept,
                            snaps, losses, n)
    del kept, snaps
    checks["window_compiles"] = {"value": _COMPILES.count, "limit": 0}
    checks["final_loss_finite"] = {"value": int(not np.isfinite(final_loss)), "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    log(f"[check] {time.perf_counter() - t:.3f} s")

    result = {"correct": bool(correct), "attempted": n, "failed": failed}
    if trace:
        ctx = Reading(reading, n, stats0, stats1, feed,
                      "jit_" + consumer.STEP_NAME, devices[0].device_kind)
        metrics = {}
        for m in cell.per_layer:
            value = load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        dev_info = {**device_info(cell.chips), "memory_peak_bytes": memory_peak,
                    "busy_s": reading.busy_s, "window_s": reading.window_s}
        result["device"] = dev_info
        result["breakdown"] = reading.breakdown()
    else:
        wait_ms = sorted(w * 1e3 for w in waits)
        values = {
            "cells_per_s": n * batch_size / window_s,
            "batch_wait_p95_ms": statistics.quantiles(wait_ms, n=20)[-1] if len(wait_ms) > 1
            else wait_ms[0],
            "setup_s": setup_s,
        }
        result["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = {**device_info(cell.chips), "memory_peak_bytes": memory_peak}
    for name, c in checks.items():
        log(f"[limit] {name}: {c['value']!r} (limit {c['limit']!r})")
    result["checks"] = checks
    shutil.rmtree(data_dir, ignore_errors=True)
    return result


def _check(cell: Cell, consumer, fmt, data_dir: str, seed: int, delivered: list,
           kept: list, snaps: dict, losses: list, n_window: int):
    """Compare with the plain reference; returns (checks, failed batches)."""
    cfg, tr = cell.config, cell.traffic
    plates = fmt.read_plates(data_dir)
    rows = reference.Rows(plates, int(cfg["n_genes"]))
    want = reference.batches(int(rows.offsets[-1]), int(tr["strategy_params"]["block_size"]),
                             int(tr["batch_size"]), int(tr["fetch_factor"]), int(seed))
    expected = [next(want) for _ in delivered]
    bad_rows = {i for i, (got, exp) in enumerate(zip(delivered, expected))
                if not np.array_equal(got, exp)}

    bad_values = 0
    bad_batches = set()
    for i, got in kept:
        ids = expected[i]
        diff = int(np.count_nonzero(got["x"] != rows.dense(ids)))
        for t in consumer.TASKS:
            diff += int(np.count_nonzero(got[t] != rows.labels(ids, t)))
        bad_values += diff
        if diff:
            bad_batches.add(i)

    ref_batches = [{"x": rows.dense(ids), **{t: rows.labels(ids, t) for t in consumer.TASKS}}
                   for ids in expected[:REFERENCE_STEPS]]
    ref = consumer.reference_steps(snaps["heads0"], ref_batches)
    prog = consumer.program_readings(snaps["heads0"], snaps["opt1"], snaps["heads3"], losses)
    gaps = consumer.compare(prog, ref)
    log(f"[check] losses program {prog['losses']} reference {ref['losses']}")

    checks = {
        "rows_mismatched_batches": {"value": len(bad_rows), "limit": 0},
        "values_mismatched": {"value": bad_values, "limit": 0},
    }
    for name, value in gaps.items():
        checks[name] = {"value": value, "limit": consumer.LIMITS[name]}
    window_first = len(delivered) - n_window
    failed = len({i for i in bad_rows | bad_batches if i >= window_first})
    return checks, failed


class Reading:
    """What a per-layer reader may read: the reduced trace, the loader's
    counters over the window, and the window's batch count."""

    def __init__(self, trace, n_batches: int, stats0: dict, stats1: dict, feed,
                 step_module: str, device_kind: str):
        self.trace = trace
        self.step_module = step_module
        self.device_kind = device_kind
        self.n_batches = n_batches
        self.counters = {k: stats1[k] - stats0.get(k, 0) for k in stats1
                         if isinstance(stats1[k], (int, float))}
        self.feed_shapes = getattr(feed, "shapes", None)
