"""Drive the loader's main path once on a TPU and check what comes out.

    python chip_smoke.py            # one chip: data, cell and token phases
    python chip_smoke.py --chips 4  # four chips: data-parallel Fig. 5 step only

Phases (one process; the script never falls back to the CPU):

- device: prints the JAX version and the device; exits nonzero when the
  platform is not ``tpu``.
- data: generates a Tahoe-like atlas from ``--seed`` at the published widths
  (62,710 genes, ~3k nonzeros per cell, 14 plates, 50 cell lines, 380 drugs,
  4 broad / 27 fine MoA classes).  Only the cell count is cut; every cut is
  printed on a ``reduced:`` line.
- cell: ``Pipeline.from_uri("sharded-csr://...")`` -> host CSR batch ->
  device -> the paper's Fig. 5 step (four linear heads, Adam).  Each batch is
  also shipped as ELL at the dataset-level K and densified on the chip by the
  Pallas kernel, which must equal the host ``to_dense()`` bit for bit.  Step
  1's loss is checked against a float64 numpy reference.
- token: ``repro.launch.train.build_loader`` + ``train_loop`` at the full
  width of ``smollm-360m`` for a few steps; losses must be finite.
- ``--chips 4``: four rank pipelines (``.shard(r, 4)``), one global batch
  sharded over a ``("data",)`` mesh, the Fig. 5 step data-parallel with
  replicated heads, compared with the same global batches on one device.

Times printed here are informational, not benchmark numbers.  The last line
of standard output is one JSON object naming the device, printed only when
every phase passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import statistics
import sys
import tempfile
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [p for p in (_ROOT, os.path.join(_ROOT, "src")) if p not in sys.path]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.bench_fig5_classification import LR, TASKS, _train_step  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.data.synth import generate_tahoe_like, load_tahoe_like  # noqa: E402
from repro.distributed.dataio import put_batch  # noqa: E402
from repro.distributed.sharding import RULES_TRAIN  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.train import build_loader, train_loop  # noqa: E402
from repro.models import Model  # noqa: E402
from repro.pipeline import Pipeline  # noqa: E402

PAPER_FETCH_FACTOR = 256  # scDataset paper, Fig. 5: b=16, f=256


@dataclasses.dataclass(frozen=True)
class Sizes:
    n_cells: int = 50_000  # Tahoe-100M has ~100M; the only cut of the atlas
    n_genes: int = 62_710  # Tahoe-100M's published gene count
    total_counts: int = 4_096  # ~3.1k nonzeros per cell at 62,710 genes
    chunk: int = 512  # generator rows per block: ~0.5 GB of host memory
    batch: int = 64
    block_size: int = 16
    fetch_factor: int = PAPER_FETCH_FACTOR
    n_batches: int = 32
    arch: str = "smollm-360m"
    lm_batch: int = 8
    lm_seq: int = 128
    lm_steps: int = 5
    # --chips 4: each rank needs whole fetches of its own, so a smaller f
    # keeps the atlas (and its generation time on four chips) small
    dp_n_cells: int = 8_192
    dp_fetch_factor: int = 16
    dp_steps: int = 4


SIZES = Sizes()
REQUIRED_PLATFORM = "tpu"
KERNEL_BACKEND = "pallas"  # never "auto": that would resolve to the jnp reference off-TPU
DATA_ROOT = os.path.join(tempfile.gettempdir(), "repro_chip_smoke")
INIT_SCALE = 0.01  # random heads, so step 1's loss depends on the features
# TPU's default f32 matmul rounds its inputs to bfloat16: ~1e-4 relative on
# the summed loss at these widths, so 2e-3 leaves a 10x margin.
LOSS_RTOL = 2e-3
# data-parallel vs one device: the same step, the batch's rows summed in a
# different order.  Adam divides each gradient by its own running RMS, so an
# entry whose gradient is near 0 can move by a sizeable part of one step (LR)
# on a last-bit difference: a per-entry bound would be vacuous or flaky.  The
# heads are compared as a whole instead: their difference must stay under 1%
# of how far the steps moved them.
DP_HEAD_RTOL = 1e-2
DP_LOSS_RTOL = 1e-5


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or non-finite result."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


# ------------------------------------------------------------------ device
def check_device(min_count: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    print(f"[device] jax {jax.__version__}: platform={d.platform} "
          f"kind={d.device_kind!r} count={len(devs)}")
    if d.platform != REQUIRED_PLATFORM:
        raise SystemExit(
            f"chip_smoke: needs platform {REQUIRED_PLATFORM!r}, JAX found "
            f"{d.platform!r}; refusing to run on it"
        )
    if len(devs) < min_count:
        raise SystemExit(f"chip_smoke: needs {min_count} devices, found {len(devs)}")
    return info


# -------------------------------------------------------------------- data
def data_phase(n_cells: int, seed: int):
    s = SIZES
    root = os.path.join(DATA_ROOT, f"tahoe_{n_cells}x{s.n_genes}_tc{s.total_counts}_s{seed}")
    t0 = time.perf_counter()
    generate_tahoe_like(root, n_cells=n_cells, n_genes=s.n_genes,
                        total_counts=s.total_counts, chunk=s.chunk, seed=seed)
    gen_s = time.perf_counter() - t0
    store = load_tahoe_like(root)
    nnz = np.concatenate([np.diff(sh._indptr) for sh in store.shards])
    _check(len(store) == n_cells and store.n_var == s.n_genes and len(store.shards) == 14,
           f"atlas shape {len(store)}x{store.n_var} in {len(store.shards)} plates")
    print(f"[data] {len(store)} cells x {store.n_var} genes in {len(store.shards)} plates "
          f"at {root}: nnz/cell mean {nnz.mean():.1f} max {nnz.max()}, "
          f"ELL K {store.ell_width}, {store.avg_row_bytes:.0f} B/cell CSR, "
          f"generated or reused in {gen_s:.1f} s")
    print(f"reduced: cells {n_cells} (Tahoe-100M: ~100M); genes, nonzeros per "
          "cell, plates and label cardinalities at published widths")
    return root, store


def _pipeline(root: str, fetch_factor: int, seed: int, rank: int = 0, world: int = 1):
    return (
        Pipeline.from_uri(f"sharded-csr://{root}")
        .strategy("block", block_size=SIZES.block_size)
        .batch(SIZES.batch, fetch_factor=fetch_factor)
        .shard(rank, world)
        .seed(seed)
        .build()
    )


# -------------------------------------------------------------- Fig. 5 step
def _init_state(n_genes: int, seed: int) -> tuple[dict, dict]:
    rng = np.random.default_rng(seed)
    heads = {
        t: {"w": (rng.standard_normal((n_genes, c)) * INIT_SCALE).astype(np.float32),
            "b": np.zeros((c,), np.float32)}
        for t, c in TASKS.items()
    }
    zeros = jax.tree.map(np.zeros_like, heads)
    return heads, {"m": zeros, "v": zeros, "count": np.zeros((), np.int32)}


def _labels(batch) -> dict:
    return {t: np.asarray(batch.obs[t], np.int32) for t in TASKS}


def reference_loss(heads: dict, dense: np.ndarray, ys: dict) -> float:
    """Fig. 5 loss in float64 numpy: sum over tasks of mean cross-entropy."""
    x = np.log1p(dense.astype(np.float64))
    total = 0.0
    for t in TASKS:
        logits = x @ heads[t]["w"].astype(np.float64) + heads[t]["b"].astype(np.float64)
        top = logits.max(axis=1)
        lse = top + np.log(np.exp(logits - top[:, None]).sum(axis=1))
        total += float(np.mean(lse - logits[np.arange(len(x)), ys[t]]))
    return total


def _median_ms(ts) -> str:
    return f"{statistics.median(ts) * 1e3:.3f} ms" if ts else "n/a"


# -------------------------------------------------------------------- cell
def cell_phase(root: str, store, seed: int) -> None:
    s = SIZES
    if s.fetch_factor != PAPER_FETCH_FACTOR:
        print(f"reduced: fetch_factor {s.fetch_factor} (paper f={PAPER_FETCH_FACTOR})")
    K, G = store.ell_width, store.n_var
    heads_h, opt_h = _init_state(G, seed)
    heads, opt = jax.device_put(heads_h), jax.device_put(opt_h)
    losses, step_s, kernel_s, host_s = [], [], [], []
    with _pipeline(root, s.fetch_factor, seed) as pipe:
        it = iter(pipe)
        for i in range(s.n_batches):
            t0 = time.perf_counter()
            batch = next(it)
            dense = batch.to_dense()
            vals, cols = batch.to_ell(k_max=K)
            ys_h = _labels(batch)
            host_s.append(time.perf_counter() - t0)
            _check(dense.shape == (s.batch, G), f"batch {i}: dense shape {dense.shape}")

            x_dev = jax.device_put(dense)
            ys = jax.device_put(ys_h)
            # on-chip densify of the same batch must equal the host densify
            vals_d, cols_d = jax.device_put(vals), jax.device_put(cols)
            t0 = time.perf_counter()
            on_chip = ops.ell_to_dense(vals_d, cols_d, n_cols=G, backend=KERNEL_BACKEND)
            on_chip.block_until_ready()
            kt = time.perf_counter() - t0
            if i == 0:
                print(f"[cell] ell_to_dense ({s.batch}x{K} -> {s.batch}x{G}, "
                      f"backend={KERNEL_BACKEND}) first call incl. compile: {kt:.3f} s")
            else:
                kernel_s.append(kt)
            _check(bool(jnp.array_equal(on_chip, x_dev)),
                   f"batch {i}: on-chip ELL densify differs from host to_dense()")

            t0 = time.perf_counter()
            x = jnp.log1p(x_dev)
            heads, opt, loss = _train_step(heads, opt, x, ys)
            loss.block_until_ready()
            st = time.perf_counter() - t0
            if i == 0:
                print(f"[cell] first Fig. 5 step incl. trace + compile: {st:.3f} s")
                want = reference_loss(heads_h, dense, ys_h)
                got = float(loss)
                print(f"[cell] step 1 loss {got:.6f} vs float64 reference {want:.6f} "
                      f"(rel err {abs(got - want) / abs(want):.2e}, rtol {LOSS_RTOL})")
                _check(math.isclose(got, want, rel_tol=LOSS_RTOL),
                       f"step 1 loss {got} != reference {want}")
            else:
                step_s.append(st)
            losses.append(float(loss))
    _check(all(math.isfinite(v) for v in losses), f"non-finite Fig. 5 loss: {losses}")
    print(f"[cell] {len(losses)} batches: ELL densify bitwise equal to host on every "
          f"batch; losses finite, {losses[0]:.4f} -> {losses[-1]:.4f}")
    print(f"[cell] median after warm-up (informational): Fig. 5 step "
          f"{_median_ms(step_s)}, ell_to_dense {_median_ms(kernel_s)}, host batch "
          f"(fetch + to_dense + to_ell) {_median_ms(host_s[1:])}")


# ------------------------------------------------------------------- token
def token_phase(seed: int) -> None:
    s = SIZES
    cfg = get_config(s.arch)
    model = Model(cfg)
    vocab = min(cfg.vocab_size, 1024)
    print(f"[token] {cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
          f"vocab {cfg.vocab_size}, {model.n_params() / 1e6:.1f}M params (random, seed {seed})")
    print(f"reduced: {s.lm_steps} steps at batch {s.lm_batch} x seq {s.lm_seq}; "
          f"token ids drawn from the first {vocab} of {cfg.vocab_size}")
    loader = build_loader(os.path.join(DATA_ROOT, "corpus"), s.lm_seq, s.lm_batch,
                          vocab_size=vocab, seed=seed)
    t0 = time.perf_counter()
    try:
        res = train_loop(model, loader, steps=s.lm_steps, log_every=1, seed=seed)
    finally:
        loader.close()
    losses = [m["loss"] for m in res["metrics"]]
    print(f"[token] {s.lm_steps} steps incl. compile: {time.perf_counter() - t0:.1f} s "
          "(informational)")
    _check(len(losses) == s.lm_steps and all(math.isfinite(v) for v in losses),
           f"token losses {losses}")


# ---------------------------------------------------------- data-parallel
def dp_phase(root: str, seed: int) -> None:
    s = SIZES
    if s.dp_fetch_factor != PAPER_FETCH_FACTOR:
        print(f"reduced: fetch_factor {s.dp_fetch_factor} (paper f={PAPER_FETCH_FACTOR})")
    mesh = make_host_mesh()
    world = mesh.size
    one = jax.devices()[0]
    replicated = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    heads_h, opt_h = _init_state(SIZES.n_genes, seed)
    heads_dp = jax.device_put(heads_h, replicated)
    opt_dp = jax.device_put(opt_h, replicated)
    heads_1, opt_1 = jax.device_put(heads_h, one), jax.device_put(opt_h, one)
    pipes = [_pipeline(root, s.dp_fetch_factor, seed, r, world) for r in range(world)]
    try:
        its = [iter(p) for p in pipes]
        for step in range(s.dp_steps):
            parts = [next(it) for it in its]
            dense = np.concatenate([b.to_dense() for b in parts])
            ys_h = {t: np.concatenate([_labels(b)[t] for b in parts]) for t in TASKS}
            gb = put_batch({"x": dense, **ys_h}, mesh, RULES_TRAIN)
            if step == 0:
                shards = sorted(gb["x"].addressable_shards, key=lambda sh: sh.index[0].start)
                _check(len({sh.device for sh in shards}) == world,
                       "global batch does not span every device")
                for r, sh in enumerate(shards):
                    rows = sh.index[0]
                    print(f"[dp] rank {r}: rows {rows.start}:{rows.stop} on {sh.device}")
                    _check(np.array_equal(np.asarray(sh.data), parts[r].to_dense()),
                           f"device {sh.device} does not hold rank {r}'s batch")
            heads_dp, opt_dp, loss_dp = _train_step(
                heads_dp, opt_dp, jnp.log1p(gb["x"]), {t: gb[t] for t in TASKS})
            heads_1, opt_1, loss_1 = _train_step(
                heads_1, opt_1, jnp.log1p(jax.device_put(dense, one)),
                jax.device_put(ys_h, one))
            l_dp, l_1 = float(loss_dp), float(loss_1)
            print(f"[dp] step {step + 1}: loss {world} devices {l_dp:.6f}, "
                  f"one device {l_1:.6f}")
            _check(math.isfinite(l_dp) and math.isclose(l_dp, l_1, rel_tol=DP_LOSS_RTOL),
                   f"step {step + 1}: data-parallel loss {l_dp} != one-device {l_1}")
    finally:
        for p in pipes:
            p.close()
    flat = lambda tree: np.concatenate([np.asarray(a, np.float64).ravel()
                                        for a in jax.tree.leaves(tree)])
    h_dp, h_1, h_0 = flat(heads_dp), flat(heads_1), flat(heads_h)
    rel = float(np.linalg.norm(h_dp - h_1) / np.linalg.norm(h_1 - h_0))
    print(f"[dp] heads after {s.dp_steps} steps: |data-parallel - one device| / "
          f"|one device - init| = {rel:.3e} (rtol {DP_HEAD_RTOL}); max entry "
          f"difference {np.max(np.abs(h_dp - h_1)):.3e} (LR {LR})")
    _check(rel <= DP_HEAD_RTOL, f"data-parallel heads differ by {rel:.3e} of their move")


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip data-parallel phase")
    args = ap.parse_args(argv)

    device = check_device(args.chips)
    print(f"[device] compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        root, _ = data_phase(SIZES.dp_n_cells, args.seed)
        dp_phase(root, args.seed)
    else:
        root, store = data_phase(SIZES.n_cells, args.seed)
        cell_phase(root, store, args.seed)
        token_phase(args.seed)
    print(f"[done] all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
