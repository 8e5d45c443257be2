"""End-to-end training driver: scDataset block sampling → JAX train loop.

The paper's loader is the input pipeline: a memory-mapped token corpus is
block-sampled (BlockShuffling b, batched fetching f), the per-rank round-robin
fetch assignment feeds the data-parallel axis, and loader state rides in every
checkpoint so restarts resume mid-epoch deterministically.

Runs for real on the local CPU device with reduced configs::

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/run1

Resume after a crash (same command + --resume) continues bit-exactly.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config, smoke_config
from repro.core import LoaderState
from repro.data.tokens import generate_token_corpus
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.pipeline import DataPipeline, Pipeline
from repro.train.optimizer import AdamWConfig, warmup_cosine
from repro.train.step import make_train_state, make_train_step

__all__ = ["build_loader", "train_loop", "main"]


def build_loader(
    corpus_dir: str,
    seq_len: int,
    batch: int,
    *,
    block_size: int = 16,
    fetch_factor: int = 8,
    seed: int = 0,
    rank: int = 0,
    world_size: int = 1,
    n_tokens: int = 2_000_000,
    vocab_size: int = 1024,
    prefetch_workers: int = 0,
) -> DataPipeline:
    """The training input pipeline, declared through the Pipeline API.

    ``pipe.spec`` is the full serializable description of the stream; it
    rides in every checkpoint (``extra["data_spec"]``) and its fingerprint
    in the loader state, so a resumed run refuses a drifted data config.
    """
    generate_token_corpus(corpus_dir, n_tokens=n_tokens, vocab_size=vocab_size)
    return (
        Pipeline.from_uri(f"tokens://{corpus_dir}", seq_len=int(seq_len))
        .strategy("block", block_size=block_size)
        .batch(batch, fetch_factor=fetch_factor)
        .shard(rank, world_size)
        .seed(seed)
        .prefetch(workers=prefetch_workers)
        .build()
    )


def train_loop(
    model: Model,
    loader: DataPipeline,
    *,
    steps: int,
    ckpt_dir: str | None = None,
    ckpt_every: int = 20,
    resume: bool = False,
    lr: float = 3e-4,
    log_every: int = 10,
    seed: int = 0,
    crash_after: int | None = None,  # fault-injection hook (tests)
) -> dict:
    opt_cfg = AdamWConfig(
        lr=warmup_cosine(lr, warmup=max(1, steps // 20), total=steps),
        weight_decay=0.01,
        moment_dtype="float32",
    )
    step_fn = jax.jit(make_train_step(model, opt_cfg), donate_argnums=(0,))
    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None

    start_step = 0
    if resume and mgr and mgr.latest_step() is not None:
        template = jax.eval_shape(
            lambda k: make_train_state(model, k, opt_cfg), jax.random.PRNGKey(seed)
        )
        state, manifest = mgr.restore(template)
        loader.load_state(LoaderState.from_dict(manifest["loader_state"]))
        start_step = manifest["step"]
        print(f"[train] resumed at step {start_step}, loader {manifest['loader_state']}")
    else:
        state = make_train_state(model, jax.random.PRNGKey(seed), opt_cfg)

    it = iter(loader)
    metrics_hist = []
    t0 = time.time()
    step = start_step
    while step < steps:
        try:
            batch = next(it)
        except StopIteration:
            it = iter(loader)
            batch = next(it)
        jb = {
            "tokens": jnp.asarray(batch["tokens"]),
            "labels": jnp.asarray(batch["labels"]),
        }
        state, metrics = step_fn(state, jb)
        step += 1
        if step % log_every == 0 or step == steps:
            m = {k: float(v) for k, v in metrics.items()}
            metrics_hist.append({"step": step, **m})
            tput = jb["tokens"].size * log_every / max(1e-9, time.time() - t0)
            print(f"[train] step {step} loss={m['loss']:.4f} "
                  f"ce={m['ce_loss']:.4f} gnorm={m['grad_norm']:.2f} "
                  f"({tput:.0f} tok/s)")
            t0 = time.time()
        if mgr and (step % ckpt_every == 0 or step == steps):
            extra = {"arch": model.cfg.name}
            spec = getattr(loader, "spec", None)
            if spec is not None and spec.uri is not None:
                extra["data_spec"] = spec.to_dict()  # rebuildable input pipeline
            mgr.save(step, state, loader_state=loader.state().to_dict(),
                     extra=extra, blocking=True)
        if crash_after is not None and step >= crash_after:
            raise RuntimeError(f"injected crash at step {step}")
    return {"final_state": state, "metrics": metrics_hist, "last_step": step}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--fetch-factor", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--corpus", default="/tmp/repro_corpus")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.family in ("vlm", "encdec"):
        print(f"[train] note: {cfg.name} uses stub frontends; training the backbone "
              "on token data only is not meaningful — use examples/ for these.")
    model = Model(cfg)
    loader = build_loader(
        args.corpus, args.seq, args.batch,
        block_size=args.block_size, fetch_factor=args.fetch_factor,
        vocab_size=min(cfg.vocab_size, 1024),
    )
    res = train_loop(model, loader, steps=args.steps, ckpt_dir=args.ckpt_dir,
                     resume=args.resume, lr=args.lr)
    print(f"[train] done at step {res['last_step']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
