"""Pipeline parallelism: GPipe-style microbatch pipeline via shard_map + ppermute.

Completes the parallelism menu (DP / FSDP / TP / EP / SP / **PP**).  Stages
map onto a mesh axis; each device holds one stage's parameters (leading
stage dim sharded over the axis) and activations stream stage-to-stage with
``jax.lax.ppermute``.  The schedule is the classic GPipe loop: with M
microbatches and S stages, ``M + S - 1`` ticks; device s computes microbatch
``t - s`` at tick t (bubble ticks compute garbage that is masked out of the
output collection).

This is the communication pattern of the paper's §Appendix-B world applied
one level down: deterministic round-robin work assignment, here over stages
instead of fetches.  Used by ``tests/test_pipeline.py`` (toy stage stack vs
sequential reference) and available to configs as an alternative layout for
depth-dominated models; collective cost = one (mb, d) ppermute per tick
per stage boundary — O(M·S) point-to-point transfers that overlap with
stage compute on TPU.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["pipeline_apply"]


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,  # pytree, leaves (S, ...) — stage-major
    x: jax.Array,  # (M, mb, d) microbatched inputs
    *,
    mesh: Mesh,
    axis: str = "model",
) -> jax.Array:
    """Run x through S pipelined stages; returns (M, mb, d) outputs.

    ``stage_fn(params_for_one_stage, activations) -> activations`` must be
    shape-preserving across stages (classic equal-width pipeline).
    """
    S = mesh.shape[axis]
    M = x.shape[0]
    leaves = jax.tree.leaves(stage_params)
    if leaves and leaves[0].shape[0] != S:
        raise ValueError(
            f"stage_params leading dim {leaves[0].shape[0]} != pipeline size {S}"
        )

    def per_device(params_local, x_local):
        # params_local: (1, ...) this device's stage; x_local: full (M, mb, d)
        # (inputs replicated across the stage axis; only stage 0 consumes them)
        params_one = jax.tree.map(lambda l: l[0], params_local)
        sid = jax.lax.axis_index(axis)
        mb_shape = x_local.shape[1:]

        def tick(carry, t):
            state, outs = carry  # state: (mb, d) activation entering this stage
            # stage 0 ingests microbatch t (if valid), others take the carry
            mb_idx = jnp.clip(t, 0, M - 1)
            inject = jnp.equal(sid, 0)
            inp = jnp.where(inject, x_local[mb_idx], state)
            out = stage_fn(params_one, inp)
            # pass activations to the next stage (ring; last->0 wraps unused)
            nxt = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % S) for i in range(S)]
            )
            # last stage emits microbatch t - (S - 1) at tick t
            emit_idx = t - (S - 1)
            is_emit = jnp.logical_and(jnp.equal(sid, S - 1), emit_idx >= 0)
            outs = jax.lax.cond(
                is_emit,
                lambda o: o.at[jnp.clip(emit_idx, 0, M - 1)].set(out),
                lambda o: o,
                outs,
            )
            return (nxt, outs), None

        # the carries differ per stage: type them as varying over the axis
        state0 = jax.lax.pcast(jnp.zeros(mb_shape, x_local.dtype), axis, to="varying")
        outs0 = jax.lax.pcast(
            jnp.zeros((M,) + mb_shape, x_local.dtype), axis, to="varying"
        )
        (state, outs), _ = jax.lax.scan(
            tick, (state0, outs0), jnp.arange(M + S - 1)
        )
        # every device returns an outs buffer; only the last stage's is real.
        # psum with a mask keeps it SPMD-uniform and makes the result
        # replicated over the stage axis.
        mask = jnp.equal(sid, S - 1).astype(outs.dtype)
        return jax.lax.psum(outs * mask, axis)  # (M, mb, d)

    param_specs = jax.tree.map(lambda _: P(axis), stage_params)
    return jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
    )(stage_params, x)
