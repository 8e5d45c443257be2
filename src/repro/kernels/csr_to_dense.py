"""ELL → dense decompression — the scDataset ``fetch_transform`` hot-spot on TPU.

The paper converts CSR cell batches to dense on the host CPU.  At pod scale
the conversion belongs on-chip, but GPU-style scatter (one thread per
nonzero) has no TPU analogue: per-lane scatter into VMEM is not vectorizable.
The TPU-native rethink (DESIGN.md §2) is **compare-and-accumulate over
column tiles**: for a padded slab of nonzeros and a BC-wide column tile
resident in VMEM,

    dense[r, c] = Σ_k vals[r, k] * [cols[r, k] == c]

evaluated as K broadcast-compare-select sweeps of a register tile — pure VPU
work, no data-dependent addressing.  Work is O(R·K·G); profitable because
K ≪ G for scRNA (≈1–3k nnz vs 62,710 genes).

Layout.  Mosaic cannot slice the lane axis at a loop-carried offset, so the
kernel never reads "column k of an (R, K) slab".  It takes the slab as
(K, R) and reads row ``k`` along the sublane axis (``ref[pl.ds(k, 1), :]``),
a (1, BR) vector that broadcasts down a (BC, BR) tile of the TRANSPOSED
output.  The wrapper transposes the small ELL slab in and the dense result
out, so callers keep the (R, K) → (R, G) contract.

Tiles.  Rows sit on the 128-lane axis: ``block_rows`` is a multiple of 128,
or the whole (padded) row count when R is smaller.  ``block_cols`` sits on
the sublane axis and is a multiple of 8.  VMEM holds two (K, BR) input
blocks, double-buffered: at K=3456, BR=128 that is 4 × 1.77 MB = 7 MB, under
the 16 MB default scoped limit of v5e.

Grid: (rows/BR, G/BC); the ELL blocks revisit along the column grid axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ell_to_dense"]


def _kernel(vals_ref, cols_ref, out_ref, *, block_cols: int):
    col0 = pl.program_id(1) * block_cols
    K, BR = vals_ref.shape
    col_ids = col0 + jax.lax.broadcasted_iota(jnp.int32, (block_cols, BR), 0)

    def body(k, acc):
        c = cols_ref[pl.ds(k, 1), :]  # (1, BR) int32, -1 padding
        v = vals_ref[pl.ds(k, 1), :]
        return acc + jnp.where(c == col_ids, v, 0.0).astype(acc.dtype)

    acc = jnp.zeros((block_cols, BR), jnp.float32)
    acc = jax.lax.fori_loop(0, K, body, acc)
    out_ref[...] = acc.astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("n_cols", "block_rows", "block_cols", "interpret")
)
def ell_to_dense(
    vals: jax.Array,  # (R, K) float
    cols: jax.Array,  # (R, K) int32, -1 = padding
    *,
    n_cols: int,
    block_rows: int = 128,
    block_cols: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Decompress an ELL slab to a dense (R, n_cols) matrix on-chip."""
    R, K = vals.shape
    if cols.shape != (R, K):
        raise ValueError(f"cols shape {cols.shape} != vals shape {(R, K)}")
    if block_rows % 128 or block_cols % 8:
        raise ValueError(
            f"block_rows={block_rows} must be a multiple of 128 and "
            f"block_cols={block_cols} a multiple of 8 (TPU (8, 128) tiling)"
        )
    # rows on lanes: one full-width block when R is small, else 128-multiples
    br = block_rows if R > block_rows else R
    Rp = -(-R // br) * br
    Gp = -(-n_cols // block_cols) * block_cols
    vals_t = jnp.pad(vals.T, ((0, 0), (0, Rp - R)))
    cols_t = jnp.pad(cols.T, ((0, 0), (0, Rp - R)), constant_values=-1)
    out_t = pl.pallas_call(
        functools.partial(_kernel, block_cols=block_cols),
        grid=(Rp // br, Gp // block_cols),
        in_specs=[
            pl.BlockSpec((K, br), lambda i, j: (0, i)),
            pl.BlockSpec((K, br), lambda i, j: (0, i)),
        ],
        out_specs=pl.BlockSpec((block_cols, br), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((Gp, Rp), vals.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ell_to_dense",
    )(vals_t, cols_t)
    return out_t[:n_cols, :R].T
