"""A Tahoe-100M-shaped atlas drawn from a seed, plate by plate.

Every cell holds ``total_counts`` counts drawn from a mixture of four gene
distributions: one shared by all cells, one per cell line, one per drug (its
fine MoA's genes plus its own) and one per plate (the batch effect).  Each
distribution is a table of ``TABLE_SIZE`` gene ids, so a draw is one random
16-bit index into a table: O(counts) per cell, and no dense row is ever made.
A cell's draws are then sorted and run-length encoded into its CSR row
(distinct genes, each with its count).

Within a plate, cells are sorted by condition (cell line, then drug), which
is Tahoe's layout and the reason block sampling keeps reads contiguous.
Labels are ``cell_line``, ``drug``, ``moa_fine``, ``moa_broad`` and
``plate``; ``cell_id`` is each cell's global row, so the correctness check
can tell which rows a batch holds.
"""
from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator

import numpy as np

TABLE_SIZE = 1 << 16  # entries per gene table: a draw is one uint16 index
CHUNK_CELLS = 2048  # cells drawn at once: 2048 x 4096 uint16 = 16 MiB
WORKERS = min(8, os.cpu_count() or 1)  # numpy releases the GIL in the heavy steps


@dataclasses.dataclass
class Plate:
    """One plate's cells as CSR plus its label columns (global row order)."""

    index: int
    data: np.ndarray  # (nnz,) float32 counts
    indices: np.ndarray  # (nnz,) int32 gene ids, ascending within a row
    indptr: np.ndarray  # (n_cells + 1,) int64
    obs: dict  # column -> (n_cells,) int32


def _table(genes: np.ndarray, weights: np.ndarray) -> np.ndarray:
    """Inverse CDF of ``weights`` over ``genes`` at TABLE_SIZE midpoints."""
    cdf = np.cumsum(weights, dtype=np.float64)
    cdf /= cdf[-1]
    mid = (np.arange(TABLE_SIZE, dtype=np.float64) + 0.5) / TABLE_SIZE
    pos = np.minimum(np.searchsorted(cdf, mid), len(genes) - 1)
    return genes[pos].astype(np.uint16)


def _signature_tables(rng, n: int, n_genes: int, size: int, sigma: float) -> np.ndarray:
    """``n`` tables, each over ``size`` genes of its own with log-normal weights."""
    out = np.empty((n, TABLE_SIZE), np.uint16)
    for i in range(n):
        genes = np.sort(rng.choice(n_genes, size=size, replace=False))
        out[i] = _table(genes, rng.lognormal(0.0, sigma, size))
    return out


def plate_sizes(cfg: dict) -> np.ndarray:
    fracs = np.asarray(cfg["plate_fracs"], np.float64)
    fracs = fracs / fracs.sum()
    sizes = np.floor(fracs * cfg["n_cells"]).astype(np.int64)
    sizes[-1] += cfg["n_cells"] - sizes.sum()
    return sizes


def generate(cfg: dict, seed: int) -> Iterator[Plate]:
    """Yield the atlas's plates in order; the same ``(cfg, seed)`` gives the
    same bytes."""
    g = cfg["generator"]
    n_genes = int(cfg["n_genes"])
    if n_genes > np.iinfo(np.uint16).max + 1:
        raise ValueError(f"n_genes {n_genes} does not fit the uint16 gene tables")
    draws = g["draws"]
    if sum(draws.values()) != cfg["total_counts"]:
        raise ValueError(f"draws {draws} do not sum to total_counts {cfg['total_counts']}")
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0xA71A5]))
    sig = g["signature_genes"]
    n_lines, n_drugs = int(cfg["n_cell_lines"]), int(cfg["n_drugs"])
    n_fine, n_broad = int(cfg["n_moa_fine"]), int(cfg["n_moa_broad"])
    n_plates = len(cfg["plate_fracs"])

    base = _table(np.arange(n_genes), rng.lognormal(0.0, g["base_sigma"], n_genes))
    lines = _signature_tables(rng, n_lines, n_genes, sig["line"], g["signature_sigma"])
    plates = _signature_tables(rng, n_plates, n_genes, sig["plate"], g["signature_sigma"])
    drug_fine = rng.integers(0, n_fine, n_drugs)
    fine_broad = rng.integers(0, n_broad, n_fine)
    moa_genes = [rng.choice(n_genes, size=sig["moa"], replace=False) for _ in range(n_fine)]
    drugs = np.empty((n_drugs, TABLE_SIZE), np.uint16)
    for d in range(n_drugs):
        own = rng.choice(n_genes, size=sig["drug"], replace=False)
        genes = np.concatenate([moa_genes[drug_fine[d]], own])
        drugs[d] = _table(genes, rng.lognormal(0.0, g["signature_sigma"], len(genes)))
    line_probs = rng.dirichlet(np.full(n_lines, g["plate_line_alpha"]), size=n_plates)

    offset = 0
    with ThreadPoolExecutor(WORKERS) as pool:
        for p, n_p in enumerate(plate_sizes(cfg)):
            n_p = int(n_p)
            cell_line = rng.choice(n_lines, size=n_p, p=line_probs[p])
            drug = rng.integers(0, n_drugs, n_p)
            order = np.lexsort((drug, cell_line))  # Tahoe layout: sorted by condition
            cell_line, drug = cell_line[order], drug[order]
            # each chunk draws from a stream of its own, so the bytes do not
            # depend on which thread ran it
            chunks = list(pool.map(
                lambda lo: _draw_rows(
                    np.random.default_rng(np.random.SeedSequence([int(seed), 0xA71A5, p, lo])),
                    draws, base, lines, cell_line[lo:lo + CHUNK_CELLS],
                    drugs, drug[lo:lo + CHUNK_CELLS], plates[p]),
                range(0, n_p, CHUNK_CELLS)))
            yield _plate(p, offset, chunks, cell_line, drug, drug_fine, fine_broad)
            offset += n_p


def _plate(p, offset, chunks, cell_line, drug, drug_fine, fine_broad) -> Plate:
    n_p = len(cell_line)
    data, indices, nnz = zip(*chunks)
    indptr = np.zeros(n_p + 1, np.int64)
    np.cumsum(np.concatenate(nnz), out=indptr[1:])
    fine = drug_fine[drug]
    return Plate(
        index=p,
        data=np.concatenate(data),
        indices=np.concatenate(indices),
        indptr=indptr,
        obs={
            "cell_id": np.arange(offset, offset + n_p, dtype=np.int32),
            "plate": np.full(n_p, p, np.int32),
            "cell_line": cell_line.astype(np.int32),
            "drug": drug.astype(np.int32),
            "moa_fine": fine.astype(np.int32),
            "moa_broad": fine_broad[fine].astype(np.int32),
        },
    )


def _draw_rows(rng, draws: dict, base, lines, line_of, drugs, drug_of, plate_tab):
    """CSR rows of one chunk of cells: (data, indices, nnz per row)."""
    n = len(line_of)
    genes = np.empty((n, sum(draws.values())), np.uint16)
    col = 0
    for part, flat, row_table in (("base", base, None), ("line", lines.ravel(), line_of),
                                  ("drug", drugs.ravel(), drug_of),
                                  ("plate", plate_tab, None)):
        k = draws[part]
        r = rng.integers(0, TABLE_SIZE, size=(n, k), dtype=np.uint16)
        if row_table is not None:  # each cell draws from its own line's / drug's table
            r = (row_table.astype(np.int64)[:, None] << 16) | r
        genes[:, col:col + k] = flat[r]
        col += k
    genes.sort(axis=1, kind="stable")  # radix sort for 16-bit keys
    new = np.ones(genes.shape, bool)
    new[:, 1:] = genes[:, 1:] != genes[:, :-1]
    starts = np.flatnonzero(new.ravel())
    counts = np.diff(starts, append=genes.size)
    return (counts.astype(np.float32), genes.ravel()[starts].astype(np.int32),
            new.sum(axis=1))
