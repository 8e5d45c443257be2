"""Plain reference for what the loader delivers: which rows, in what order,
with what values.  It imports nothing of the loader.

Rows follow the scDataset paper's Algorithm 1 (arXiv:2506.01883) with the
loader's documented seeding: an epoch's order shuffles the ``block_size``-row
blocks of ``range(n)`` with ``default_rng(SeedSequence((seed, epoch,
0xB10C)))``; the order is cut into fetches of ``batch_size * fetch_factor``
rows, the ragged last fetch dropped; each fetch is sorted, permuted with
``default_rng(SeedSequence((seed, epoch, 0xF37C, fetch)))`` and cut into
whole batches.  Values come from the atlas files, read with numpy or h5py.
"""
from __future__ import annotations

from typing import Iterator

import numpy as np


def batches(n: int, block_size: int, batch_size: int, fetch_factor: int,
            seed: int) -> Iterator[np.ndarray]:
    """Global row ids of every batch, epoch after epoch, without end."""
    fetch = batch_size * fetch_factor
    epoch = 0
    while True:
        starts = np.arange(0, n, block_size, dtype=np.int64)
        np.random.default_rng(np.random.SeedSequence((seed, epoch, 0xB10C))).shuffle(starts)
        order = np.concatenate([np.arange(s, min(s + block_size, n)) for s in starts])
        for k in range(len(order) // fetch):
            rows = np.sort(order[k * fetch:(k + 1) * fetch], kind="stable")
            rng = np.random.default_rng(np.random.SeedSequence((seed, epoch, 0xF37C, k)))
            perm = rng.permutation(fetch)
            for j in range(fetch_factor):
                yield rows[perm[j * batch_size:(j + 1) * batch_size]]
        epoch += 1


class Rows:
    """Dense rows and labels of an atlas, from ``read_plates`` output."""

    def __init__(self, plates: list, n_genes: int):
        self.plates = plates
        self.n_genes = n_genes
        self.offsets = np.cumsum([0] + [len(p[0]) - 1 for p in plates])
        self.obs = {k: np.concatenate([p[3][k] for p in plates]) for k in plates[0][3]}

    def dense(self, ids: np.ndarray) -> np.ndarray:
        out = np.zeros((len(ids), self.n_genes), np.float32)
        for i, cell in enumerate(ids):
            p = int(np.searchsorted(self.offsets, cell, side="right")) - 1
            indptr, data, indices, _ = self.plates[p]
            lo, hi = indptr[cell - self.offsets[p]], indptr[cell - self.offsets[p] + 1]
            out[i, np.asarray(indices[lo:hi])] = data[lo:hi]
        return out

    def labels(self, ids: np.ndarray, name: str) -> np.ndarray:
        return self.obs[name][ids]
