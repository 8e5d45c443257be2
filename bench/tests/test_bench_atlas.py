"""The atlas generator: published widths, plate fractions, cardinalities,
Tahoe's layout, and the same bytes for the same seed."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src")) if p not in sys.path]

from bench import atlas  # noqa: E402
from bench.harness import BENCH  # noqa: E402


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "tahoe-csr.json")) as f:
        c = json.load(f)
    c["n_cells"] = 3000  # the only cut; every width stays as published
    return c


@pytest.fixture(scope="module")
def plates(cfg):
    return list(atlas.generate(cfg, 2**31 + 3))


def test_published_widths_and_cardinalities(cfg, plates):
    assert cfg["n_genes"] == 62_710 and cfg["total_counts"] == 4_096
    assert len(plates) == len(cfg["plate_fracs"]) == 14
    nnz = np.concatenate([np.diff(p.indptr) for p in plates])
    assert len(nnz) == cfg["n_cells"]
    assert 2_900 <= nnz.mean() <= 3_300  # ~3.1k distinct genes of 4,096 counts
    for p in plates:
        n = len(p.indptr) - 1
        assert p.indices.min() >= 0 and p.indices.max() < cfg["n_genes"]
        assert p.data.dtype == np.float32 and p.indices.dtype == np.int32
        totals = np.add.reduceat(p.data, p.indptr[:-1])
        np.testing.assert_array_equal(totals, cfg["total_counts"])
        for row in range(0, n, 97):  # genes ascend and are distinct within a row
            assert np.all(np.diff(p.indices[p.indptr[row]:p.indptr[row + 1]]) > 0)
        assert p.obs["cell_line"].max() < cfg["n_cell_lines"]
        assert p.obs["drug"].max() < cfg["n_drugs"]
        assert p.obs["moa_fine"].max() < cfg["n_moa_fine"]
        assert p.obs["moa_broad"].max() < cfg["n_moa_broad"]
        assert np.all(p.obs["plate"] == p.index)


def test_plate_fractions_and_condition_layout(cfg, plates):
    sizes = [len(p.indptr) - 1 for p in plates]
    np.testing.assert_array_equal(sizes, atlas.plate_sizes(cfg))
    fracs = np.asarray(cfg["plate_fracs"]) / np.sum(cfg["plate_fracs"])
    # floors leave at most one cell per plate to the last plate
    np.testing.assert_allclose(np.asarray(sizes) / cfg["n_cells"], fracs,
                               atol=len(sizes) / cfg["n_cells"])
    ids = np.concatenate([p.obs["cell_id"] for p in plates])
    np.testing.assert_array_equal(ids, np.arange(cfg["n_cells"]))
    for p in plates:  # sorted by condition: cell line, then drug
        key = p.obs["cell_line"].astype(np.int64) * 1000 + p.obs["drug"]
        assert np.all(np.diff(key) >= 0)


def test_deterministic_per_seed(cfg, plates):
    small = dict(cfg, n_cells=500)
    a = list(atlas.generate(small, 2**31 + 3))
    b = list(atlas.generate(small, 2**31 + 3))
    c = list(atlas.generate(small, 2**31 + 4))
    for pa, pb in zip(a, b):
        np.testing.assert_array_equal(pa.data, pb.data)
        np.testing.assert_array_equal(pa.indices, pb.indices)
        np.testing.assert_array_equal(pa.indptr, pb.indptr)
        for k in pa.obs:
            np.testing.assert_array_equal(pa.obs[k], pb.obs[k])
    assert any(not np.array_equal(pa.indices, pc.indices) for pa, pc in zip(a, c))
