"""``h5ad://`` — AnnData/HDF5 storage adapter (the paper's native format).

An ``.h5ad`` file stores the cell-by-gene matrix ``X`` as on-disk CSR —
``X/data`` (values), ``X/indices`` (gene ids), ``X/indptr`` (row offsets) —
plus per-cell metadata columns under ``obs``.  This adapter maps that layout
onto the :class:`~repro.data.backend.StorageAdapter` contract, so h5ad files
get the cross-shard planner, block cache, async execution and IOStats
accounting for free (see ``docs/adapters.md``, which uses this adapter as
its worked example).

Two interchangeable drivers:

- ``h5py`` — used when importable (real HDF5 library, full format support);
- ``shim`` — the pure-Python subset reader (:mod:`repro.data.h5shim`), used
  automatically when h5py is absent, so tests and CI never need the dep.
  Handles h5py-default and :func:`repro.data.synth.write_h5ad` files
  (contiguous or 1-D chunked/deflate/shuffle datasets).

Force one with ``open_collection("h5ad:///data/cells.h5ad?driver=shim")``.
Bare paths ending in ``.h5ad`` are sniffed: ``open_collection("/x/y.h5ad")``
works without a scheme.

Layout assumptions (checked at open): CSR orientation (``indptr`` length is
``n_obs + 1``), ``n_var`` from the ``X`` group's ``shape`` attribute with a
``var/_index`` length fallback.  ``indptr`` and obs columns are loaded into
RAM at open (small: O(n_obs)); ``data``/``indices`` are read on demand in
contiguous row ranges — exactly one byte-range per planner extent.  Obs
columns decode under BOTH drivers: plain datasets, variable-length strings
(global-heap reads in the shim), and anndata categorical subgroups
(``codes`` + ``categories``); anything else is skipped, not fatal.
"""
from __future__ import annotations

import os
from typing import Optional, Sequence

import numpy as np

from .backend import CSRCompositeAdapter, StorageAdapter, register_backend
from .csr_store import (
    BufferPool,
    CSRBatch,
    _concat_batches,
    gather_rows,
    gathered_nbytes,
)

__all__ = ["H5adStore", "H5adAdapter", "ShardedH5adAdapter"]

try:  # optional — the shim below is the no-dependency fallback
    import h5py  # type: ignore

    _HAVE_H5PY = True
except Exception:  # pragma: no cover - import guard
    h5py = None
    _HAVE_H5PY = False


def _as_str_array(col: np.ndarray) -> np.ndarray:
    """h5py returns vlen strings as object arrays of ``bytes``; normalize to
    a unicode array so both drivers hand consumers the same dtype."""
    if col.dtype.kind == "O":
        return np.array(
            [c.decode("utf-8") if isinstance(c, bytes) else str(c) for c in col],
            dtype=str,
        )
    return col


def _decode_categorical(codes: np.ndarray, categories: np.ndarray) -> np.ndarray:
    """anndata categorical -> label array: ``categories[codes]`` with the
    pandas missing sentinel (``codes == -1``) mapped to the empty string."""
    cats = np.asarray(categories)
    if cats.dtype.kind == "S":  # normalize: one label dtype per column
        cats = np.array([c.decode("utf-8") for c in cats], dtype=str)
    elif cats.dtype.kind == "O":
        cats = np.array(
            [c.decode("utf-8") if isinstance(c, bytes) else str(c) for c in cats],
            dtype=str,
        )
    codes = np.asarray(codes, dtype=np.int64)
    out = np.empty(len(codes), dtype=cats.dtype if cats.dtype.kind == "U" else object)
    valid = codes >= 0
    out[valid] = cats[codes[valid]]
    if cats.dtype.kind == "U":
        out[~valid] = ""
        return out
    out[~valid] = None
    return out


class H5adStore:
    """Row-range reader over one ``.h5ad`` file (CSR ``X`` + ``obs``)."""

    def __init__(self, path: str, driver: str = "auto"):
        if driver not in ("auto", "h5py", "shim"):
            raise ValueError(f"driver must be auto|h5py|shim, got {driver!r}")
        if driver == "h5py" and not _HAVE_H5PY:
            raise ImportError("driver='h5py' requested but h5py is not installed")
        if not os.path.exists(path):
            raise FileNotFoundError(path)
        self.path = path
        self.driver = "h5py" if (driver == "h5py" or (driver == "auto" and _HAVE_H5PY)) else "shim"
        if self.driver == "h5py":
            self._f = h5py.File(path, "r")
            self._data = self._f["X/data"]
            self._indices = self._f["X/indices"]
            x_attrs = dict(self._f["X"].attrs)
            indptr = np.asarray(self._f["X/indptr"][:], dtype=np.int64)
            obs_names = list(self._f["obs"].keys()) if "obs" in self._f else []
        else:
            from .h5shim import ShimFile

            self._f = ShimFile(path)
            self._data = self._f.dataset("X/data")
            self._indices = self._f.dataset("X/indices")
            x_attrs = self._f.attrs("X")
            indptr = np.asarray(self._f.dataset("X/indptr")[:], dtype=np.int64)
            obs_names = self._f.keys("obs") if self._has_group("obs") else []
        self._indptr = indptr
        self.n_obs = len(indptr) - 1
        self.n_var = self._resolve_n_var(x_attrs)
        enc = x_attrs.get("encoding-type")
        if enc is not None:
            enc = enc.decode() if isinstance(enc, bytes) else str(enc)
            if "csr" not in enc:
                raise ValueError(
                    f"{path}: X encoding {enc!r} is not CSR; only csr_matrix "
                    "h5ad layouts are supported"
                )
        self._obs = self._load_obs(obs_names)
        self._row_bytes = (
            (self._data.nbytes + self._indices.nbytes) / max(1, self.n_obs)
        )

    def _has_group(self, name: str) -> bool:
        try:
            return self._f.is_group(name)
        except KeyError:
            return False

    def _resolve_n_var(self, x_attrs: dict) -> int:
        shape = x_attrs.get("shape")
        if shape is not None and len(np.atleast_1d(shape)) == 2:
            return int(np.atleast_1d(shape)[1])
        # fallback: the var axis length (anndata always writes var/_index)
        try:
            if self.driver == "h5py":
                return int(self._f["var/_index"].shape[0])
            return int(self._f.dataset("var/_index").shape[0])
        except KeyError:
            raise ValueError(
                f"{self.path}: cannot determine n_var (no X 'shape' attribute "
                "and no var/_index dataset)"
            ) from None

    def _load_obs(self, names: Sequence[str]) -> dict:
        out: dict = {}
        for name in names:
            if name.startswith("_") or name == "index":
                continue  # axis index, not a label column
            col = self._load_obs_column(name)
            if col is not None and col.ndim == 1 and len(col) == self.n_obs:
                out[name] = col
        return out

    def _load_obs_column(self, name: str) -> Optional[np.ndarray]:
        """Decode ``obs/<name>`` under either driver, or None if unreadable.

        Plain datasets (numeric, fixed- or variable-length strings) load
        directly; anndata *categorical* columns are a subgroup holding
        ``codes`` (int, -1 = missing) + ``categories`` and decode to the
        label array a ``weights_obs``/``labels_obs``/``diversity_obs``
        consumer expects.  Anything else is skipped, not fatal."""
        path = f"obs/{name}"
        try:
            if self.driver == "h5py":
                node = self._f[path]
                if not hasattr(node, "shape"):  # subgroup
                    if "codes" in node and "categories" in node:
                        return _decode_categorical(
                            np.asarray(node["codes"][:]),
                            np.asarray(node["categories"][:]),
                        )
                    return None
                return _as_str_array(np.asarray(node[:]))
            if self._f.is_group(path):
                kids = set(self._f.keys(path))
                if {"codes", "categories"} <= kids:
                    return _decode_categorical(
                        np.asarray(self._f.dataset(f"{path}/codes")[:]),
                        np.asarray(self._f.dataset(f"{path}/categories")[:]),
                    )
                return None
            return np.asarray(self._f.dataset(path)[:])
        except (KeyError, NotImplementedError, TypeError):
            return None  # undecodable column: skip like before

    def __len__(self) -> int:
        return self.n_obs

    @property
    def obs(self) -> dict:
        return self._obs

    @property
    def avg_row_bytes(self) -> float:
        return self._row_bytes

    def read_range(self, start: int, stop: int, pool: BufferPool) -> CSRBatch:
        """ONE contiguous read of rows ``[start, stop)`` — a single
        ``data``/``indices`` byte range each (the planner's physical-read
        primitive; no stats recording here), into ``pool``'s buffers."""
        lo, hi = int(self._indptr[start]), int(self._indptr[stop])
        return CSRBatch(
            data=self._read_into(pool, self._data, lo, hi, np.float32),
            indices=self._read_into(pool, self._indices, lo, hi, self._indices.dtype),
            indptr=self._indptr[start:stop + 1].astype(np.int64) - lo,
            n_var=self.n_var,
            obs={k: v[start:stop] for k, v in self._obs.items()},
        )

    def _read_into(self, pool: BufferPool, dataset, lo: int, hi: int, dtype) -> np.ndarray:
        out = pool.empty(hi - lo, dtype)
        if self.driver == "h5py" and hi > lo:
            dataset.read_direct(out, np.s_[lo:hi])
        else:
            out[:] = dataset[lo:hi]
        return out

    def close(self) -> None:
        self._f.close()


class H5adAdapter(StorageAdapter):
    """AnnData ``.h5ad`` file behind the unified planner (CSR batch type)."""

    def __init__(self, store: H5adStore):
        self.store = store
        self.pool = BufferPool()  # read extents and gathered batches

    def __len__(self) -> int:
        return len(self.store)

    def read_range(self, start: int, stop: int) -> CSRBatch:
        return self.store.read_range(start, stop, self.pool)

    def take(self, piece: CSRBatch, rows: np.ndarray) -> CSRBatch:
        return piece[rows]

    def concat(self, pieces: Sequence[CSRBatch]) -> CSRBatch:
        return _concat_batches(list(pieces), self.store.n_var)

    def gather(self, sources: Sequence[tuple[CSRBatch, np.ndarray]]) -> CSRBatch:
        return gather_rows(sources, self.store.n_var, self.pool)

    def gather_nbytes(self, ranges: Sequence[tuple[CSRBatch, int, int]]) -> int:
        return gathered_nbytes(ranges)

    def end_fetch(self) -> None:
        self.pool.trim()

    def nbytes_of(self, rows: np.ndarray) -> int:
        rows = np.asarray(rows, dtype=np.int64)
        nnz = (self.store._indptr[rows + 1] - self.store._indptr[rows]).sum()
        per = self.store._data.dtype.itemsize + self.store._indices.dtype.itemsize
        return int(nnz) * per

    @property
    def avg_row_bytes(self) -> float:
        return self.store.avg_row_bytes

    @property
    def schema(self) -> dict:
        return {
            "kind": "csr",
            "n_obs": self.store.n_obs,
            "n_var": self.store.n_var,
            "obs_keys": list(self.store.obs.keys()),
            "driver": self.store.driver,
        }

    def obs_keys(self) -> list[str]:
        return list(self.store.obs.keys())

    def obs_column(self, key: str) -> np.ndarray:
        return self.store.obs[key]

    def close(self) -> None:
        self.store.close()


class ShardedH5adAdapter(CSRCompositeAdapter):
    """Many ``.h5ad`` plate files behind ONE row space (``sharded-h5ad://``).

    The composite the ROADMAP called for: a ``sharded-csr``-style manifest
    over real AnnData files.  Each plate is an :class:`H5adStore`; the
    boundary dispatch, batch algebra and nnz byte accounting are the shared
    :class:`~repro.data.backend.CSRCompositeAdapter` plumbing — the
    cross-shard planner merges runs *across plates in planning* and splits
    them back per file for execution, exactly like the sharded CSR store,
    but over HDF5 bytes.
    """

    def __init__(self, stores: Sequence[H5adStore]):
        if not stores:
            raise ValueError("need at least one h5ad shard")
        n_vars = {s.n_var for s in stores}
        if len(n_vars) != 1:
            raise ValueError(f"h5ad shards disagree on n_var: {n_vars}")
        super().__init__(stores, n_vars.pop())
        # obs columns every shard can decode (driver-dependent), same order
        keys = set(self.stores[0].obs.keys())
        for s in self.stores[1:]:
            keys &= set(s.obs.keys())
        self._obs_keys = [k for k in self.stores[0].obs.keys() if k in keys]

    @property
    def schema(self) -> dict:
        return {
            "kind": "csr",
            "n_obs": self.n_obs,
            "n_var": self.n_var,
            "n_shards": len(self.stores),
            "obs_keys": list(self._obs_keys),
            "driver": self.stores[0].driver,
        }

    def obs_keys(self) -> list[str]:
        return list(self._obs_keys)

    def obs_column(self, key: str) -> np.ndarray:
        if key not in self._obs_keys:
            raise KeyError(key)
        return np.concatenate([s.obs[key] for s in self.stores])

    def close(self) -> None:
        for s in self.stores:
            s.close()


@register_backend("h5ad")
def _open_h5ad(path: str, *, driver: str = "auto") -> H5adAdapter:
    return H5adAdapter(H5adStore(path, driver=str(driver)))


@register_backend("sharded-h5ad")
def _open_sharded_h5ad(path: str, *, driver: str = "auto") -> ShardedH5adAdapter:
    """``sharded-h5ad://<dir>`` (dir holding ``manifest.json`` with a
    ``shards`` list of ``.h5ad`` files), ``sharded-h5ad://<manifest.json>``
    directly, or comma-joined ``.h5ad`` paths.  Bare directories whose
    manifest lists ``.h5ad`` shards are sniffed (``open_collection("/dir")``
    works without a scheme)."""
    if "," in path:
        shard_paths = path.split(",")
    else:
        manifest_path = (
            path if path.endswith(".json") else os.path.join(path, "manifest.json")
        )
        import json

        with open(manifest_path) as f:
            manifest = json.load(f)
        base = os.path.dirname(manifest_path)
        shard_paths = [os.path.join(base, s) for s in manifest["shards"]]
    return ShardedH5adAdapter(
        [H5adStore(p, driver=str(driver)) for p in shard_paths]
    )
