"""AnnData ``.h5ad`` plate files (X as CSR), read by ``sharded-h5ad://``.

``driver=h5py`` is forced in the URI, so a run without h5py fails instead of
falling back to the loader's pure-Python HDF5 reader.  Datasets are written
contiguous and uncompressed, as ``anndata.write_h5ad`` does by default.  The
writer and the plain reader use h5py alone.
"""
from __future__ import annotations

import json
import os

import h5py
import numpy as np


def uri(root: str) -> str:
    return f"sharded-h5ad://{root}?driver=h5py"


def write(root: str, plates, n_genes: int) -> None:
    names = []
    for p in plates:
        name = f"plate_{p.index:02d}.h5ad"
        n_obs = len(p.indptr) - 1
        with h5py.File(os.path.join(root, name), "w") as f:
            f.attrs["encoding-type"] = "anndata"
            f.attrs["encoding-version"] = "0.1.0"
            x = f.create_group("X")
            x.attrs["encoding-type"] = "csr_matrix"
            x.attrs["encoding-version"] = "0.1.0"
            x.attrs["shape"] = np.array([n_obs, n_genes], np.int64)
            x.create_dataset("data", data=p.data)
            x.create_dataset("indices", data=p.indices)
            x.create_dataset("indptr", data=p.indptr)
            for group, index, cols in (("obs", np.arange(n_obs), p.obs),
                                       ("var", np.arange(n_genes), {})):
                g = f.create_group(group)
                g.attrs["encoding-type"] = "dataframe"
                g.attrs["encoding-version"] = "0.2.0"
                g.attrs["_index"] = "_index"
                g.attrs["column-order"] = list(cols)
                g.create_dataset("_index", data=index.astype(np.int64))
                for k, v in cols.items():
                    g.create_dataset(k, data=v)
        names.append(name)
    with open(os.path.join(root, "manifest.json"), "w") as f:
        json.dump({"shards": names}, f)


def read_plates(root: str) -> list:
    """Per plate ``(indptr, data, indices, obs)``, read whole with h5py."""
    with open(os.path.join(root, "manifest.json")) as f:
        names = json.load(f)["shards"]
    out = []
    for name in names:
        with h5py.File(os.path.join(root, name), "r") as f:
            obs = {k: f["obs"][k][:] for k in f["obs"].attrs["column-order"]}
            out.append((f["X/indptr"][:], f["X/data"][:], f["X/indices"][:], obs))
    return out
