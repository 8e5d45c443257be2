"""The loader's own sharded CSR layout, read by ``sharded-csr://`` through memmap.

One directory per plate (``data.npy``, ``indices.npy``, ``indptr.npy``,
``obs.npz``, ``meta.json``) and a ``manifest.json`` listing them.  The
writer and the plain reader here use numpy alone.
"""
from __future__ import annotations

import json
import os

import numpy as np


def uri(root: str) -> str:
    return f"sharded-csr://{root}"


def write(root: str, plates, n_genes: int) -> None:
    names = []
    for p in plates:
        name = f"plate_{p.index:02d}"
        d = os.path.join(root, name)
        os.makedirs(d)
        np.save(os.path.join(d, "data.npy"), p.data)
        np.save(os.path.join(d, "indices.npy"), p.indices)
        np.save(os.path.join(d, "indptr.npy"), p.indptr)
        np.savez(os.path.join(d, "obs.npz"), **p.obs)
        with open(os.path.join(d, "meta.json"), "w") as f:
            json.dump({"n_obs": len(p.indptr) - 1, "n_var": int(n_genes), "plate": p.index}, f)
        names.append(name)
    with open(os.path.join(root, "manifest.json"), "w") as f:
        json.dump({"shards": names}, f)


def read_plates(root: str) -> list:
    """Per plate ``(indptr, data, indices, obs)``, data and indices as memmaps."""
    with open(os.path.join(root, "manifest.json")) as f:
        names = json.load(f)["shards"]
    out = []
    for name in names:
        d = os.path.join(root, name)
        obs = np.load(os.path.join(d, "obs.npz"))
        out.append((np.load(os.path.join(d, "indptr.npy")),
                    np.load(os.path.join(d, "data.npy"), mmap_mode="r"),
                    np.load(os.path.join(d, "indices.npy"), mmap_mode="r"),
                    {k: obs[k] for k in obs.files}))
    return out
