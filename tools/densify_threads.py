"""Time ``CSRBatch.to_dense`` at Tahoe-100M's widths for each thread cap.

    PYTHONPATH=src python tools/densify_threads.py [--rows 64 256] \\
        [--caps 1 2 4 8] [--reps 40] [--seed 0]

Builds a batch of ``rows`` cells of 62,710 genes with ~3.1k nonzeros a cell,
carrying a ``BufferPool`` as a fetched batch does, and prints one JSON line
per (rows, cap): the cap (``DENSE_WORKERS``, set here for the sweep only),
the workers it gives, and the median and quartiles of ``to_dense``'s wall
time in ms; a first line gives the CPUs this process may use.  It imports
no JAX, so it needs no accelerator: it measures the host it runs on.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import time

import numpy as np

from repro.data import csr_store
from repro.data.csr_store import BufferPool, CSRBatch

N_GENES = 62_710
NNZ = (2_800, 3_400)  # nonzeros a cell, uniform


def batch(rows: int, rng: np.random.Generator) -> CSRBatch:
    lens = rng.integers(*NNZ, rows)
    indptr = np.zeros(rows + 1, np.int64)
    np.cumsum(lens, out=indptr[1:])
    indices = np.concatenate(
        [np.sort(rng.choice(N_GENES, int(k), replace=False)) for k in lens]
    ).astype(np.int32)
    data = rng.random(int(indptr[-1]), dtype=np.float32)
    return CSRBatch(data, indices, indptr, N_GENES, {}, pool=BufferPool())


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, nargs="+", default=[64, 256])
    ap.add_argument("--caps", type=int, nargs="+", default=[1, 2, 4, 8])
    ap.add_argument("--reps", type=int, default=40)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    print(json.dumps({"cpus": len(os.sched_getaffinity(0)),
                      "cpu_count": os.cpu_count()}), flush=True)
    rng = np.random.default_rng(args.seed)
    for rows in args.rows:
        b = batch(rows, rng)
        want = b.to_dense()
        for cap in args.caps:
            csr_store.DENSE_WORKERS = cap
            if csr_store._dense_pool is not None:
                csr_store._dense_pool.shutdown()
                csr_store._dense_pool = None  # the next is cap - 1 threads
            got = b.to_dense()
            assert np.array_equal(got, want)
            del got
            times = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                x = b.to_dense()
                times.append(time.perf_counter() - t0)
                del x
            q1, med, q3 = statistics.quantiles(times, n=4)
            workers = csr_store._dense_workers(rows, rows * N_GENES * 4)
            print(json.dumps({"rows": rows, "cap": cap, "workers": workers,
                              "median_ms": round(1e3 * med, 3),
                              "q1_ms": round(1e3 * q1, 3),
                              "q3_ms": round(1e3 * q3, 3)}), flush=True)


if __name__ == "__main__":
    main()
