"""Kernel microbench — ref-path timings + interpret-mode validation deltas.

On this CPU container the Pallas kernels run in interpret mode (Python), so
wall-clock here measures the jnp REFERENCE path (what the dry-run lowers);
the kernel rows report max|err| vs the oracle across a shape sweep — the
quantity that must be 0-ish for the TPU deployment to be trustworthy.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit

from repro.kernels import ref
from repro.kernels.csr_to_dense import ell_to_dense
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssm_scan import ssm_scan


def _time(fn, *args, reps=5, **kw):
    fn(*args, **kw)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def run() -> None:
    rng = np.random.default_rng(0)

    # --- flash attention
    B, H, Hkv, S, D = 1, 4, 2, 512, 64
    q = jnp.asarray(rng.normal(0, 1, (B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (B, Hkv, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (B, Hkv, S, D)), jnp.float32)
    us = _time(ref.flash_attention_ref, q, k, v, causal=True)
    out_i = flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                            interpret=True)
    err = float(jnp.max(jnp.abs(out_i - ref.flash_attention_ref(q, k, v, causal=True))))
    emit("kernel_flash_attention", us, f"ref_path_us={us:.0f};interp_max_err={err:.2e}")

    # --- ELL decompress
    R, K, G = 256, 64, 2048
    vals = jnp.asarray(rng.normal(0, 1, (R, K)), jnp.float32)
    cols = jnp.asarray(rng.integers(-1, G, (R, K)), jnp.int32)
    us = _time(lambda v_, c_: ref.ell_to_dense_ref(v_, c_, G), vals, cols)
    out_i = ell_to_dense(vals, cols, n_cols=G, block_cols=256, interpret=True)
    err = float(jnp.max(jnp.abs(out_i - ref.ell_to_dense_ref(vals, cols, G))))
    emit("kernel_ell_to_dense", us, f"ref_path_us={us:.0f};interp_max_err={err:.2e}")

    # --- SSM scan
    Bsz, Sq, Dm, N = 2, 256, 128, 16
    x = jnp.asarray(rng.normal(0, 1, (Bsz, Sq, Dm)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (Bsz, Sq, Dm)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2.0, (Dm, N)), jnp.float32)
    Bc = jnp.asarray(rng.normal(0, 1, (Bsz, Sq, N)), jnp.float32)
    Cc = jnp.asarray(rng.normal(0, 1, (Bsz, Sq, N)), jnp.float32)
    Dd = jnp.asarray(rng.normal(0, 1, (Dm,)), jnp.float32)
    us = _time(ref.ssm_scan_ref, x, dt, A, Bc, Cc, Dd)
    y_i, h_i = ssm_scan(x, dt, A, Bc, Cc, Dd, block_d=64, chunk=64, interpret=True)
    y_r, h_r = ref.ssm_scan_ref(x, dt, A, Bc, Cc, Dd)
    err = max(float(jnp.max(jnp.abs(y_i - y_r))), float(jnp.max(jnp.abs(h_i - h_r))))
    emit("kernel_ssm_scan", us, f"ref_path_us={us:.0f};interp_max_err={err:.2e}")


if __name__ == "__main__":
    run()
