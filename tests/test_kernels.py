"""Per-kernel shape/dtype sweeps vs the ref.py oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.csr_to_dense import ell_to_dense
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssm_scan import ssm_scan

RNG = np.random.default_rng(0)


# --------------------------------------------------------------- flash attn
@pytest.mark.parametrize("B,H,Hkv,S,T,D", [
    (1, 2, 2, 64, 64, 16),
    (2, 4, 2, 128, 128, 32),
    (1, 8, 1, 96, 160, 64),   # MQA, ragged S/T vs blocks
    (2, 2, 1, 64, 128, 32),
])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 48), (False, None)])
def test_flash_attention_sweep(B, H, Hkv, S, T, D, causal, window):
    if not causal and window is not None:
        pytest.skip("window implies causal here")
    q = jnp.asarray(RNG.normal(0, 1, (B, H, S, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (B, Hkv, T, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (B, Hkv, T, D)), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=32, block_k=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


def test_flash_attention_bf16():
    q = jnp.asarray(RNG.normal(0, 1, (1, 2, 64, 32)), jnp.bfloat16)
    k = jnp.asarray(RNG.normal(0, 1, (1, 2, 64, 32)), jnp.bfloat16)
    v = jnp.asarray(RNG.normal(0, 1, (1, 2, 64, 32)), jnp.bfloat16)
    out = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), atol=3e-2
    )


def test_flash_attention_q_offset_decode_tile():
    """Decode-style: 1 query at absolute position `off` over a long cache."""
    B, H, D, T = 1, 2, 32, 256
    off = 200
    q = jnp.asarray(RNG.normal(0, 1, (B, H, 8, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (B, H, T, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (B, H, T, D)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, q_offset=off,
                          block_q=8, block_k=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, q_offset=off)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=3e-5)


# ------------------------------------------------------------------- ELL
@pytest.mark.parametrize("R,K,G,br,bc", [
    (16, 8, 64, 128, 64),
    (200, 5, 100, 128, 32),  # ragged row tiles + ragged col tiles
    (8, 16, 512, 128, 128),
    (1, 1, 8, 128, 8),
])
def test_ell_to_dense_sweep(R, K, G, br, bc):
    vals = jnp.asarray(RNG.normal(0, 1, (R, K)), jnp.float32)
    cols = jnp.asarray(RNG.integers(-1, G, (R, K)), jnp.int32)
    out = ell_to_dense(vals, cols, n_cols=G, block_rows=br, block_cols=bc,
                       interpret=True)
    want = ref.ell_to_dense_ref(vals, cols, G)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6)


def test_ell_duplicate_columns_accumulate():
    vals = jnp.asarray([[1.0, 2.0, 3.0]], jnp.float32)
    cols = jnp.asarray([[4, 4, -1]], jnp.int32)
    out = ell_to_dense(vals, cols, n_cols=8, block_cols=8, interpret=True)
    assert float(out[0, 4]) == 3.0
    assert float(jnp.abs(out).sum()) == 3.0


def test_ell_matches_csr_batch(tmp_path):
    """End-to-end: CSRBatch.to_ell() -> kernel == CSRBatch.to_dense()."""
    from repro.data import write_csr_shard, CSRStore

    rng = np.random.default_rng(5)
    n, g = 64, 96
    lens = rng.integers(0, 9, n)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(lens, out=indptr[1:])
    data = rng.normal(0, 1, int(indptr[-1])).astype(np.float32)
    # canonical CSR: unique sorted columns per row
    indices = np.concatenate(
        [np.sort(rng.choice(g, size=int(l), replace=False)) for l in lens]
        or [np.empty(0)]
    ).astype(np.int32)
    p = str(tmp_path / "s")
    write_csr_shard(p, data, indices, indptr, g, {"plate": np.zeros(n, np.int32)})
    b = CSRStore(p)[np.arange(n)]
    vals, cols = b.to_ell()
    out = ell_to_dense(jnp.asarray(vals), jnp.asarray(cols), n_cols=g,
                       block_cols=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), b.to_dense(), atol=1e-6)


# ------------------------------------------------------------------- SSM
@pytest.mark.parametrize("B,S,Dm,N,bd,ch", [
    (1, 32, 16, 4, 16, 16),
    (2, 64, 32, 8, 16, 16),
    (1, 100, 64, 16, 64, 32),  # ragged seq vs chunk
    (2, 48, 16, 16, 8, 48),
])
def test_ssm_scan_sweep(B, S, Dm, N, bd, ch):
    x = jnp.asarray(RNG.normal(0, 1, (B, S, Dm)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (B, S, Dm)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2, (Dm, N)), jnp.float32)
    Bc = jnp.asarray(RNG.normal(0, 1, (B, S, N)), jnp.float32)
    Cc = jnp.asarray(RNG.normal(0, 1, (B, S, N)), jnp.float32)
    D = jnp.asarray(RNG.normal(0, 1, (Dm,)), jnp.float32)
    h0 = jnp.asarray(RNG.normal(0, 0.5, (B, Dm, N)), jnp.float32)
    y, hf = ssm_scan(x, dt, A, Bc, Cc, D, h0, block_d=bd, chunk=ch, interpret=True)
    yr, hr = ref.ssm_scan_ref(x, dt, A, Bc, Cc, D, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-4)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hr), atol=2e-4)


def test_ssm_kernel_matches_model_path():
    """Kernel == models/ssm.py chunked associative scan == sequential ref."""
    from repro.models.ssm import selective_scan

    B, S, Dm, N = 2, 64, 32, 8
    x = jnp.asarray(RNG.normal(0, 1, (B, S, Dm)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (B, S, Dm)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2, (Dm, N)), jnp.float32)
    Bc = jnp.asarray(RNG.normal(0, 1, (B, S, N)), jnp.float32)
    Cc = jnp.asarray(RNG.normal(0, 1, (B, S, N)), jnp.float32)
    D = jnp.asarray(RNG.normal(0, 1, (Dm,)), jnp.float32)
    y1, h1 = selective_scan(x, dt, A, Bc, Cc, D, chunk=16)
    y2, h2 = ssm_scan(x, dt, A, Bc, Cc, D, block_d=16, chunk=16, interpret=True)
    yr, hr = ref.ssm_scan_ref(x, dt, A, Bc, Cc, D)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(yr), atol=2e-4)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(yr), atol=2e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(hr), atol=2e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(hr), atol=2e-4)
