"""Pallas kernels in the Pallas interpreter (interpret=True) vs pure-jnp
oracles, swept over shapes/dtypes. The compiled TPU lowering of the same
kernels is checked in test_tpu_compile.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref as R
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_scan import mamba_scan
from repro.kernels.moe_gmm import grouped_matmul

DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dt):
    return dict(rtol=2e-2, atol=2e-2) if dt == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)


@pytest.mark.slow
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("B,S,H,KV,hd,causal,window", [
    (2, 256, 4, 2, 64, True, 0),
    (1, 256, 4, 4, 128, False, 0),
    (2, 512, 8, 2, 64, True, 100),
    (1, 128, 2, 1, 32, True, 0),
])
def test_flash_attention_sweep(B, S, H, KV, hd, causal, window, dtype, rng):
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=128, interpret=True)
    ref = R.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.slow
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("B,W,H,KV,hd,ring", [
    (2, 256, 8, 2, 64, False),
    (3, 128, 4, 4, 32, True),
    (1, 512, 16, 2, 128, False),
])
def test_decode_attention_sweep(B, W, H, KV, hd, ring, dtype, rng):
    ks = jax.random.split(rng, 4)
    q = jax.random.normal(ks[0], (B, H, hd), dtype)
    kc = jax.random.normal(ks[1], (B, W, KV, hd), dtype)
    vc = jax.random.normal(ks[2], (B, W, KV, hd), dtype)
    pos = jax.random.randint(ks[3], (B,), 5, W * 2 if ring else W)
    out = decode_attention(q, kc, vc, pos, ring=ring, block_w=64,
                           interpret=True)
    ref = R.decode_attention_ref(q, kc, vc, pos, ring=ring)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.slow
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("B,S,DI,N,chunk,bd", [
    (2, 128, 64, 8, 32, 32),
    (1, 64, 128, 16, 64, 64),
    (2, 96, 32, 4, 32, 16),
])
def test_mamba_scan_sweep(B, S, DI, N, chunk, bd, dtype, rng):
    ks = jax.random.split(rng, 6)
    dt = (jax.nn.softplus(jax.random.normal(ks[0], (B, S, DI))) * 0.1).astype(dtype)
    x = jax.random.normal(ks[1], (B, S, DI), dtype)
    Bc = jax.random.normal(ks[2], (B, S, N), dtype)
    Cc = jax.random.normal(ks[3], (B, S, N), dtype)
    A = -jnp.exp(jax.random.normal(ks[4], (DI, N)) * 0.2)
    D = jax.random.normal(ks[5], (DI,))
    y = mamba_scan(dt, x, Bc, Cc, A, D, chunk=chunk, block_d=bd,
                   interpret=True)
    ref = R.mamba_scan_ref(dt, x, Bc, Cc, A, D)
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ref, np.float32), **tol)


@pytest.mark.slow
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("T,D,F,E,bt", [
    (512, 128, 256, 4, 64),
    (256, 64, 128, 8, 32),
])
def test_grouped_matmul_sweep(T, D, F, E, bt, dtype, rng):
    ks = jax.random.split(rng, 3)
    x = jax.random.normal(ks[0], (T, D), dtype)
    w = jax.random.normal(ks[1], (E, D, F), dtype)
    bmap = jax.random.randint(ks[2], (T // bt,), 0, E).astype(jnp.int32)
    y = grouped_matmul(x, w, bmap, block_t=bt, interpret=True)
    ref = R.grouped_matmul_ref(x, w, bmap, bt)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_kernel_is_tiled():
    """BlockSpec tiling: odd block sizes halve down to divide S."""
    q = jnp.zeros((1, 96, 2, 32), jnp.float32)
    k = jnp.zeros((1, 96, 1, 32), jnp.float32)
    out = flash_attention(q, k, k, causal=True, block_q=64, block_k=64,
                          interpret=True)
    assert out.shape == (1, 96, 2, 32)
