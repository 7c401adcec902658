"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal=True, window=0):
    """Materialized-scores attention. q [B,S,H,hd]; k,v [B,S,KV,hd]."""
    from repro.models.attention import attend_plain
    return attend_plain(q, k, v, causal=causal, window=window)


def decode_attention_ref(q, k_cache, v_cache, positions, *, ring=False):
    from repro.models.attention import attend_decode
    return attend_decode(q, k_cache, v_cache, positions, ring=ring, impl="ref")


def mamba_scan_ref(dt, x, B, C, A, D):
    """Sequential recurrence in f32 from a zero state, one step per position.
    Shapes as the kernel wrapper's; returns (y [Bt, S, DI], h_S [Bt, DI, N])."""
    Bt, S, DI = x.shape
    f32 = jnp.float32
    dt, x = dt.astype(f32), x.astype(f32)
    a = jnp.exp(dt[..., None] * A.astype(f32))
    bx = (dt * x)[..., None] * B.astype(f32)[:, :, None, :]

    def step(h, inp):
        at, bxt, ct = inp
        h = at * h + bxt
        return h, jnp.sum(h * ct[:, None, :], axis=-1)

    h0 = jnp.zeros((Bt, DI, A.shape[1]), f32)
    h_last, ys = jax.lax.scan(step, h0, (a.swapaxes(0, 1), bx.swapaxes(0, 1),
                                         C.astype(f32).swapaxes(0, 1)))
    return ys.swapaxes(0, 1) + x * D.astype(f32), h_last


def grouped_matmul_ref(x, w, block_to_expert, block_t):
    """Row-block i uses expert block_to_expert[i]."""
    T, D = x.shape
    nt = T // block_t
    xb = x.reshape(nt, block_t, D)
    wb = w[block_to_expert]                        # [nt, D, F]
    y = jnp.einsum("ntd,ndf->ntf", xb, wb)
    return y.reshape(T, -1).astype(x.dtype)
