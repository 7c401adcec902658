"""Mamba-1 (selective SSM) block — falcon-mamba and the jamba hybrid.

Sequence mode uses a *chunked* scan: ``lax.scan`` over chunks carrying the
SSM state, with a numerically-stable ``lax.associative_scan`` inside each
chunk — the state tensor [B, chunk, d_inner, d_state] never exceeds one chunk
(a full-sequence associative scan at 32k × 8192 × 16 would be ~17 GB/device).
This mirrors the VMEM-chunked structure of the Pallas kernel in
``repro.kernels.mamba_scan``.

Decode mode is the O(1) recurrence: one state update per token; the "cache"
is (conv ring window, SSM state) — constant in sequence length, which is why
falcon-mamba/jamba run the long_500k cell.

Falcon-Mamba normalises dt, B and C after ``x_proj`` by a weightless RMS
norm (``MambaConfig.mixer_rms_eps``; 0 leaves it out, as Mamba-1 does).
The scopes ``mamba.conv``, ``mamba.scan`` and ``mamba.step`` name the causal
conv, the sequence scan and the one-token state update in the profiler's op
view and in HLO dumps.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import shard_act


def _ssm_chunk_scan(dt: jax.Array, xi: jax.Array, Bc: jax.Array, Cc: jax.Array,
                    A: jax.Array, h0: jax.Array, chunk: int,
                    unroll: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Fused chunked selective scan:  y_t = C_t · h_t,
    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t.

    dt, xi: [B, S, DI] f32; Bc, Cc: [B, S, N] f32; A: [DI, N]; h0: [B, DI, N].
    Returns (y [B, S, DI] f32, h_S).

    a/bx/h are built PER CHUNK inside the scan and y is contracted against C
    before the next chunk — the [B, S, DI, N] tensors never exist at full
    sequence length (an 88-layer jamba prefill materializing them measured
    198 GiB/device; fused: chunk-sized only). Mirrors the Pallas kernel's
    VMEM blocking (repro.kernels.mamba_scan).
    """
    B, S, DI = xi.shape
    chunk = min(chunk, S)
    if S % chunk:
        chunk = S
    nc = S // chunk

    def r(x):
        return x.reshape(B, nc, chunk, *x.shape[2:]).swapaxes(0, 1)
    dtc, xic, Bcc, Ccc = r(dt), r(xi), r(Bc), r(Cc)

    def combine(l, rgt):
        al, bl = l
        ar, br = rgt
        return al * ar, bl * ar + br

    def body(h, inputs):
        dt_c, xi_c, B_c, C_c = inputs                  # [B, c, ...]
        a = jnp.exp(dt_c[..., None] * A)               # [B, c, DI, N]
        bx = (dt_c * xi_c)[..., None] * B_c[:, :, None, :]
        aa, bb = jax.lax.associative_scan(combine, (a, bx), axis=1)
        h_all = aa * h[:, None] + bb                   # [B, c, DI, N]
        y = jnp.einsum("bcen,bcn->bce", h_all, C_c)    # [B, c, DI]
        return h_all[:, -1], y

    h_last, ys = jax.lax.scan(body, h0, (dtc, xic, Bcc, Ccc),
                              unroll=nc if unroll else 1)
    y = ys.swapaxes(0, 1).reshape(B, S, DI)
    return y, h_last


def _causal_conv(x: jax.Array, w: jax.Array, carry: jax.Array = None):
    """Depthwise causal conv. x: [B, S, DI]; w: [K, DI]; carry: [B, K-1, DI]."""
    K = w.shape[0]
    if carry is None:
        carry = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([carry, x], axis=1)             # [B, S+K-1, DI]
    out = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(K))
    new_carry = xp[:, -(K - 1):]
    return out, new_carry


def _rms(v: jax.Array, eps: float) -> jax.Array:
    """Weightless RMS normalisation over the last axis, in float32."""
    v32 = v.astype(jnp.float32)
    return (v32 * jax.lax.rsqrt(jnp.mean(v32 * v32, -1, keepdims=True) + eps)
            ).astype(v.dtype)


def _selection(xi: jax.Array, p: dict, m):
    """dt [..., DI] (f32, after softplus), B and C [..., N] of the conv's
    output xi [..., DI]."""
    bcdt = jnp.einsum("...e,er->...r", xi, p["x_proj"])  # [..., dt_rank+2N]
    dt, Bc, Cc = jnp.split(bcdt, [m.dt_rank, m.dt_rank + m.d_state], axis=-1)
    if m.mixer_rms_eps:
        dt, Bc, Cc = (_rms(v, m.mixer_rms_eps) for v in (dt, Bc, Cc))
    dt = jax.nn.softplus(jnp.einsum("...r,re->...e", dt, p["dt_proj"])
                         + p["dt_bias"]).astype(jnp.float32)
    return dt, Bc, Cc


def mamba_forward(x: jax.Array, p: dict, cfg, *, chunk: int = 256,
                  unroll: bool = False) -> Tuple[jax.Array, dict]:
    """Sequence mode. x: [B, S, D] -> (y [B, S, D], cache {conv, ssm})."""
    m = cfg.mamba
    B, S, D = x.shape
    xi = jnp.einsum("bsd,de->bse", x, p["in_x"])         # [B, S, DI]
    z = jnp.einsum("bsd,de->bse", x, p["in_z"])
    xi = shard_act(xi, ("act_batch", "act_seq", "act_mlp"))
    with jax.named_scope("mamba.conv"):
        xi, conv_carry = _causal_conv(xi, p["conv_w"])
    xi = jax.nn.silu(xi + p["conv_b"])

    dt, Bc, Cc = _selection(xi, p, m)                    # dt [B, S, DI]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))                      # [DI, N]
    with jax.named_scope("mamba.scan"):
        y, h_last = _ssm_chunk_scan(dt, xi.astype(jnp.float32),
                                    Bc.astype(jnp.float32),
                                    Cc.astype(jnp.float32), A,
                                    jnp.zeros((B, m.d_inner, m.d_state),
                                              jnp.float32), chunk, unroll)
    y = (y + xi.astype(jnp.float32) * p["D"].astype(jnp.float32)) \
        * jax.nn.silu(z.astype(jnp.float32))
    out = jnp.einsum("bse,ed->bsd", y.astype(x.dtype), p["out_proj"])
    return out, {"conv": conv_carry, "ssm": h_last.astype(jnp.float32)}


def mamba_decode(x: jax.Array, p: dict, cfg, cache: dict) -> Tuple[jax.Array, dict]:
    """One-token mode. x: [B, D]; cache {conv [B,K-1,DI], ssm [B,DI,N]}."""
    m = cfg.mamba
    xi = jnp.einsum("bd,de->be", x, p["in_x"])
    z = jnp.einsum("bd,de->be", x, p["in_z"])
    with jax.named_scope("mamba.conv"):
        xi3, conv_carry = _causal_conv(xi[:, None], p["conv_w"],
                                       cache["conv"].astype(xi.dtype))
    xi = jax.nn.silu(xi3[:, 0] + p["conv_b"])

    dt, Bc, Cc = _selection(xi, p, m)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    with jax.named_scope("mamba.step"):
        a = jnp.exp(dt[..., None] * A)                                # [B,DI,N]
        bx = dt[..., None] * Bc[:, None, :].astype(jnp.float32) * xi[..., None].astype(jnp.float32)
        h = a * cache["ssm"] + bx
        y = jnp.einsum("ben,bn->be", h, Cc.astype(jnp.float32))
    y = (y + xi.astype(jnp.float32) * p["D"].astype(jnp.float32)) \
        * jax.nn.silu(z.astype(jnp.float32))
    out = jnp.einsum("be,ed->bd", y.astype(x.dtype), p["out_proj"])
    return out, {"conv": conv_carry.astype(cache["conv"].dtype), "ssm": h}
