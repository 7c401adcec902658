"""Dense GQA decoder: the Llama form that DeepSeek-Coder and Mistral-Large publish.

RMSNorm before attention and before a SwiGLU MLP, rotary embedding by
halves, grouped-query causal attention, an untied output head. The engine
holds it as one slot of layers (``LM.param_specs`` for a dense model);
RMSNorm gains are stored as ``w`` and applied as ``1 + w``, as the engine
does. The contract of this file is in ``families/__init__.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import HIGHEST, dot, rms_norm

Q_CHUNK = 256          # query rows per attention block; divides reference.PAD
BYTES = 2              # bfloat16 weights, caches and logits


def engine_fields(cfg: dict) -> dict:
    return dict(num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
                d_ff=cfg["intermediate_size"], norm_eps=cfg["rms_norm_eps"],
                rope_theta=cfg["rope_theta"], gated_mlp=True, qk_norm=False,
                moe=None, mamba=None, sliding_window=0)


def layout(cfg: dict) -> dict:
    L, D, V = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["vocab_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    F = cfg["intermediate_size"]
    return {
        "embed": (V, D), "unembed": (V, D), "final_norm": (D,),
        "slots.0.norm1": (L, D), "slots.0.wq": (L, D, q), "slots.0.wk": (L, D, kv),
        "slots.0.wv": (L, D, kv), "slots.0.wo": (L, q, D), "slots.0.norm2": (L, D),
        "slots.0.wi": (L, D, F), "slots.0.wg": (L, D, F), "slots.0.wo_mlp": (L, F, D),
    }


# ------------------------------------------------------------ reference
def _rope(x, theta):
    """x [T, heads, hd] at positions 0..T-1, rotated by halves."""
    T, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal grouped-query attention. q [T, H, hd]; k, v [T, KV, hd]."""
    T, H, hd = q.shape
    G = H // k.shape[1]
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    keys = jnp.arange(T)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * Q_CHUNK, Q_CHUNK)
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HIGHEST) * hd ** -0.5
        rows = i * Q_CHUNK + jnp.arange(Q_CHUNK)
        s = jnp.where(keys[None, None, :] <= rows[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(T // Q_CHUNK))
    return out.reshape(T, H, hd)


def layer(x, w, cfg: dict, quant: str):
    T = x.shape[0]
    H, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = rms_norm(x, w["norm1"], eps)
    q = _rope(dot(h, w["wq"], quant).reshape(T, H, hd), theta)
    k = _rope(dot(h, w["wk"], quant).reshape(T, KV, hd), theta)
    v = dot(h, w["wv"], quant).reshape(T, KV, hd)
    x = x + dot(_attention(q, k, v).reshape(T, H * hd), w["wo"], quant)
    h = rms_norm(x, w["norm2"], eps)
    gate = jax.nn.silu(dot(h, w["wg"], quant)) * dot(h, w["wi"], quant)
    return x + dot(gate, w["wo_mlp"], quant)


def head(x, top, cfg: dict, quant: str):
    h = rms_norm(x, top["final_norm"], cfg["rms_norm_eps"])
    return dot(h, top["unembed"].T, quant)


# --------------------------------------------------------------- counts
def _dims(cfg: dict):
    D, F, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return cfg["num_hidden_layers"], D, F, V, q, kv


def layer_params(cfg: dict) -> int:
    L, D, F, V, q, kv = _dims(cfg)
    return D * q + 2 * D * kv + q * D + 3 * D * F + 2 * D


def params(cfg: dict) -> int:
    L, D, F, V, q, kv = _dims(cfg)
    return L * layer_params(cfg) + 2 * V * D + D


def _matmul_flops_per_token(cfg: dict) -> int:
    L, D, F, V, q, kv = _dims(cfg)
    return L * 2 * (D * q + 2 * D * kv + q * D + 3 * D * F)


def prefill_flops(cfg: dict, n: int) -> int:
    L, D, F, V, q, kv = _dims(cfg)
    attn = L * 2 * q * n * (n + 1)          # QK^T and PV over the causal triangle
    return n * _matmul_flops_per_token(cfg) + attn + 2 * D * V


def decode_flops(cfg: dict, seen: list) -> int:
    L, D, F, V, q, kv = _dims(cfg)
    per_token = _matmul_flops_per_token(cfg) + 2 * D * V
    return sum(per_token + L * 4 * q * n for n in seen)


def decode_bytes(cfg: dict, seen: list) -> int:
    """Every weight once, the embedding rows of the batch, the cached keys
    and values of valid positions read once, the new ones and the logits
    written once."""
    L, D, F, V, q, kv = _dims(cfg)
    weights = L * layer_params(cfg) + V * D + D
    b = len(seen)
    kv_read = sum(L * 2 * kv * (n - 1) for n in seen)
    kv_write = b * L * 2 * kv
    return BYTES * (weights + b * D + kv_read + kv_write + b * V)
