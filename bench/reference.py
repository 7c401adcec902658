"""Plain float32 reference of a dense GQA decoder, and its low-precision control.

The Llama form that DeepSeek-Coder and Mistral-Large publish: RMSNorm before
attention and before a SwiGLU MLP, rotary embedding by halves, grouped-query
causal attention, an untied output head. It imports nothing of the engine
and takes nothing the engine made: it draws its weights from the seed with
:mod:`bench.weights`, one layer at a time, and runs every sequence alone, in
float32 with matmuls at ``HIGHEST`` precision. RMSNorm gains are applied as
``1 + w``, the engine's storage of a gain (``weights.py``).

The controls put the next precision below the configuration's bfloat16 in
the engine's place: with ``quant="fp8"`` (the control that sets the limits)
every matmul of the forward pass takes float8 e4m3 weights (a scale per
output column) and activations (a scale per row); ``quant="int8"`` does the
same in int8, which reads too close to sound runs to bound them (PERF.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HIGHEST = jax.lax.Precision.HIGHEST
PAD = 512              # sequences run padded to a multiple of this
Q_CHUNK = 256          # query rows per attention block


def _quantize(a, axis):
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(a / scale), scale


def _dot(x, w, quant):
    """x [T, i] @ w [i, o] in float32."""
    w = w.astype(jnp.float32)
    if quant == "int8":
        xq, sx = _quantize(x, -1)
        wq, sw = _quantize(w, 0)
        return jnp.dot(xq, wq, precision=HIGHEST) * sx * sw
    if quant == "fp8":
        f8 = jnp.float8_e4m3fn
        sx = jnp.max(jnp.abs(x), -1, keepdims=True) / 448.0
        sw = jnp.max(jnp.abs(w), 0, keepdims=True) / 448.0
        xq = (x / sx).astype(f8).astype(jnp.float32)
        wq = (w / sw).astype(f8).astype(jnp.float32)
        return jnp.dot(xq, wq, precision=HIGHEST) * sx * sw
    return jnp.dot(x, w, precision=HIGHEST)


def _rms_norm(x, g, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x * (1.0 + g.astype(jnp.float32))


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


@functools.partial(jax.jit, static_argnames=("cfg", "quant"))
def _layer(x, w, *, cfg, quant):
    c = dict(cfg)
    T = x.shape[0]
    H, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    h = _rms_norm(x, w["norm1"], eps)
    q = _rope(_dot(h, w["wq"], quant).reshape(T, H, hd), theta)
    k = _rope(_dot(h, w["wk"], quant).reshape(T, KV, hd), theta)
    v = _dot(h, w["wv"], quant).reshape(T, KV, hd)
    x = x + _dot(_attention(q, k, v).reshape(T, H * hd), w["wo"], quant)
    h = _rms_norm(x, w["norm2"], eps)
    gate = jax.nn.silu(_dot(h, w["wg"], quant)) * _dot(h, w["wi"], quant)
    return x + _dot(gate, w["wo_mlp"], quant)


@functools.partial(jax.jit, static_argnames=("cfg", "quant"))
def _head(x, top, *, cfg, quant):
    h = _rms_norm(x, top["final_norm"], dict(cfg)["rms_norm_eps"])
    return _dot(h, top["unembed"].T, quant)


def _frozen(cfg: dict):
    keep = ("num_hidden_layers", "hidden_size", "intermediate_size", "vocab_size",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta")
    return tuple((k, cfg[k]) for k in keep)


@functools.lru_cache
def _drawers(fc):
    cfg = dict(fc)
    return (jax.jit(lambda key: weights.top_weights(key, cfg)),
            jax.jit(lambda key, layer: weights.layer_weights(key, cfg, layer)))


def logits(cfg: dict, seed: int, seqs: list, rows: list, quant: str = "none"):
    """Logits [len(rows[i]), V] of each token sequence ``seqs[i]`` at its
    positions ``rows[i]``, with the weights of ``seed``."""
    fc = _frozen(cfg)
    key = weights.base_key(seed)
    draw_top, draw_layer = _drawers(fc)
    top = draw_top(key)
    xs = []
    for s in seqs:
        T = -(-len(s) // PAD) * PAD
        toks = np.zeros(T, np.int32)
        toks[:len(s)] = s
        xs.append(top["embed"][jnp.asarray(toks)].astype(jnp.float32))
    for layer in range(cfg["num_hidden_layers"]):
        w = draw_layer(key, layer)
        xs = [_layer(x, w, cfg=fc, quant=quant) for x in xs]
        del w
    return [np.asarray(_head(x[jnp.asarray(r)], top, cfg=fc, quant=quant))
            for x, r in zip(xs, rows)]


def served_rows(prompt: np.ndarray, served: np.ndarray):
    """The sequence a request's served tokens were predicted from, and the
    positions whose logits predicted them."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    P = len(prompt)
    return seq, np.arange(P - 1, P - 1 + len(served))


def gap(ref: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far each token's reference logit lies below the reference's best."""
    return ref.max(-1) - ref[np.arange(len(tokens)), tokens]
