"""Plain float32 reference of a served model, layer by layer, and its low-precision controls.

The model's layers and head are its family's (``families/<reference>.py``);
this file runs them. It imports nothing of the engine and takes nothing the
engine made: it draws its weights from the seed with :mod:`bench.weights`,
one layer at a time, and runs every sequence alone, in float32 with matmuls
at ``HIGHEST`` precision.

The controls put the next precision below the configuration's bfloat16 in
the engine's place: with ``quant="fp8"`` (the control that sets the limits)
every matmul of the forward pass (each goes through :func:`dot`) takes
float8 e4m3 weights (a scale per output column) and activations (a scale per
row); ``quant="int8"`` does the same in int8, which reads too close to sound
runs to bound them (PERF.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.families import family

HIGHEST = jax.lax.Precision.HIGHEST
PAD = 512              # sequences run padded to a multiple of this


def _quantize(a, axis):
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(a / scale), scale


def dot(x, w, quant):
    """x [T, i] @ w [i, o] in float32."""
    w = w.astype(jnp.float32)
    if quant == "int8":
        qx, sx = _quantize(x, -1)
        qw, sw = _quantize(w, 0)
        return jnp.dot(qx, qw, precision=HIGHEST) * sx * sw
    if quant == "fp8":
        f8 = jnp.float8_e4m3fn
        sx = jnp.max(jnp.abs(x), -1, keepdims=True) / 448.0
        sw = jnp.max(jnp.abs(w), 0, keepdims=True) / 448.0
        qx = (x / sx).astype(f8).astype(jnp.float32)
        qw = (w / sw).astype(f8).astype(jnp.float32)
        return jnp.dot(qx, qw, precision=HIGHEST) * sx * sw
    return jnp.dot(x, w, precision=HIGHEST)


def rms_norm(x, g, eps):
    """RMSNorm with the gain stored as ``g`` and applied as ``1 + g``, as the
    engine stores a gain."""
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x * (1.0 + g.astype(jnp.float32))


def _frozen(cfg: dict):
    """The configuration's scalar keys, hashable: what a family may read."""
    return tuple((k, v) for k, v in cfg.items()
                 if v is None or isinstance(v, (bool, int, float, str)))


@functools.lru_cache
def _programs(fc, quant: str):
    """The family's layer and head, jitted, for one configuration and control."""
    cfg, fam = dict(fc), family(dict(fc))

    def layer(x, w):
        return fam.layer(x, w, cfg, quant)

    def head(x, top):
        return fam.head(x, top, cfg, quant)
    return jax.jit(layer), jax.jit(head)


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
    run_layer, run_head = _programs(fc, quant)
    top = draw_top(key)
    xs = []
    for s in seqs:
        T = -(-len(s) // PAD) * PAD
        toks = np.zeros(T, np.int32)
        toks[:len(s)] = s
        xs.append(top["embed"][jnp.asarray(toks)].astype(jnp.float32))
    for layer in range(cfg["num_hidden_layers"]):
        w = draw_layer(key, layer)
        xs = [run_layer(x, w) for x in xs]
        del w
    return [np.asarray(run_head(x[jnp.asarray(r)], top))
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
