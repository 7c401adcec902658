"""Seeded weights of a dense GQA decoder, drawn on the device.

The benchmark makes an image's weights from ``--seed`` and hands them to the
engine; the plain reference draws the same values again, layer by layer, from
the same seed. Each weight has a name; every layer of it is drawn from a key
folded from the seed, the name and the layer index, so one layer can be
redrawn alone.

The names and shapes are the engine's parameter tree (``LM.param_specs`` for
a dense model: layers stacked on a leading axis under ``slots.0``); the
harness checks the engine's tree against :func:`layout` before a run.
RMSNorm gains are stored as ``w`` and applied as ``1 + w``, as the engine
does.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

DTYPE = jnp.bfloat16


def layout(cfg: dict) -> dict:
    """Name -> shape of every weight; layer weights carry a leading layer axis."""
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


def base_key(seed: int) -> jax.Array:
    """Key of a seed of up to 64 bits (``PRNGKey`` alone keeps 32)."""
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def _std(name: str, shape) -> float:
    if name == "embed":
        return 1.0
    if name == "unembed":
        return shape[-1] ** -0.5
    return shape[-2] ** -0.5                 # matrices: 1/sqrt(fan_in)


def draw(key, name: str, shape) -> jax.Array:
    """One weight (one layer of it, for layer weights) from its key."""
    z = jax.random.normal(key, shape, jnp.float32)
    if "norm" in name:
        return (0.1 * z).astype(DTYPE)
    return (_std(name, shape) * z).astype(DTYPE)


def name_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()))


def _layer_key(key, name: str, layer):
    return jax.random.fold_in(name_key(key, name), layer)


def layer_weights(key, cfg: dict, layer) -> dict:
    """Every layer weight of layer ``layer`` (traceable)."""
    return {name.split(".")[-1]: draw(_layer_key(key, name, layer), name, shape[1:])
            for name, shape in layout(cfg).items() if name.startswith("slots.")}


def all_weights(key, cfg: dict) -> dict:
    """Every weight, flat by name, layer weights stacked (traceable). Each
    layer is drawn into its place in the stack, so no weight is ever held
    twice."""
    out = {}
    for name, shape in layout(cfg).items():
        if not name.startswith("slots."):
            out[name] = draw(name_key(key, name), name, shape)
            continue
        w = jnp.zeros(shape, DTYPE)
        for layer in range(shape[0]):
            w = w.at[layer].set(draw(_layer_key(key, name, layer), name, shape[1:]))
        out[name] = w
    return out


def top_weights(key, cfg: dict) -> dict:
    """The weights outside the layers (traceable)."""
    return {name: draw(name_key(key, name), name, shape)
            for name, shape in layout(cfg).items()
            if not name.startswith("slots.")}
