"""Seeded weights of a served model, drawn on the device.

The benchmark makes an image's weights from ``--seed`` and hands them to the
engine; the plain reference draws the same values again, layer by layer, from
the same seed. Each weight has a name; every layer of it is drawn from a key
folded from the seed, the name and the layer index, so one layer can be
redrawn alone.

The names, shapes and dtypes come from the configuration's family
(``families/<reference>.py``): the engine's parameter tree, layer weights
stacked on a leading axis under ``slots.0``. The harness checks the engine's
tree against :func:`layout` before a run.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from bench.families import family

DTYPE = "bfloat16"     # of every weight that the family's DTYPES leaves out


def layout(cfg: dict) -> dict:
    """Name -> (shape, dtype) of every weight; layer weights carry a leading
    layer axis."""
    fam = family(cfg)
    dtypes = getattr(fam, "DTYPES", {})
    return {n: (tuple(s), str(jnp.dtype(dtypes.get(n, DTYPE))))
            for n, s in fam.layout(cfg).items()}


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


def draw(key, name: str, shape, dtype) -> jax.Array:
    """One weight (one layer of it, for layer weights) from its key: norm
    gains 0.1 N(0, 1), the embedding N(0, 1), every other weight normal by
    its fan-in. A family with other rules defines its own ``draw``."""
    z = jax.random.normal(key, shape, jnp.float32)
    if "norm" in name:
        return (0.1 * z).astype(dtype)
    return (_std(name, shape) * z).astype(dtype)


def _drawer(cfg: dict):
    return getattr(family(cfg), "draw", draw)


def name_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()))


def _layer_key(key, name: str, layer):
    return jax.random.fold_in(name_key(key, name), layer)


def layer_weights(key, cfg: dict, layer) -> dict:
    """Every layer weight of layer ``layer`` (traceable)."""
    draw_ = _drawer(cfg)
    return {name.split(".")[-1]: draw_(_layer_key(key, name, layer), name, shape[1:], dt)
            for name, (shape, dt) in layout(cfg).items() if name.startswith("slots.")}


def all_weights(key, cfg: dict) -> dict:
    """Every weight, flat by name, layer weights stacked (traceable). Each
    layer is drawn into its place in the stack, so no weight is ever held
    twice."""
    draw_, out = _drawer(cfg), {}
    for name, (shape, dt) in layout(cfg).items():
        if not name.startswith("slots."):
            out[name] = draw_(name_key(key, name), name, shape, dt)
            continue
        w = jnp.zeros(shape, dt)
        for layer in range(shape[0]):
            w = w.at[layer].set(draw_(_layer_key(key, name, layer), name, shape[1:], dt))
        out[name] = w
    return out


def top_weights(key, cfg: dict) -> dict:
    """The weights outside the layers (traceable)."""
    draw_ = _drawer(cfg)
    return {name: draw_(name_key(key, name), name, shape, dt)
            for name, (shape, dt) in layout(cfg).items()
            if not name.startswith("slots.")}
