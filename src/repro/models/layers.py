"""Core layers, parameter specs, and the logical-axis annotation system.

Params are described once by :class:`ParamSpec` trees; ``abstract_params``,
``init_params`` and ``logical_axes`` all derive from the same spec so shapes,
shardings and initializers can never drift apart.

Activation sharding: models call :func:`shard_act` with *logical* dim names;
when a sharding context (mesh + rules) is active — set by the trainer or the
dry-run harness — this becomes ``with_sharding_constraint``; on bare CPU it is
the identity, so the same model code runs in unit tests.
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]      # logical axis name per dim (w_* names)
    init: str = "normal"                 # normal | zeros | ones | mamba_a | mamba_dt
    scale: float = 1.0                   # fan-in style scale for "normal"

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def abstract_param(spec: ParamSpec, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(spec.shape, dtype)


def init_param(spec: ParamSpec, rng, dtype) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init == "mamba_a":
        # A_log init: log of [1..d_state] broadcast over d_inner (mamba1 S4D-real)
        n = spec.shape[-1]
        a = jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))
        return jnp.broadcast_to(a, spec.shape).astype(dtype)
    if spec.init == "mamba_dt":
        # dt_proj bias: softplus^-1 of dt in [1e-3, 1e-1] log-uniform
        u = jax.random.uniform(rng, spec.shape, jnp.float32)
        dt = jnp.exp(u * (np.log(0.1) - np.log(1e-3)) + np.log(1e-3))
        return jnp.log(jnp.expm1(dt)).astype(dtype)
    if spec.init == "normal":
        fan_in = spec.shape[0] if len(spec.shape) >= 2 else max(spec.shape[-1], 1)
        if len(spec.shape) >= 3:  # stacked [L, fan_in, ...] or [E, fan_in, ...]
            fan_in = spec.shape[-2]
        std = spec.scale / np.sqrt(fan_in)
        return (std * jax.random.normal(rng, spec.shape, jnp.float32)).astype(dtype)
    raise ValueError(spec.init)


def build_params(specs, rng, dtype):
    """Materialize a ParamSpec pytree into real arrays. Jit it at real widths
    (``serving.engine.image_params`` does): then each leaf is drawn and cast
    inside one program and no f32 copy of a leaf is ever resident."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    rngs = jax.random.split(rng, len(leaves))
    return jax.tree.unflatten(treedef, [init_param(s, r, dtype) for s, r in zip(leaves, rngs)])


def build_abstract(specs, dtype):
    return jax.tree.map(lambda s: abstract_param(s, dtype), specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def build_axes(specs):
    return jax.tree.map(lambda s: s.axes, specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


# ---------------------------------------------------------------------------
# Activation sharding context
# ---------------------------------------------------------------------------

_CTX = threading.local()


@contextlib.contextmanager
def sharding_context(resolver: Callable):
    """resolver(shape, logical_names) -> NamedSharding | None."""
    prev = getattr(_CTX, "resolver", None)
    _CTX.resolver = resolver
    try:
        yield
    finally:
        _CTX.resolver = prev


def shard_act(x: jax.Array, names: Tuple[Optional[str], ...]) -> jax.Array:
    resolver = getattr(_CTX, "resolver", None)
    if resolver is None:
        return x
    s = resolver(x.shape, names)
    if s is None:
        return x
    return jax.lax.with_sharding_constraint(x, s)


# ---------------------------------------------------------------------------
# Primitive layers (pure functions)
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    # variance as a dot with f32 accumulation: no f32 x-shaped tensor may
    # appear in the HLO at all, else XLA's LICM hoists the convert of the
    # residual stack into the backward while-loop carry (+13.6 GiB measured
    # on the 62-layer train cell). Scaling applies in the compute dtype.
    var = jnp.einsum("...d,...d->...", x, x,
                     preferred_element_type=jnp.float32)[..., None] / x.shape[-1]
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * (1.0 + w).astype(x.dtype)


def rms_norm_f32(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """Full-f32 reference (oracle for tests)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


def head_rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """qwen3 qk_norm: RMSNorm over the trailing head_dim, weight shared across heads."""
    var = jnp.einsum("...d,...d->...", x, x,
                     preferred_element_type=jnp.float32)[..., None] / x.shape[-1]
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * (1.0 + w).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: [..., S, H, hd] (hd even), positions: [..., S]."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq          # [..., S, half]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]  # [..., S, 1, half]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def sinusoidal_pos(seq: int, d: int, dtype) -> jax.Array:
    """Absolute sinusoidal position table (hubert frontend-stub positions)."""
    pos = np.arange(seq)[:, None]
    i = np.arange(d // 2)[None, :]
    ang = pos / (10000 ** (2 * i / d))
    tab = np.concatenate([np.sin(ang), np.cos(ang)], -1)
    return jnp.asarray(tab, dtype)


def mlp(x: jax.Array, p: dict, gated: bool) -> jax.Array:
    """SwiGLU (gated) or GELU (plain) MLP. Weights: wi [D,F] (+wg), wo [F,D]."""
    if gated:
        h = jax.nn.silu(jnp.einsum("...d,df->...f", x, p["wg"])) \
            * jnp.einsum("...d,df->...f", x, p["wi"])
    else:
        h = jax.nn.gelu(jnp.einsum("...d,df->...f", x, p["wi"]))
    h = shard_act(h, ("act_batch", "act_seq", "act_mlp"))
    return jnp.einsum("...f,fd->...d", h, p["wo"])
