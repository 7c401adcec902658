"""Mamba-1, attention-free: the form that Falcon-Mamba publishes.

Each layer is one RMSNorm and a Mamba-1 mixer added to the residual stream;
there is no MLP. The mixer: in-projections x and z, a depthwise causal conv
(kernel ``conv_kernel``, with bias) and SiLU on x, then ``x_proj`` to dt
(``time_step_rank`` wide), B and C (``state_size`` each), each normalised by a
weightless RMSNorm (``mixer_rms_eps``, Falcon-Mamba's change to Mamba-1),
dt through ``dt_proj`` with bias and softplus, the selective scan

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t,    y_t = C_t . h_t + D x_t,

with A = -exp(A_log), gated by SiLU(z) and projected out. An untied output
head after a final RMSNorm. The engine holds it as one slot of layers
(``LM.param_specs`` for an attention-free model); RMSNorm gains are stored
as ``w`` and applied as ``1 + w``, as the engine does. The contract of this
file is in ``families/__init__.py``; beside it, :func:`prefill_bytes` gives
a prefill's least traffic, which ``metrics/prefill_roofline.py`` reads.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.reference import dot, rms_norm

BYTES = 2              # bfloat16 weights, activations, conv window and logits
STATE_BYTES = 4        # the SSM state is float32


def engine_fields(cfg: dict) -> dict:
    from repro.configs.base import MambaConfig
    return dict(num_heads=0, num_kv_heads=0, d_ff=0,
                norm_eps=cfg["layer_norm_epsilon"],
                mamba=MambaConfig(cfg["intermediate_size"], cfg["state_size"],
                                  cfg["conv_kernel"], cfg["time_step_rank"],
                                  cfg["mixer_rms_eps"], cfg["residual_in_fp32"]),
                moe=None, sliding_window=0)


def _dims(cfg: dict):
    return (cfg["num_hidden_layers"], cfg["hidden_size"], cfg["vocab_size"],
            cfg["intermediate_size"], cfg["state_size"], cfg["conv_kernel"],
            cfg["time_step_rank"])


def layout(cfg: dict) -> dict:
    L, D, V, DI, N, K, R = _dims(cfg)
    return {
        "embed": (V, D), "unembed": (V, D), "final_norm": (D,),
        "slots.0.norm1": (L, D), "slots.0.in_x": (L, D, DI), "slots.0.in_z": (L, D, DI),
        "slots.0.conv_w": (L, K, DI), "slots.0.conv_b": (L, DI),
        "slots.0.x_proj": (L, DI, R + 2 * N), "slots.0.dt_proj": (L, R, DI),
        "slots.0.dt_bias": (L, DI), "slots.0.A_log": (L, DI, N), "slots.0.D": (L, DI),
        "slots.0.out_proj": (L, DI, D),
    }


def draw(key, name: str, shape, dtype):
    """Mamba-1's initialisation where it has one of its own: A_log = log(1..N)
    over every channel (S4D-real), dt_bias the inverse softplus of a dt drawn
    log-uniform in [1e-3, 0.1], D ones, the conv bias zero; every other weight
    as ``weights.draw`` draws it."""
    last = name.split(".")[-1]
    if last == "A_log":
        a = jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32))
        return jnp.broadcast_to(a, shape).astype(dtype)
    if last == "dt_bias":
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (np.log(0.1) - np.log(1e-3)) + np.log(1e-3))
        return jnp.log(jnp.expm1(dt)).astype(dtype)
    if last == "D":
        return jnp.ones(shape, dtype)
    if last == "conv_b":
        return jnp.zeros(shape, dtype)
    return weights.draw(key, name, shape, dtype)


# ------------------------------------------------------------ reference
def _rms(v, eps):
    return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps)


def _scan(dt, x, B, C, A):
    """The selective scan, one step per position, carrying one [DI, N]
    state. dt, x [T, DI]; B, C [T, N]; A [DI, N]. Returns y [T, DI]."""
    def step(h, inp):
        dt_t, x_t, B_t, C_t = inp
        h = jnp.exp(dt_t[:, None] * A) * h + (dt_t * x_t)[:, None] * B_t[None, :]
        return h, jnp.sum(h * C_t[None, :], -1)
    _, y = jax.lax.scan(step, jnp.zeros(A.shape, jnp.float32), (dt, x, B, C))
    return y


def layer(x, w, cfg: dict, quant: str):
    T = x.shape[0]
    K, R, N = cfg["conv_kernel"], cfg["time_step_rank"], cfg["state_size"]
    eps = cfg["mixer_rms_eps"]
    f32 = {k: v.astype(jnp.float32) for k, v in w.items()}
    h = rms_norm(x, w["norm1"], cfg["layer_norm_epsilon"])
    xi, z = dot(h, w["in_x"], quant), dot(h, w["in_z"], quant)
    xp = jnp.concatenate([jnp.zeros((K - 1, xi.shape[1]), jnp.float32), xi])
    xi = sum(xp[k:k + T] * f32["conv_w"][k] for k in range(K)) + f32["conv_b"]
    xi = jax.nn.silu(xi)
    bcdt = dot(xi, w["x_proj"], quant)
    dt, B, C = (_rms(v, eps) for v in (bcdt[:, :R], bcdt[:, R:R + N], bcdt[:, R + N:]))
    dt = jax.nn.softplus(dot(dt, w["dt_proj"], quant) + f32["dt_bias"])
    y = _scan(dt, xi, B, C, -jnp.exp(f32["A_log"])) + xi * f32["D"]
    return x + dot(y * jax.nn.silu(z), w["out_proj"], quant)


def head(x, top, cfg: dict, quant: str):
    h = rms_norm(x, top["final_norm"], cfg["layer_norm_epsilon"])
    return dot(h, top["unembed"].T, quant)


# --------------------------------------------------------------- counts
def _matmul_params(cfg: dict) -> int:
    L, D, V, DI, N, K, R = _dims(cfg)
    return 2 * D * DI + DI * (R + 2 * N) + R * DI + DI * D


def layer_params(cfg: dict) -> int:
    L, D, V, DI, N, K, R = _dims(cfg)
    # matmuls, conv weight and bias, dt bias, A_log, D, the norm
    return _matmul_params(cfg) + K * DI + DI + DI + DI * N + DI + D


def params(cfg: dict) -> int:
    L, D, V, DI, N, K, R = _dims(cfg)
    return L * layer_params(cfg) + 2 * V * D + D


def _flops_per_token(cfg: dict) -> int:
    """One token through every layer: the matmuls, the conv, and the scan's
    state update (dt A, dt x B, a h + b) and read-out (C . h)."""
    L, D, V, DI, N, K, R = _dims(cfg)
    return L * (2 * _matmul_params(cfg) + 2 * K * DI + 6 * DI * N)


def prefill_flops(cfg: dict, n: int) -> int:
    L, D, V, DI, N, K, R = _dims(cfg)
    return n * _flops_per_token(cfg) + 2 * D * V


def decode_flops(cfg: dict, seen: list) -> int:
    L, D, V, DI, N, K, R = _dims(cfg)
    return len(seen) * (_flops_per_token(cfg) + 2 * D * V)


def _state_bytes(cfg: dict) -> int:
    """One slot's state: the conv window and the SSM state of every layer."""
    L, D, V, DI, N, K, R = _dims(cfg)
    return L * ((K - 1) * DI * BYTES + DI * N * STATE_BYTES)


def _weight_bytes(cfg: dict) -> int:
    """The layers, the final norm and the output head; the embedding is
    read by rows."""
    L, D, V, DI, N, K, R = _dims(cfg)
    return BYTES * (L * layer_params(cfg) + V * D + D)


def decode_bytes(cfg: dict, seen: list) -> int:
    """Every weight once, the batch's embedding rows, each active slot's
    conv window and SSM state read and written once, the logits written
    once. The state does not grow with ``seen``."""
    L, D, V, DI, N, K, R = _dims(cfg)
    b = len(seen)
    return _weight_bytes(cfg) + b * (BYTES * (D + V) + 2 * _state_bytes(cfg))


def prefill_bytes(cfg: dict, n: int) -> int:
    """One prompt of ``n`` tokens: every weight once, its embedding rows,
    the slot's state written once, one row of logits."""
    L, D, V, DI, N, K, R = _dims(cfg)
    return _weight_bytes(cfg) + BYTES * (n * D + V) + _state_bytes(cfg)
