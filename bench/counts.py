"""Operations and bytes of the served programs of a dense GQA decoder.

Counted from the configuration's shapes, as the algorithm needs them: a
multiply-add is two operations, causal attention counts only the keys a
query sees, and bytes are those a step has to move at the least (each weight
read once, the keys and values of valid positions read once, the new ones
and the logits written once). Padding, masked positions and copies that the
program makes beyond that are waste and are not counted, so a share of the
roofline built on these counts cannot pass 100%.
"""
from __future__ import annotations

BYTES = 2                       # bfloat16 weights, caches and logits


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
    """One prompt of ``n`` tokens, logits of its last token only."""
    L, D, F, V, q, kv = _dims(cfg)
    attn = L * 2 * q * n * (n + 1)          # QK^T and PV over the causal triangle
    return n * _matmul_flops_per_token(cfg) + attn + 2 * D * V


def decode_flops(cfg: dict, seen: list) -> int:
    """One decode step; ``seen[s]`` is the number of keys active slot ``s``
    attends, its new token included."""
    L, D, F, V, q, kv = _dims(cfg)
    per_token = _matmul_flops_per_token(cfg) + 2 * D * V
    return sum(per_token + L * 4 * q * n for n in seen)


def decode_bytes(cfg: dict, seen: list) -> int:
    """One decode step over the active slots: every weight once, the
    embedding rows of the batch, the cached keys and values of valid
    positions read once, the new ones and the logits written once."""
    L, D, F, V, q, kv = _dims(cfg)
    weights = L * layer_params(cfg) + V * D + D
    b = len(seen)
    kv_read = sum(L * 2 * kv * (n - 1) for n in seen)
    kv_write = b * L * 2 * kv
    return BYTES * (weights + b * D + kv_read + kv_write + b * V)
