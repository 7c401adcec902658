"""Jit'd dispatch wrappers around the Pallas kernels.

``impl`` selection:
* "pallas"    — pl.pallas_call compiled by Mosaic (TPU only)
* "interpret" — pl.pallas_call in the Pallas interpreter (any backend; how
  the CPU tests check the kernels)
* "ref"       — pure-jnp oracle

The caller's ``impl`` is the mode that runs; nothing overrides it. The model
layer calls the attention kernels when constructed with ``attn_impl="pallas"``
or ``"interpret"``, and ``mamba_scan`` in every Mamba prefill on a TPU
(``models/mamba.py``).
"""
from __future__ import annotations


import jax

from repro.kernels import ref as _ref
from repro.kernels.decode_attention import decode_attention as _decode_pallas
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.mamba_scan import mamba_scan as _mamba_pallas
from repro.kernels.moe_gmm import grouped_matmul as _gmm_pallas

IMPLS = ("pallas", "interpret", "ref")


def _interpret(impl: str) -> bool:
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    return impl == "interpret"


def flash_attention(q, k, v, *, causal=True, window=0, impl="interpret"):
    if impl == "ref":
        return _ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return _flash_pallas(q, k, v, causal=causal, window=window,
                         interpret=_interpret(impl))


def decode_attention(q, k_cache, v_cache, positions, *, ring=False,
                     impl="interpret"):
    if impl == "ref":
        return _ref.decode_attention_ref(q, k_cache, v_cache, positions, ring=ring)
    return _decode_pallas(q, k_cache, v_cache, positions, ring=ring,
                          interpret=_interpret(impl))


def mamba_scan(dt, x, B, C, A, D, *, impl="interpret"):
    """-> (y [Bt, S, DI] f32, final state [Bt, DI, N] f32)."""
    if impl == "ref":
        return _ref.mamba_scan_ref(dt, x, B, C, A, D)
    return _mamba_pallas(dt, x, B, C, A, D, interpret=_interpret(impl))


def grouped_matmul(x, w, block_to_expert, *, block_t=128, impl="interpret"):
    if impl == "ref":
        return _ref.grouped_matmul_ref(x, w, block_to_expert, block_t)
    return _gmm_pallas(x, w, block_to_expert, block_t=block_t,
                       interpret=_interpret(impl))


def moe_expert_ffn(xin, wg, wi, wo, block_to_expert=None, *, impl="interpret"):
    """SwiGLU expert FFN over expert-sorted rows via three grouped matmuls.

    xin: [T_pad, D] expert-sorted; wg/wi: [E, D, F]; wo: [E, F, D].
    """
    if block_to_expert is None:
        raise ValueError("block_to_expert required")
    g = grouped_matmul(xin, wg, block_to_expert, impl=impl)
    u = grouped_matmul(xin, wi, block_to_expert, impl=impl)
    h = jax.nn.silu(g) * u
    return grouped_matmul(h, wo, block_to_expert, impl=impl)
