"""Pallas TPU decode attention: one query token vs a (ring) KV cache.

Memory-bound kernel: the whole cache streams HBM->VMEM once. Grid is
(batch, kv_head, kv_block) with the kv_block dim sequential so the online
softmax state for the G grouped q-heads sits in VMEM scratch. All G q-heads
of one kv head are processed together as a [G, hd] tile (q_per_kv x 128 is
the MXU-friendly packing for GQA decode).

The wrapper passes per-batch ``valid_len`` (= min(pos+1, W); ring buffers are
fully valid once wrapped) so ring and linear caches share one kernel. It is
scalar-prefetched into SMEM: a rank-1 VMEM block of one element is not a
legal TPU tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _decode_kernel(vl_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, scale, bw, nw):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    valid_len = vl_ref[pl.program_id(0)]
    first = j * bw

    @pl.when(first < valid_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                   # [G, hd]
        k = k_ref[0, 0].astype(jnp.float32)                   # [bw, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        slot = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(slot < valid_len, s, NEG_INF)           # [G, bw]
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
        pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr[:, None] + pv
        m_scr[...] = m_new

    @pl.when(j == nw - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     positions: jax.Array, *, ring: bool = False,
                     block_w: int = 512, interpret: bool) -> jax.Array:
    """q: [B, H, hd]; caches: [B, W, KV, hd]; positions: [B] -> [B, H, hd]."""
    B, W, KV, hd = k_cache.shape
    H = q.shape[1]
    G = H // KV
    bw = min(block_w, W)
    while W % bw:
        bw //= 2
    nw = W // bw
    if ring:
        valid_len = jnp.where(positions >= W, W, positions + 1).astype(jnp.int32)
    else:
        valid_len = jnp.minimum(positions + 1, W).astype(jnp.int32)

    qt = q.reshape(B, KV, G, hd)
    kt = k_cache.transpose(0, 2, 1, 3)     # [B, KV, W, hd]
    vt = v_cache.transpose(0, 2, 1, 3)

    kernel = functools.partial(_decode_kernel, scale=hd ** -0.5, bw=bw, nw=nw)
    from jax.experimental.pallas import tpu as pltpu
    from repro.kernels.flash_attention import _dim_semantics, _vmem

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, nw),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, j, vl: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bw, hd), lambda b, h, j, vl: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bw, hd), lambda b, h, j, vl: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, j, vl: (b, h, 0, 0)),
        scratch_shapes=[_vmem((G,), jnp.float32), _vmem((G,), jnp.float32),
                        _vmem((G, hd), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        compiler_params=_dim_semantics(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(valid_len, qt, kt, vt)
    return out.reshape(B, H, hd)
