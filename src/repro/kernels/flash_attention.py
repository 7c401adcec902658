"""Pallas TPU flash attention (forward): causal / sliding-window / GQA.

TPU-native blocking: the grid is (batch, q_head, q_block, kv_block) with the
kv_block dimension marked "arbitrary" (sequential), so the online-softmax
state (m, l, acc) lives in VMEM scratch across kv steps of the same q tile.
MXU-aligned tiles: q/kv blocks default 128/512, head_dim is the lane dim.
GQA is handled in the k/v index_map (q head h reads kv head h // G).

Masked-out (i, j) tiles are skipped with pl.when — the causal lower triangle
and the sliding-window band cost zero MXU work, matching the exact-triangle
accounting of the jnp reference path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale, causal, window, bq, bk, nk, g):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # tile visibility under the mask (static per (i, j) would be nicer; pl.when
    # keeps the skipped tile free of MXU work)
    first_q = i * bq
    last_q = first_q + bq - 1
    first_k = j * bk
    last_k = first_k + bk - 1
    visible = jnp.bool_(True)
    if causal:
        visible &= first_k <= last_q
    if window and window > 0:
        visible &= last_k > first_q - window

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                  # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)                  # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = first_q + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = first_k + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window and window > 0:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=1)
        pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr[:, None] + pv
        m_scr[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 512,
                    interpret: bool) -> jax.Array:
    """q: [B, S, H, hd]; k, v: [B, S, KV, hd] -> [B, S, H, hd]."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    g = H // KV
    bq = min(block_q, S)
    bk = min(block_k, S)
    while S % bq:
        bq //= 2
    while S % bk:
        bk //= 2
    nq, nk = S // bq, S // bk

    qt = q.transpose(0, 2, 1, 3)      # [B, H, S, hd]
    kt = k.transpose(0, 2, 1, 3)      # [B, KV, S, hd]
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _flash_kernel, scale=hd ** -0.5, causal=causal, window=window,
        bq=bq, bk=bk, nk=nk, g=g)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, g=g: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, g=g: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            _vmem((bq,), jnp.float32),
            _vmem((bq,), jnp.float32),
            _vmem((bq, hd), jnp.float32),
        ],
        compiler_params=_dim_semantics(("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


def _dim_semantics(sem):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=sem)
