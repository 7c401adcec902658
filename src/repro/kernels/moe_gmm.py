"""Pallas TPU grouped matmul for MoE expert compute (megablocks-style).

Tokens arrive sorted by expert with each group padded to the row-tile size
(the wrapper in ops.py builds this layout from the router output). A
scalar-prefetched ``block_to_expert`` map then lets the weight BlockSpec
index_map select the right expert's tile — so expert weights stream HBM->VMEM
once per used row block and dispatch costs no MXU FLOPs (vs the one-hot
einsum's T·E·C·D).

Grid: (row_block, ff_block, k_block) with k sequential accumulating in VMEM.
128x128x512 default tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _gmm_kernel(bmap_ref, x_ref, w_ref, y_ref, acc_scr, *, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _fin():
        y_ref[...] = acc_scr[...].astype(y_ref.dtype)


def grouped_matmul(x: jax.Array, w: jax.Array, block_to_expert: jax.Array, *,
                   block_t: int = 128, block_f: int = 128, block_d: int = 512,
                   interpret: bool) -> jax.Array:
    """x: [T_pad, D] expert-sorted rows; w: [E, D, F];
    block_to_expert: [T_pad // block_t] int32 -> y [T_pad, F]."""
    T, D = x.shape
    E, _, F = w.shape
    bt = block_t
    while T % bt:
        bt //= 2
    bf = min(block_f, F)
    while F % bf:
        bf //= 2
    bd = min(block_d, D)
    while D % bd:
        bd //= 2
    nt, nf, nk = T // bt, F // bf, D // bd
    assert block_to_expert.shape == (nt,), (block_to_expert.shape, nt)

    from jax.experimental.pallas import tpu as pltpu
    from repro.kernels.flash_attention import _dim_semantics, _vmem

    kernel = functools.partial(_gmm_kernel, nk=nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nt, nf, nk),
        in_specs=[
            pl.BlockSpec((bt, bd), lambda i, j, k, bmap: (i, k)),
            pl.BlockSpec((1, bd, bf), lambda i, j, k, bmap: (bmap[i], k, j)),
        ],
        out_specs=pl.BlockSpec((bt, bf), lambda i, j, k, bmap: (i, j)),
        scratch_shapes=[_vmem((bt, bf), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, F), x.dtype),
        compiler_params=_dim_semantics(("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(block_to_expert, x, w)
