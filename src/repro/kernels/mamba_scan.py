"""Pallas TPU selective-scan (Mamba-1) kernel: the scan of every prefill.

``models/mamba.py`` serves a prefill's scan from this kernel on a TPU. The
XLA form there (``_ssm_chunk_scan``) materializes a = exp(dt*A), bx = dt*B*x,
their associative-scan partials and every state as [B, chunk, DI, N] f32
tensors in HBM, 16x the activation size at N=16. This kernel fuses the whole
recurrence: HBM traffic is just the [B, S, DI]-sized dt/x/y, the [B, S, N]
B/C and the final state; the SSM state h [N, bd] never leaves VMEM.

Grid: (batch, d_inner blocks, seq chunks); the chunk dim is sequential with h
carried in VMEM scratch, and h is written out once, after the last chunk. The
state is laid out [N, bd] (state on sublanes, channels on lanes), so every
per-step operand is a lane row or a sublane column and no step needs a
relayout. Inside a chunk a fori_loop walks groups of ``grp`` time steps: each
group loads aligned [grp, bd] / [grp, N] tiles (a TPU load at a dynamic
sublane offset must be tile-aligned), transposes the B/C tiles once, and
unrolls the ``grp`` recurrence steps statically. The recurrence is
elementwise VPU and EUP work, not MXU work. The call is named ``mamba_scan``,
the name its events carry in a profiler trace.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

GROUP = 32              # time steps per unrolled group, at most (tuned on the v5e)


def _mamba_kernel(dt_ref, x_ref, b_ref, c_ref, at_ref, d_ref, y_ref, hl_ref,
                  h_scr, y_scr, *, chunk, grp):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    At = at_ref[...].astype(jnp.float32)                     # [N, bd]
    D = d_ref[...].astype(jnp.float32)                       # [1, bd]

    def group(g, h):
        base = pl.multiple_of(g * grp, grp)
        rows = pl.ds(base, grp)
        dt = dt_ref[0, rows, :].astype(jnp.float32)          # [grp, bd]
        x = x_ref[0, rows, :].astype(jnp.float32)
        bT = b_ref[0, rows, :].astype(jnp.float32).T         # [N, grp]
        cT = c_ref[0, rows, :].astype(jnp.float32).T
        for i in range(grp):
            dt_i, x_i = dt[i:i + 1], x[i:i + 1]              # [1, bd]
            h = jnp.exp(dt_i * At) * h + (dt_i * x_i) * bT[:, i:i + 1]
            y_scr[i:i + 1, :] = (jnp.sum(h * cT[:, i:i + 1], axis=0,
                                         keepdims=True) + D * x_i)
        y_ref[0, rows, :] = y_scr[...]
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk // grp, group, h_scr[...])

    @pl.when(j == pl.num_programs(2) - 1)
    def _last():
        hl_ref[0] = h_scr[...]


def mamba_scan(dt: jax.Array, x: jax.Array, B: jax.Array, C: jax.Array,
               A: jax.Array, D: jax.Array, *, chunk: int = 256,
               block_d: int = 1024, interpret: bool
               ) -> Tuple[jax.Array, jax.Array]:
    """dt, x: [Bt, S, DI]; B, C: [Bt, S, N]; A: [DI, N]; D: [DI]
    -> (y [Bt, S, DI] f32, h_S [Bt, DI, N] f32), from a zero state.

    y_t = C_t · h_t + D*x_t  with  h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t,
    every operand cast to f32 inside the kernel (x may come in bf16).
    ``chunk`` and ``block_d`` halve until they divide S and DI.
    """
    Bt, S, DI = x.shape
    N = A.shape[1]
    ch = min(chunk, S)
    while S % ch:
        ch //= 2
    bd = min(block_d, DI)
    while DI % bd:
        bd //= 2
    nc, nd = S // ch, DI // bd

    grp = math.gcd(ch, GROUP)
    kernel = functools.partial(_mamba_kernel, chunk=ch, grp=grp)
    from repro.kernels.flash_attention import _dim_semantics, _vmem

    y, h_last = pl.pallas_call(
        kernel,
        grid=(Bt, nd, nc),
        in_specs=[
            pl.BlockSpec((1, ch, bd), lambda b, d, j: (b, j, d)),   # dt
            pl.BlockSpec((1, ch, bd), lambda b, d, j: (b, j, d)),   # x
            pl.BlockSpec((1, ch, N), lambda b, d, j: (b, j, 0)),    # B
            pl.BlockSpec((1, ch, N), lambda b, d, j: (b, j, 0)),    # C
            pl.BlockSpec((N, bd), lambda b, d, j: (0, d)),          # A^T [N, DI]
            pl.BlockSpec((1, bd), lambda b, d, j: (0, d)),          # D [1, DI]
        ],
        out_specs=[
            pl.BlockSpec((1, ch, bd), lambda b, d, j: (b, j, d)),   # y
            pl.BlockSpec((1, N, bd), lambda b, d, j: (b, 0, d)),    # h_S^T
        ],
        out_shape=[jax.ShapeDtypeStruct((Bt, S, DI), jnp.float32),
                   jax.ShapeDtypeStruct((Bt, N, DI), jnp.float32)],
        scratch_shapes=[_vmem((N, bd), jnp.float32),
                        _vmem((grp, bd), jnp.float32)],
        compiler_params=_dim_semantics(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="mamba_scan",
    )(dt, x, B, C, A.T, D.reshape(1, DI))
    return y, h_last.swapaxes(1, 2)
