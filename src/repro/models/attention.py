"""Attention: blocked-flash reference implementations + decode paths.

Three executable paths, all pure jnp (XLA), all parity-tested against the
plain-einsum oracle in ``repro.kernels.ref``:

* :func:`attend_blocked` — memory-bounded flash attention as a scan over the
  *static list of contributing (q-block, kv-block) pairs*.  For causal masks
  this is the exact lower triangle (no wasted FLOPs on masked-out blocks —
  matters for the roofline's useful-FLOPs ratio at 32k); for sliding-window
  it is the diagonal band; for bidirectional it is the full square.
* :func:`attend_plain` — small-seq einsum path (smoke tests, tiny serving
  functions).
* :func:`attend_decode` — one-token GQA attention against a (possibly
  ring-buffered) KV cache.

The Pallas kernels in ``repro.kernels`` replace the first and third paths
with ``impl="pallas"`` (compiled, TPU) or ``impl="interpret"`` (the Pallas
interpreter, any backend); the dry-run keeps ``ref`` so cost analysis
reflects the XLA program actually being lowered for the mesh.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import shard_act

NEG_INF = -1e30


def _block_pairs(nq: int, nkv: int, *, causal: bool, window_blocks: int) -> np.ndarray:
    """Static (i, j) block pairs that can contribute under the mask."""
    pairs = []
    for i in range(nq):
        lo = 0
        if window_blocks > 0:                       # sliding window band
            lo = max(0, i - window_blocks)
        hi = i + 1 if causal else nkv
        for j in range(lo, hi):
            pairs.append((i, j))
    return np.asarray(pairs, np.int32)


def _pair_mask(i, j, block, causal, window):
    karr = jnp.arange(block)
    qpos = i * block + karr
    kpos = j * block + karr
    mask = jnp.ones((block, block), bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window and window > 0:
        mask &= (qpos[:, None] - kpos[None, :]) < window
    return mask


def attend_blocked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool, window: int = 0, block: int = 512,
                   impl: str = "ref", unroll: bool = False) -> jax.Array:
    """Flash attention over the static list of contributing block pairs.

    q: [B, S, H, hd]; k, v: [B, S, KV, hd]. Returns [B, S, H, hd].
    ``window`` > 0 restricts to a causal sliding window (gemma3 local layers).

    Uses a flash-style custom VJP: the backward recomputes p-blocks from the
    saved (q, k, v, out, logsumexp) instead of letting JAX AD store every
    [bq, bk] probability block of the forward scan (which would cost
    O(S²/block) residual memory and defeat the whole construction).
    """
    if impl != "ref":  # Pallas kernel, compiled ("pallas") or interpreted
        from repro.kernels import ops
        return ops.flash_attention(q, k, v, causal=causal, window=window,
                                   impl=impl)

    B, S, H, hd = q.shape
    block = min(block, S)
    assert S % block == 0, (S, block)

    @partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
    def _attend(q, k, v, causal, window, block, unroll):
        out, _ = _attend_fwd_impl(q, k, v, causal, window, block, unroll)
        return out

    def _fwd(q, k, v, causal, window, block, unroll):
        out, lse = _attend_fwd_impl(q, k, v, causal, window, block, unroll)
        return out, (q, k, v, out, lse)

    def _bwd(causal, window, block, unroll, res, dout):
        return _attend_bwd_impl(res, dout, causal, window, block, unroll)

    _attend.defvjp(_fwd, _bwd)
    return _attend(q, k, v, causal, window, block, unroll)


def _attend_fwd_impl(q, k, v, causal, window, block, unroll):
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    nb = S // block
    wb = int(np.ceil(window / block)) if window else 0
    pairs = _block_pairs(nb, nb, causal=causal, window_blocks=wb)
    scale = hd ** -0.5

    qb = q.reshape(B, nb, block, KV, G, hd)
    kb = k.reshape(B, nb, block, KV, hd)
    vb = v.reshape(B, nb, block, KV, hd)

    acc0 = jnp.zeros((B, nb, block, KV, G, hd), jnp.float32)
    m0 = jnp.full((B, nb, block, KV, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, nb, block, KV, G), jnp.float32)

    def body(carry, pij):
        acc, m, l = carry
        i, j = pij[0], pij[1]
        qi = jax.lax.dynamic_index_in_dim(qb, i, 1, keepdims=False)   # [B,bq,KV,G,hd]
        kj = jax.lax.dynamic_index_in_dim(kb, j, 1, keepdims=False)   # [B,bk,KV,hd]
        vj = jax.lax.dynamic_index_in_dim(vb, j, 1, keepdims=False)
        s = jnp.einsum("bqkgd,btkd->bkgqt", qi, kj,
                       preferred_element_type=jnp.float32) * scale    # [B,KV,G,bq,bk]
        s = jnp.where(_pair_mask(i, j, block, causal, window), s, NEG_INF)
        blk_m = jnp.moveaxis(jnp.max(s, axis=-1), -1, 1)              # [B,bq,KV,G]
        mi = jax.lax.dynamic_index_in_dim(m, i, 1, keepdims=False)
        li = jax.lax.dynamic_index_in_dim(l, i, 1, keepdims=False)
        acci = jax.lax.dynamic_index_in_dim(acc, i, 1, keepdims=False)
        m_new = jnp.maximum(mi, blk_m)
        p = jnp.exp(s - jnp.moveaxis(m_new, 1, -1)[..., None])        # [B,KV,G,bq,bk]
        corr = jnp.exp(mi - m_new)
        l_new = li * corr + jnp.moveaxis(jnp.sum(p, -1), -1, 1)
        pv = jnp.einsum("bkgqt,btkd->bqkgd", p.astype(vj.dtype), vj,
                        preferred_element_type=jnp.float32)
        acc_new = acci * corr[..., None] + pv
        acc = jax.lax.dynamic_update_index_in_dim(acc, acc_new, i, 1)
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, i, 1)
        l = jax.lax.dynamic_update_index_in_dim(l, l_new, i, 1)
        return (acc, m, l), None

    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), jnp.asarray(pairs),
                                  unroll=len(pairs) if unroll else 1)
    l = jnp.maximum(l, 1e-30)
    out = (acc / l[..., None]).reshape(B, S, H, hd).astype(q.dtype)
    lse = (m + jnp.log(l)).reshape(B, S, KV, G)                       # logsumexp
    # barrier: `out` is a saved custom-vjp residual; without it XLA sinks the
    # f32->bf16 convert past the layer-scan's residual stacking and stores the
    # f32 accumulator stack instead (2x bytes; +13.6 GiB on the 62L train cell)
    out, lse = jax.lax.optimization_barrier((out, lse))
    return out, lse


def _attend_bwd_impl(res, dout, causal, window, block, unroll):
    q, k, v, out, lse = res
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    nb = S // block
    wb = int(np.ceil(window / block)) if window else 0
    pairs = _block_pairs(nb, nb, causal=causal, window_blocks=wb)
    scale = hd ** -0.5

    qb = q.reshape(B, nb, block, KV, G, hd)
    kb = k.reshape(B, nb, block, KV, hd)
    vb = v.reshape(B, nb, block, KV, hd)
    dob = dout.reshape(B, nb, block, KV, G, hd)
    lseb = lse.reshape(B, nb, block, KV, G)
    # D_i = rowsum(dO ∘ O) — the softmax-jacobian diagonal term
    Db = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                 axis=-1).reshape(B, nb, block, KV, G)

    dq0 = jnp.zeros((B, nb, block, KV, G, hd), jnp.float32)
    dk0 = jnp.zeros((B, nb, block, KV, hd), jnp.float32)
    dv0 = jnp.zeros((B, nb, block, KV, hd), jnp.float32)

    def body(carry, pij):
        dq, dk, dv = carry
        i, j = pij[0], pij[1]
        qi = jax.lax.dynamic_index_in_dim(qb, i, 1, keepdims=False)
        kj = jax.lax.dynamic_index_in_dim(kb, j, 1, keepdims=False)
        vj = jax.lax.dynamic_index_in_dim(vb, j, 1, keepdims=False)
        doi = jax.lax.dynamic_index_in_dim(dob, i, 1, keepdims=False)
        lsei = jax.lax.dynamic_index_in_dim(lseb, i, 1, keepdims=False)
        Di = jax.lax.dynamic_index_in_dim(Db, i, 1, keepdims=False)
        s = jnp.einsum("bqkgd,btkd->bkgqt", qi, kj,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(_pair_mask(i, j, block, causal, window), s, NEG_INF)
        p = jnp.exp(s - jnp.moveaxis(lsei, 1, -1)[..., None])         # [B,KV,G,bq,bk]
        pc = p.astype(vj.dtype)
        dvj = jnp.einsum("bkgqt,bqkgd->btkd", pc, doi,
                         preferred_element_type=jnp.float32)
        dp = jnp.einsum("bqkgd,btkd->bkgqt", doi, vj,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - jnp.moveaxis(Di, 1, -1)[..., None]) * scale    # [B,KV,G,bq,bk]
        dsc = ds.astype(qi.dtype)
        dqi = jnp.einsum("bkgqt,btkd->bqkgd", dsc, kj,
                         preferred_element_type=jnp.float32)
        dkj = jnp.einsum("bkgqt,bqkgd->btkd", dsc, qi,
                         preferred_element_type=jnp.float32)
        dq = jax.lax.dynamic_update_index_in_dim(
            dq, jax.lax.dynamic_index_in_dim(dq, i, 1, keepdims=False) + dqi, i, 1)
        dk = jax.lax.dynamic_update_index_in_dim(
            dk, jax.lax.dynamic_index_in_dim(dk, j, 1, keepdims=False) + dkj, j, 1)
        dv = jax.lax.dynamic_update_index_in_dim(
            dv, jax.lax.dynamic_index_in_dim(dv, j, 1, keepdims=False) + dvj, j, 1)
        return (dq, dk, dv), None

    (dq, dk, dv), _ = jax.lax.scan(body, (dq0, dk0, dv0), jnp.asarray(pairs),
                                   unroll=len(pairs) if unroll else 1)
    return (dq.reshape(B, S, H, hd).astype(q.dtype),
            dk.reshape(B, S, KV, hd).astype(k.dtype),
            dv.reshape(B, S, KV, hd).astype(v.dtype))


def attend_plain(q: jax.Array, k: jax.Array, v: jax.Array, *,
                 causal: bool, window: int = 0) -> jax.Array:
    """Materialized-scores reference (small sequences / oracle)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qr = q.reshape(B, S, KV, G, hd)
    s = jnp.einsum("bqkgd,btkd->bkgqt", qr.astype(jnp.float32),
                   k.astype(jnp.float32)) * hd ** -0.5
    pos = jnp.arange(S)
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= pos[:, None] >= pos[None, :]
    if window and window > 0:
        mask &= (pos[:, None] - pos[None, :]) < window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqt,btkd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(B, S, H, hd).astype(q.dtype)


def attend_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                  positions: jax.Array, *, ring: bool = False,
                  impl: str = "ref") -> jax.Array:
    """One-token attention against the cache.

    q: [B, H, hd]; caches: [B, W, KV, hd]; positions: [B] (current absolute
    position, i.e. index of the token being generated).  ``ring=True`` means
    the cache is a ring buffer of width W over a longer stream (local layers):
    slot s holds absolute token  pos - ((pos - s) mod W)  and every slot
    written so far is in-window by construction.
    """
    if impl != "ref":
        from repro.kernels import ops
        return ops.decode_attention(q, k_cache, v_cache, positions, ring=ring,
                                    impl=impl)

    B, W, KV, hd = k_cache.shape
    H = q.shape[1]
    G = H // KV
    qr = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgd,btkd->bkgt", qr, k_cache,
                   preferred_element_type=jnp.float32) * hd ** -0.5
    slot = jnp.arange(W)
    if ring:
        # valid once written: slot s valid iff s <= pos or the ring has wrapped
        valid = (slot[None, :] <= positions[:, None]) | (positions[:, None] >= W)
    else:
        valid = slot[None, :] <= positions[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Full attention block (projections + rope + qk_norm + cache handling)
# ---------------------------------------------------------------------------


def attn_forward(x: jax.Array, p: dict, cfg, layer_local: bool,
                 positions: jax.Array, *, theta: float,
                 block: int = 512, impl: str = "ref",
                 unroll: bool = False) -> Tuple[jax.Array, dict]:
    """Sequence-mode attention (train/prefill). Returns (out, new_cache_entry).

    x: [B, S, D]. Cache entry: k/v [B, W, KV, hd] where W = window for local
    layers else S.
    """
    from repro.models.layers import head_rms_norm, rope
    B, S, D = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    # flat [D, H*hd] projections: divisible by the model axis for every arch
    # (H*hd, KV*hd are multiples of 128), so weights/optimizer shard fully
    q = jnp.einsum("bsd,de->bse", x, p["wq"]).reshape(B, S, H, hd)
    k = jnp.einsum("bsd,de->bse", x, p["wk"]).reshape(B, S, KV, hd)
    v = jnp.einsum("bsd,de->bse", x, p["wv"]).reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = head_rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = head_rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.causal:  # encoders use absolute positions added at the input
        q = rope(q, positions, theta)
        k = rope(k, positions, theta)
    q = shard_act(q, ("act_batch", "act_seq", "act_heads", None))
    k = shard_act(k, ("act_batch", "act_seq", "act_kv_heads", None))
    window = cfg.sliding_window if layer_local else 0
    blk = min(block, S)
    if S % blk != 0:
        blk = S                        # single-block fallback (odd smoke shapes)
    out = attend_blocked(q, k, v, causal=cfg.causal, window=window,
                         block=blk, impl=impl, unroll=unroll)
    out = shard_act(out, ("act_batch", "act_seq", "act_heads", None))
    y = jnp.einsum("bse,ed->bsd", out.reshape(B, S, H * hd), p["wo"])
    # cache entry for prefill (ring-truncate local layers to the window)
    if layer_local and cfg.sliding_window and S > cfg.sliding_window:
        W = cfg.sliding_window
        # last W tokens, placed at their ring slots (slot = pos % W)
        tail_k, tail_v = k[:, -W:], v[:, -W:]
        start = S - W
        roll = -(start % W)
        cache_k = jnp.roll(tail_k, roll, axis=1)
        cache_v = jnp.roll(tail_v, roll, axis=1)
    else:
        cache_k, cache_v = k, v
    return y, {"k": cache_k, "v": cache_v}


def attn_decode(x: jax.Array, p: dict, cfg, layer_local: bool, cache: dict,
                positions: jax.Array, *, theta: float,
                impl: str = "ref") -> Tuple[jax.Array, dict]:
    """One-token attention. x: [B, D]; cache k/v [B, W, KV, hd]; positions [B]."""
    from repro.models.layers import head_rms_norm, rope
    B, D = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bd,de->be", x, p["wq"]).reshape(B, H, hd)
    k = jnp.einsum("bd,de->be", x, p["wk"]).reshape(B, KV, hd)
    v = jnp.einsum("bd,de->be", x, p["wv"]).reshape(B, KV, hd)
    if cfg.qk_norm:
        q = head_rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = head_rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = rope(q[:, None], positions[:, None], theta)[:, 0]
    k = rope(k[:, None], positions[:, None], theta)[:, 0]
    W = cache["k"].shape[1]
    ring = bool(layer_local and cfg.sliding_window and W == cfg.sliding_window)
    slot = positions % W if ring else positions
    k_cache = _update_cache(cache["k"], k, slot)
    v_cache = _update_cache(cache["v"], v, slot)
    k_cache = shard_act(k_cache, ("act_batch", "act_kv_seq", "act_kv_heads", None))
    v_cache = shard_act(v_cache, ("act_batch", "act_kv_seq", "act_kv_heads", None))
    out = attend_decode(q, k_cache, v_cache, positions, ring=ring, impl=impl)
    y = jnp.einsum("be,ed->bd", out.reshape(B, H * hd), p["wo"])
    return y, {"k": k_cache, "v": v_cache}


def _update_cache(cache: jax.Array, new: jax.Array, slot: jax.Array) -> jax.Array:
    """Scatter new [B, KV, hd] into cache [B, W, KV, hd] at per-batch slots."""
    B = cache.shape[0]
    return cache.at[jnp.arange(B), slot].set(new.astype(cache.dtype))
