"""Unified LM covering all ten assigned architectures.

The layer stack is executed as ``lax.scan`` over *periods*: the period P is
the LCM of the arch's interleave patterns (jamba attn:mamba 1:7 and MoE-every-2
=> P=8; gemma3 local:global 5:1 => P=6; homogeneous archs => P=1).  Params are
stored per period-slot with a stacked ``[K = L/P, ...]`` leading dim, so the
HLO stays compact (~P blocks) regardless of depth — a 512-device compile of
the 88-layer mistral takes seconds, not minutes.

Modes:
* ``forward_seq``  — train / prefill: [B, S] -> last-token or full logits + cache
* ``decode_step``  — serve_step: one token against the cache (assigned decode
  shapes) — local-attention slots keep *ring-buffer* caches of width
  ``sliding_window`` (gemma3's 500k decode cache is 1024 wide on local slots).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import cached_property
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import BLOCK_ATTN, ModelConfig, ShapeConfig
from repro.models import attention as attn_mod
from repro.models import mamba as mamba_mod
from repro.models import moe as moe_mod
from repro.models.layers import (ParamSpec, build_abstract, build_axes,
                                 build_params, mlp, rms_norm, shard_act,
                                 sinusoidal_pos)

AUX_LOSS_COEF = 0.01


@dataclass(frozen=True)
class SlotKind:
    kind: str          # attn | mamba
    is_moe: bool
    is_local: bool     # sliding-window attention
    theta: float       # rope base (gemma3: 10k local / 1M global)


class LM:
    """Functional model: all methods are pure; params/caches are pytrees."""

    def __init__(self, cfg: ModelConfig, *, attn_impl: str = "ref",
                 attn_block: int = 512, mamba_chunk: int = 256,
                 unroll: bool = False):
        self.cfg = cfg
        self.attn_impl = attn_impl
        self.attn_block = attn_block
        self.mamba_chunk = mamba_chunk
        # unroll=True: every lax.scan (layers, attention block pairs, ssm
        # chunks, chunked CE) is fully unrolled so compiled cost_analysis
        # counts true totals (XLA counts while-loop bodies ONCE).  Used by the
        # dry-run's shallow probes; production keeps compact scans.
        self.unroll = unroll
        self.period = self._period(cfg)
        assert cfg.num_layers % self.period == 0, (cfg.name, self.period)
        self.num_periods = cfg.num_layers // self.period
        self.slots: List[SlotKind] = []
        for s in range(self.period):
            kind = cfg.block_kind(s)
            local = cfg.is_local_attn(s)
            theta = cfg.rope_theta
            if cfg.sliding_window and local:
                theta = 10000.0                      # gemma3 local layers
            self.slots.append(SlotKind(kind, cfg.is_moe_layer(s), local, theta))

    @staticmethod
    def _period(cfg: ModelConfig) -> int:
        p = 1
        if cfg.mamba is not None and not cfg.attention_free:
            p = math.lcm(p, cfg.attn_every)
        if cfg.moe is not None:
            p = math.lcm(p, cfg.moe.every)
        if cfg.sliding_window > 0:
            p = math.lcm(p, cfg.swa_period)
    # NB: for every assigned arch this divides num_layers (asserted above).
        return p

    # ------------------------------------------------------------------
    # Parameter specs
    # ------------------------------------------------------------------
    @cached_property
    def param_specs(self) -> Dict[str, Any]:
        c = self.cfg
        K, D = self.num_periods, c.d_model
        specs: Dict[str, Any] = {}
        if c.frontend != "frames":
            specs["embed"] = ParamSpec((c.vocab_size, D), ("w_vocab", "w_embed"))
            if not c.tie_embeddings:
                specs["unembed"] = ParamSpec((c.vocab_size, D), ("w_vocab", "w_embed"))
        else:
            specs["unembed"] = ParamSpec((c.vocab_size, D), ("w_vocab", "w_embed"))
        if c.frontend == "patches":
            specs["patch_proj"] = ParamSpec((D, D), ("w_embed", None))
        specs["final_norm"] = ParamSpec((D,), (None,), init="zeros")
        slot_specs = []
        for s, sk in enumerate(self.slots):
            ps: Dict[str, Any] = {"norm1": ParamSpec((K, D), ("w_layers", None), init="zeros")}
            if sk.kind == BLOCK_ATTN:
                H, KV, hd = c.num_heads, c.num_kv_heads, c.head_dim
                # flat projections: H*hd / KV*hd divide the model axis for
                # every assigned arch even when H doesn't (deepseek H=56)
                ps["wq"] = ParamSpec((K, D, H * hd), ("w_layers", "w_embed", "w_qdim"))
                ps["wk"] = ParamSpec((K, D, KV * hd), ("w_layers", "w_embed", "w_kvdim"))
                ps["wv"] = ParamSpec((K, D, KV * hd), ("w_layers", "w_embed", "w_kvdim"))
                ps["wo"] = ParamSpec((K, H * hd, D), ("w_layers", "w_qdim", "w_embed"))
                if c.qk_norm:
                    ps["q_norm"] = ParamSpec((K, hd), ("w_layers", None), init="zeros")
                    ps["k_norm"] = ParamSpec((K, hd), ("w_layers", None), init="zeros")
            else:
                m = c.mamba
                DI = m.d_inner
                ps["in_x"] = ParamSpec((K, D, DI), ("w_layers", "w_embed", "w_dinner"))
                ps["in_z"] = ParamSpec((K, D, DI), ("w_layers", "w_embed", "w_dinner"))
                ps["conv_w"] = ParamSpec((K, m.d_conv, DI), ("w_layers", None, "w_dinner"))
                ps["conv_b"] = ParamSpec((K, DI), ("w_layers", "w_dinner"), init="zeros")
                ps["x_proj"] = ParamSpec((K, DI, m.dt_rank + 2 * m.d_state),
                                         ("w_layers", "w_dinner", None))
                ps["dt_proj"] = ParamSpec((K, m.dt_rank, DI), ("w_layers", None, "w_dinner"))
                ps["dt_bias"] = ParamSpec((K, DI), ("w_layers", "w_dinner"), init="mamba_dt")
                ps["A_log"] = ParamSpec((K, DI, m.d_state),
                                        ("w_layers", "w_dinner", "w_state"), init="mamba_a")
                ps["D"] = ParamSpec((K, DI), ("w_layers", "w_dinner"), init="ones")
                ps["out_proj"] = ParamSpec((K, DI, D), ("w_layers", "w_dinner", "w_embed"))
            if sk.is_moe or c.d_ff > 0:
                ps["norm2"] = ParamSpec((K, D), ("w_layers", None), init="zeros")
            if sk.is_moe:
                e = c.moe
                E, F = e.num_experts, e.expert_ff
                ps["router"] = ParamSpec((K, D, E), ("w_layers", "w_embed", None))
                ps["moe_wi"] = ParamSpec((K, E, D, F), ("w_layers", "w_expert", "w_embed", "w_moe_mlp"))
                ps["moe_wg"] = ParamSpec((K, E, D, F), ("w_layers", "w_expert", "w_embed", "w_moe_mlp"))
                ps["moe_wo"] = ParamSpec((K, E, F, D), ("w_layers", "w_expert", "w_moe_mlp", "w_embed"))
            elif c.d_ff > 0:
                ps["wi"] = ParamSpec((K, D, c.d_ff), ("w_layers", "w_embed", "w_mlp"))
                if c.gated_mlp:
                    ps["wg"] = ParamSpec((K, D, c.d_ff), ("w_layers", "w_embed", "w_mlp"))
                ps["wo_mlp"] = ParamSpec((K, c.d_ff, D), ("w_layers", "w_mlp", "w_embed"))
            slot_specs.append(ps)
        specs["slots"] = slot_specs
        return specs

    def abstract_params(self):
        return build_abstract(self.param_specs, jnp.dtype(self.cfg.dtype))

    def param_axes(self):
        return build_axes(self.param_specs)

    def init_params(self, rng):
        return build_params(self.param_specs, rng, jnp.dtype(self.cfg.dtype))

    # ------------------------------------------------------------------
    # Input embedding
    # ------------------------------------------------------------------
    def embed_input(self, params, batch) -> jax.Array:
        c = self.cfg
        if c.frontend == "frames":
            x = batch["frames"].astype(jnp.dtype(c.dtype))
            S = x.shape[1]
            return x + sinusoidal_pos(S, c.d_model, x.dtype)[None]
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
        if c.frontend == "patches" and "patch_embeds" in batch:
            pe = jnp.einsum("bpd,de->bpe", batch["patch_embeds"].astype(x.dtype),
                            params["patch_proj"])
            x = jnp.concatenate([pe, x[:, pe.shape[1]:]], axis=1)
        if not c.causal:
            x = x + sinusoidal_pos(x.shape[1], c.d_model, x.dtype)[None]
        return x

    def logits(self, params, x) -> jax.Array:
        head = params.get("unembed", params.get("embed"))
        out = jnp.einsum("...d,vd->...v", x, head)
        names = ("act_batch", "act_seq", "act_vocab") if out.ndim == 3 \
            else ("act_batch", "act_vocab")
        return shard_act(out, names)

    def _stream(self, x) -> jax.Array:
        """The residual stream as carried between layers: float32 where the
        model publishes ``residual_in_fp32``, else as it is."""
        return x.astype(jnp.float32) if self.cfg.residual_in_fp32 else x

    def _compute(self, h) -> jax.Array:
        """A normed stream in the model dtype, as layers and the head take it."""
        return h.astype(self.cfg.dtype) if self.cfg.residual_in_fp32 else h

    # ------------------------------------------------------------------
    # Blocks
    # ------------------------------------------------------------------
    def _block_seq(self, x, p, sk: SlotKind, positions, want_cache: bool):
        c = self.cfg
        h = self._compute(rms_norm(x, p["norm1"], c.norm_eps))
        if sk.kind == BLOCK_ATTN:
            h, cache = attn_mod.attn_forward(
                h, p, c, sk.is_local, positions, theta=sk.theta,
                block=self.attn_block, impl=self.attn_impl, unroll=self.unroll)
        else:
            h, cache = mamba_mod.mamba_forward(h, p, c, chunk=self.mamba_chunk,
                                               unroll=self.unroll,
                                               want_cache=want_cache)
        x = x + h
        x = shard_act(x, ("act_batch", "act_seq", "act_embed"))
        if sk.is_moe:
            h = self._compute(rms_norm(x, p["norm2"], c.norm_eps))
            x = x + moe_mod.moe_forward(h, {"router": p["router"], "wi": p["moe_wi"],
                                            "wg": p["moe_wg"], "wo": p["moe_wo"]}, c,
                                        unroll=self.unroll)
        elif c.d_ff > 0:
            h = self._compute(rms_norm(x, p["norm2"], c.norm_eps))
            x = x + mlp(h, {"wi": p["wi"], "wg": p.get("wg"), "wo": p["wo_mlp"]},
                        c.gated_mlp)
        x = shard_act(x, ("act_batch", "act_seq", "act_embed"))
        return x, cache

    def _block_decode(self, x, p, sk: SlotKind, cache, positions):
        c = self.cfg
        h = self._compute(rms_norm(x, p["norm1"], c.norm_eps))
        if sk.kind == BLOCK_ATTN:
            h, cache = attn_mod.attn_decode(h, p, c, sk.is_local, cache,
                                            positions, theta=sk.theta,
                                            impl=self.attn_impl)
        else:
            h, cache = mamba_mod.mamba_decode(h, p, c, cache)
        x = x + h
        if sk.is_moe:
            h = self._compute(rms_norm(x, p["norm2"], c.norm_eps))
            x = x + moe_mod.moe_forward(h, {"router": p["router"], "wi": p["moe_wi"],
                                            "wg": p["moe_wg"], "wo": p["moe_wo"]}, c)
        elif c.d_ff > 0:
            h = self._compute(rms_norm(x, p["norm2"], c.norm_eps))
            x = x + mlp(h[:, None], {"wi": p["wi"], "wg": p.get("wg"),
                                     "wo": p["wo_mlp"]}, c.gated_mlp)[:, 0]
        return x, cache

    # ------------------------------------------------------------------
    # Sequence mode (train / prefill)
    # ------------------------------------------------------------------
    def forward_seq(self, params, batch, *, want_cache: bool,
                    remat: Optional[bool] = None):
        c = self.cfg
        use_remat = c.remat if remat is None else remat
        x = self._stream(self.embed_input(params, batch))
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x = shard_act(x, ("act_batch", "act_seq", "act_embed"))

        def period_body(xc, slot_params):
            # barrier: stops XLA hoisting the rms_norm bf16->f32 convert of the
            # carry out of the backward while-loop, which would materialize an
            # f32 copy of the whole [K, B, S, D] residual stack (measured 2x).
            xc = jax.lax.optimization_barrier(xc)
            caches = []
            for s, sk in enumerate(self.slots):
                xc, cache = self._block_seq(xc, slot_params[s], sk, positions,
                                            want_cache)
                caches.append(cache if want_cache else jnp.zeros((), x.dtype))
            return xc, caches

        # prevent_cse=False: inside scan the while-loop already blocks CSE;
        # the default barriers would pin ~3x the carry per layer (measured).
        body = jax.remat(period_body, prevent_cse=False) if use_remat \
            else period_body
        if self.unroll:
            all_caches = []
            for k in range(self.num_periods):
                pk = jax.tree.map(lambda a: a[k], params["slots"])
                x, caches = body(x, pk)
                all_caches.append(caches)
            if want_cache:
                caches = jax.tree.map(lambda *xs: jnp.stack(xs), *all_caches)
            else:
                caches = None
            x = self._compute(rms_norm(x, params["final_norm"], c.norm_eps))
            return x, caches
        x, caches = jax.lax.scan(body, x, params["slots"])
        x = self._compute(rms_norm(x, params["final_norm"], c.norm_eps))
        return x, caches if want_cache else None

    def loss_fn(self, params, batch):
        """Mean CE (+ MoE aux). batch: tokens/frames, labels, optional loss_mask."""
        c = self.cfg
        x, _ = self.forward_seq(params, batch, want_cache=False)
        labels = batch["labels"]
        mask = batch.get("loss_mask")
        if mask is None:
            mask = jnp.ones(labels.shape, jnp.float32)
        head = params.get("unembed", params.get("embed"))
        chunk = c.logits_chunk
        if chunk and labels.shape[1] % chunk == 0 and labels.shape[1] > chunk:
            loss_sum = _chunked_ce(x, head, labels, mask, chunk,
                                   unroll=self.unroll)
        else:
            logits = self.logits(params, x).astype(jnp.float32)
            lp = jax.nn.log_softmax(logits, axis=-1)
            ll = jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0]
            loss_sum = -jnp.sum(ll * mask)
        loss = loss_sum / jnp.maximum(jnp.sum(mask), 1.0)
        metrics = {"ce": loss}
        if c.moe is not None:
            # aux loss on the input embedding stream (cheap proxy over layers)
            aux = self._aux_loss(params, batch)
            metrics["aux"] = aux
            loss = loss + AUX_LOSS_COEF * aux
        return loss, metrics

    def _aux_loss(self, params, batch):
        c = self.cfg
        x = self.embed_input(params, batch)
        # first MoE slot, first period — representative balance signal
        for s, sk in enumerate(self.slots):
            if sk.is_moe:
                p0 = jax.tree.map(lambda a: a[0], params["slots"][s])
                return moe_mod.moe_aux_loss(x, {"router": p0["router"]}, c)
        return jnp.zeros((), jnp.float32)

    def prefill(self, params, batch):
        """Returns (last-token logits [B, V], cache)."""
        # no grad => no remat: the checkpoint wrapper only blocks XLA's
        # buffer reuse across the period's layers (measured +4x live set on
        # the jamba MoE prefill cell)
        x, caches = self.forward_seq(params, batch, want_cache=True,
                                     remat=False)
        logits = self.logits(params, x[:, -1])
        return logits, {"slots": caches}

    # ------------------------------------------------------------------
    # Decode mode (serve_step)
    # ------------------------------------------------------------------
    def decode_step(self, params, cache, batch):
        """batch: {token: [B] int32, pos: [B] int32}. Returns (logits, cache)."""
        c = self.cfg
        x = self._stream(jnp.take(params["embed"], batch["token"], axis=0))
        positions = batch["pos"]
        x = shard_act(x, ("act_batch", "act_embed"))

        def period_body(xc, inputs):
            slot_params, cache_k = inputs
            new_caches = []
            for s, sk in enumerate(self.slots):
                xc, nc = self._block_decode(xc, slot_params[s], sk,
                                            cache_k[s], positions)
                new_caches.append(nc)
            return xc, new_caches

        if self.unroll:
            new_caches = []
            for k in range(self.num_periods):
                pk = jax.tree.map(lambda a: a[k], params["slots"])
                ck = jax.tree.map(lambda a: a[k], cache["slots"])
                x, nc = period_body(x, (pk, ck))
                new_caches.append(nc)
            new_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *new_caches)
        else:
            x, new_cache = jax.lax.scan(period_body, x,
                                        (params["slots"], cache["slots"]))
        x = self._compute(rms_norm(x, params["final_norm"], c.norm_eps))
        return self.logits(params, x), {"slots": new_cache}

    # ------------------------------------------------------------------
    # Caches
    # ------------------------------------------------------------------
    def _cache_width(self, sk: SlotKind, max_len: int) -> int:
        if sk.is_local and self.cfg.sliding_window:
            return min(self.cfg.sliding_window, max_len)
        return max_len

    def cache_specs(self, batch_size: int, max_len: int):
        """ShapeDtypeStruct pytree + logical-axes pytree for the decode cache."""
        c = self.cfg
        K = self.num_periods
        dt = jnp.dtype(c.dtype)
        specs, axes = [], []
        for sk in self.slots:
            if sk.kind == BLOCK_ATTN:
                W = self._cache_width(sk, max_len)
                sh = (K, batch_size, W, c.num_kv_heads, c.head_dim)
                ax = ("w_layers", "act_batch", "act_kv_seq", "act_kv_heads", None)
                specs.append({"k": jax.ShapeDtypeStruct(sh, dt),
                              "v": jax.ShapeDtypeStruct(sh, dt)})
                axes.append({"k": ax, "v": ax})
            else:
                m = c.mamba
                specs.append({
                    "conv": jax.ShapeDtypeStruct((K, batch_size, m.d_conv - 1, m.d_inner), dt),
                    "ssm": jax.ShapeDtypeStruct((K, batch_size, m.d_inner, m.d_state), jnp.float32),
                })
                axes.append({
                    "conv": ("w_layers", "act_batch", None, "act_mlp"),
                    "ssm": ("w_layers", "act_batch", "act_mlp", None),
                })
        return {"slots": specs}, {"slots": axes}

    def init_cache(self, batch_size: int, max_len: int):
        specs, _ = self.cache_specs(batch_size, max_len)
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), specs)

    # ------------------------------------------------------------------
    # Dry-run input specs (ShapeDtypeStruct stand-ins; no allocation)
    # ------------------------------------------------------------------
    def input_specs(self, shape: ShapeConfig):
        """Returns (batch_specs, batch_axes) for the given assigned shape."""
        c = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        dt = jnp.dtype(c.dtype)
        if shape.mode in ("train", "prefill"):
            if c.frontend == "frames":
                specs = {"frames": jax.ShapeDtypeStruct((B, S, c.d_model), dt),
                         "labels": jax.ShapeDtypeStruct((B, S), i32),
                         "loss_mask": jax.ShapeDtypeStruct((B, S), jnp.float32)}
                axes = {"frames": ("act_batch", "act_seq", None),
                        "labels": ("act_batch", "act_seq"),
                        "loss_mask": ("act_batch", "act_seq")}
            else:
                specs = {"tokens": jax.ShapeDtypeStruct((B, S), i32),
                         "labels": jax.ShapeDtypeStruct((B, S), i32)}
                axes = {"tokens": ("act_batch", "act_seq"),
                        "labels": ("act_batch", "act_seq")}
                if c.frontend == "patches":
                    specs["patch_embeds"] = jax.ShapeDtypeStruct(
                        (B, c.num_patches, c.d_model), dt)
                    axes["patch_embeds"] = ("act_batch", None, None)
            if shape.mode == "prefill":
                specs.pop("labels", None)
                axes.pop("labels", None)
            return specs, axes
        # decode / long_decode: one token + positions; cache comes separately
        specs = {"token": jax.ShapeDtypeStruct((B,), i32),
                 "pos": jax.ShapeDtypeStruct((B,), i32)}
        axes = {"token": ("act_batch",), "pos": ("act_batch",)}
        return specs, axes


def _chunked_ce(x, head, labels, mask, chunk, unroll: bool = False):
    """Cross-entropy summed over the sequence without materializing full logits."""
    B, S, D = x.shape
    nc = S // chunk
    xs = (x.reshape(B, nc, chunk, D).swapaxes(0, 1),
          labels.reshape(B, nc, chunk).swapaxes(0, 1),
          mask.reshape(B, nc, chunk).swapaxes(0, 1))

    def body(tot, inp):
        xc, lc, mc = inp
        logits = jnp.einsum("bsd,vd->bsv", xc, head).astype(jnp.float32)
        lp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(lp, lc[..., None], axis=-1)[..., 0]
        return tot - jnp.sum(ll * mc), None

    body = jax.remat(body)
    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), xs,
                          unroll=nc if unroll else 1)
    return tot


def build_model(cfg: ModelConfig, **kw) -> LM:
    return LM(cfg, **kw)
