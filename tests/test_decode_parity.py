"""Prefill+decode == full forward (f32 exact; the system's key invariant).

MoE runs with no-drop capacity (capacity routing is not length-invariant by
design — Switch semantics); bf16 drift is covered by a loose sanity bound.
"""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import build_model

FAMILIES = ["deepseek_coder_33b", "gemma3_12b", "qwen3_32b", "moonshot_v1_16b",
            "falcon_mamba_7b", "jamba15_large", "phi3_vision"]


def _prep(arch, dtype):
    cfg = reduced(get_config(arch))
    cfg = replace(cfg, dtype=dtype)
    if cfg.moe is not None:
        cfg = replace(cfg, moe=replace(cfg.moe,
                                       capacity_factor=float(cfg.moe.num_experts)))
    return cfg


@pytest.mark.slow
@pytest.mark.parametrize("arch", FAMILIES)
def test_parity_f32_exact(arch, rng):
    cfg = _prep(arch, "float32")
    m = build_model(cfg, attn_block=8)
    params = m.init_params(rng)
    B, S, S0 = 2, 24, 16
    toks = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": toks}
    if cfg.frontend == "patches":
        batch["patch_embeds"] = jax.random.normal(
            rng, (B, cfg.num_patches, cfg.d_model), jnp.float32)

    x, _ = m.forward_seq(params, batch, want_cache=False)
    full = np.asarray(m.logits(params, x), np.float32)

    b0 = {k: (v[:, :S0] if k == "tokens" else v) for k, v in batch.items()}
    lg, cache = jax.jit(m.prefill)(params, b0)
    np.testing.assert_allclose(np.asarray(lg, np.float32), full[:, S0 - 1],
                               rtol=2e-3, atol=2e-3)

    cache_w = m.init_cache(B, S)
    cache = jax.tree.map(
        lambda d, s: s if s.shape == d.shape
        else d.at[:, :, :s.shape[2]].set(s.astype(d.dtype)), cache_w, cache)
    dec = jax.jit(m.decode_step)
    for t in range(S0, S):
        lg, cache = dec(params, cache,
                        {"token": toks[:, t], "pos": jnp.full((B,), t, jnp.int32)})
        np.testing.assert_allclose(np.asarray(lg, np.float32), full[:, t],
                                   rtol=2e-3, atol=2e-3, err_msg=f"{arch} t={t}")


@pytest.mark.slow
def test_parity_bf16_bounded(rng):
    """bf16 drift stays bounded (exactness is the f32 test's job)."""
    cfg = _prep("qwen3_32b", "bfloat16")
    m = build_model(cfg, attn_block=8)
    params = m.init_params(rng)
    B, S, S0 = 2, 20, 16
    toks = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    x, _ = m.forward_seq(params, {"tokens": toks}, want_cache=False)
    full = np.asarray(m.logits(params, x), np.float32)
    lg, cache = m.prefill(params, {"tokens": toks[:, :S0]})
    cache_w = m.init_cache(B, S)
    cache = jax.tree.map(
        lambda d, s: s if s.shape == d.shape
        else d.at[:, :, :s.shape[2]].set(s.astype(d.dtype)), cache_w, cache)
    errs = []
    for t in range(S0, S):
        lg, cache = m.decode_step(params, cache,
                                  {"token": toks[:, t],
                                   "pos": jnp.full((B,), t, jnp.int32)})
        errs.append(np.max(np.abs(np.asarray(lg, np.float32) - full[:, t])))
    assert max(errs) < 0.25, errs


@pytest.mark.slow
def test_ring_buffer_local_cache(rng):
    """gemma3 local slots keep a ring cache of width == sliding_window."""
    cfg = _prep("gemma3_12b", "float32")
    m = build_model(cfg, attn_block=8)
    B, S = 1, 48
    W = cfg.sliding_window
    assert W < S
    specs, _ = m.cache_specs(B, S)
    widths = [sl["k"].shape[2] for sl in specs["slots"] if "k" in sl]
    assert sorted(set(widths)) == [W, S]

    params = m.init_params(rng)
    toks = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    x, _ = m.forward_seq(params, {"tokens": toks}, want_cache=False)
    full = np.asarray(m.logits(params, x), np.float32)
    S0 = 32
    lg, cache = m.prefill(params, {"tokens": toks[:, :S0]})
    cache_w = m.init_cache(B, S)

    def blend(d, s):
        if s.shape == d.shape:
            return s
        if d.shape[2] == W and s.shape[2] == W:
            return s
        return d.at[:, :, :s.shape[2]].set(s.astype(d.dtype))
    cache = jax.tree.map(blend, cache_w, cache)
    for t in range(S0, S):
        lg, cache = m.decode_step(params, cache,
                                  {"token": toks[:, t],
                                   "pos": jnp.full((B,), t, jnp.int32)})
        np.testing.assert_allclose(np.asarray(lg, np.float32), full[:, t],
                                   rtol=3e-3, atol=3e-3, err_msg=f"t={t}")


@pytest.mark.parametrize("eps", [1e-6, 0.0], ids=["falcon_norm", "no_norm"])
def test_mamba_decode_matches_forward_step_by_step(rng, eps):
    """Falcon-Mamba's mixer with its dt/B/C normalisation (and Mamba-1's
    without): one-token decode from a zero state, step by step, gives the
    sequence mode's output at every position (f32)."""
    from repro.models import mamba as mamba_mod
    cfg = _prep("falcon_mamba_7b", "float32")
    assert cfg.mamba.mixer_rms_eps == 1e-6          # kept by reduced()
    cfg = replace(cfg, mamba=replace(cfg.mamba, mixer_rms_eps=eps))
    m = build_model(cfg)
    p = jax.tree.map(lambda a: a[0], m.init_params(rng)["slots"][0])
    B, S = 2, 12
    x = jax.random.normal(jax.random.PRNGKey(3), (B, S, cfg.d_model), jnp.float32)
    full, cache_seq = mamba_mod.mamba_forward(x, p, cfg, chunk=4)
    if eps:     # the normalisation changes the mixer, so parity means something
        plain, _ = mamba_mod.mamba_forward(
            x, p, replace(cfg, mamba=replace(cfg.mamba, mixer_rms_eps=0.0)), chunk=4)
        assert np.abs(np.asarray(full) - np.asarray(plain)).max() > 1e-2
    mc = cfg.mamba
    cache = {"conv": jnp.zeros((B, mc.d_conv - 1, mc.d_inner), jnp.float32),
             "ssm": jnp.zeros((B, mc.d_inner, mc.d_state), jnp.float32)}
    step = jax.jit(lambda xt, c: mamba_mod.mamba_decode(xt, p, cfg, c))
    for t in range(S):
        y, cache = step(x[:, t], cache)
        np.testing.assert_allclose(np.asarray(y), np.asarray(full[:, t]),
                                   rtol=1e-4, atol=1e-4, err_msg=f"t={t}")
    for k in ("conv", "ssm"):
        np.testing.assert_allclose(np.asarray(cache[k]), np.asarray(cache_seq[k]),
                                   rtol=1e-4, atol=1e-4)


def test_float32_residual_stream(rng):
    """Falcon-Mamba publishes ``residual_in_fp32``: in a bfloat16 model the
    stream between layers stays float32, so prefill and decode logits lie
    closer to the float32 model's than with a bfloat16 stream, while the
    logits and the slot state keep the model's dtypes."""
    base = replace(_prep("falcon_mamba_7b", "bfloat16"), num_layers=12)
    assert base.residual_in_fp32
    params = build_model(base).init_params(rng)
    B, S = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(5), (B, S + 1), 0, base.vocab_size)
    step = {"token": toks[:, S], "pos": jnp.full((B,), S, jnp.int32)}

    def serve(cfg, p):
        m = build_model(cfg)
        lg, cache = jax.jit(m.prefill)(p, {"tokens": toks[:, :S]})
        lg2, cache2 = jax.jit(m.decode_step)(p, cache, step)
        return lg, lg2, cache2, m.init_cache(B, S)

    f32 = replace(base, dtype="float32")
    want = serve(f32, jax.tree.map(lambda a: a.astype(jnp.float32), params))[:2]
    err = {}
    for stream32 in (True, False):
        lg, lg2, cache, blank = serve(
            replace(base, mamba=replace(base.mamba, residual_in_fp32=stream32)), params)
        assert lg.dtype == lg2.dtype == jnp.bfloat16
        assert jax.tree.map(lambda a: a.dtype, cache) == jax.tree.map(lambda a: a.dtype, blank)
        err[stream32] = sum(float(jnp.sqrt(jnp.mean((g.astype(jnp.float32) - w) ** 2)))
                            for g, w in zip((lg, lg2), want))
    assert err[True] < 0.9 * err[False], err


def test_falcon_mamba_config_as_published():
    from repro.configs.base import MambaConfig
    f = get_config("falcon_mamba_7b")
    assert (f.num_layers, f.d_model, f.vocab_size, f.norm_eps, f.tie_embeddings,
            f.residual_in_fp32) == (64, 4096, 65024, 1e-5, False, True)
    assert f.mamba == MambaConfig(8192, 16, 4, 256, 1e-6, residual_in_fp32=True)
    assert abs(f.param_count() / 1e9 - 7.27) < 0.005
    assert get_config("jamba15_large").mamba.mixer_rms_eps == 0.0
    assert f.recurrent and get_config("jamba15_large").recurrent
    assert not get_config("deepseek_coder_33b").recurrent
