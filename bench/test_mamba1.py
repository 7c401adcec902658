"""The Mamba-1 family (Falcon-Mamba): it keeps the contract, the engine's
prefill and decode through ``Engine`` agree with its reference's logits, a
tiny run is correct and a broken mixer is caught, and its counts."""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import counts, derive, families, harness, reference, run, weights
from bench import trace as tr
from bench.test_harness import tiny_run

HERE = Path(__file__).resolve().parent
SEED = 2147495993
# At this size sound serving read max_logit_gap <= 0.0294 (seeds 1-12 of the
# open mix, 1.5 s windows, and three seeds of each loop); the fp8 control read
# >= 0.0895 on seeds 1-12. The test limit sits between.
TINY_MAMBA_LIMITS = {"max_logit_gap": 0.05, "wrong_length": 0}


def tiny_mamba(dtype="bfloat16"):
    cfg = json.loads((HERE / "testdata" / "tiny_mamba.json").read_text())
    return {**cfg, "torch_dtype": dtype}


def falcon():
    return json.loads((HERE / "configs" / "falcon_mamba7b.json").read_text())


def test_family_keeps_the_contract():
    fam = families.family(falcon())
    assert all(callable(getattr(fam, f)) for f in families.REQUIRED + ("prefill_bytes",))


@pytest.mark.parametrize("cfg", [tiny_mamba(), falcon()], ids=["tiny", "falcon"])
def test_layout_is_the_engine_tree(cfg):
    from repro.models import build_model
    model = build_model(harness.model_config(cfg))
    flat, _ = jax.tree_util.tree_flatten_with_path(model.abstract_params())
    got = {harness._name(p): (tuple(a.shape), str(a.dtype)) for p, a in flat}
    assert got == weights.layout(cfg)
    assert counts.params(cfg) == harness.model_config(cfg).param_count()


def test_engine_config_is_published_falcon_mamba():
    mc = harness.model_config(falcon())
    assert mc.attention_free and mc.d_ff == 0 and not mc.tie_embeddings
    assert mc.norm_eps == 1e-5 and mc.num_layers == 48
    assert dataclasses.astuple(mc.mamba) == (8192, 16, 4, 256, 1e-6, True)


def test_draws_follow_mamba1_init():
    cfg = tiny_mamba()
    w = weights.layer_weights(weights.base_key(7), cfg, 0)
    np.testing.assert_array_equal(np.asarray(w["A_log"], np.float32)[3],
                                  np.log(np.arange(1, 17, dtype=np.float32)).astype(jnp.bfloat16)
                                  .astype(np.float32))
    dt = np.log1p(np.exp(np.asarray(w["dt_bias"], np.float32)))
    assert 0.9e-3 < dt.min() and dt.max() < 0.11
    assert (np.asarray(w["D"]) == 1).all() and (np.asarray(w["conv_b"]) == 0).all()


def _recording(logs):
    """``image_programs`` whose model hands every prefill's and decode
    step's logits to ``logs`` as it runs; the programs are otherwise the
    engine's own."""
    from repro.serving import engine as engine_mod
    real = engine_mod.image_programs

    def programs(model):
        prefill, decode_step = model.prefill, model.decode_step

        def tap(kind, fn):
            def call(*a):
                logits, cache = fn(*a)
                jax.debug.callback(lambda lg: logs.append((kind, np.asarray(lg))),
                                   logits, ordered=True)
                return logits, cache
            return call
        model.prefill = tap("prefill", prefill)
        model.decode_step = tap("decode", decode_step)
        return real(model)
    return programs


def _serve(monkeypatch, cfg, prompts, gen, params):
    """Serve ``prompts`` at once on one worker; returns, per prompt, the
    tokens served and the logits each was taken from."""
    from repro.core.config_store import ConfigStore, ImageRegistry
    from repro.core.router import build_tree
    from repro.core.types import FunctionConfig, Request
    from repro.serving import engine as engine_mod
    logs = []
    monkeypatch.setattr(engine_mod, "image_programs", _recording(logs))
    monkeypatch.setattr(engine_mod, "image_params", lambda model, arch: params)
    store = ConfigStore()
    store.put(FunctionConfig(name="fm", arch=harness.model_config(cfg).name,
                             concurrency=len(prompts), gen_tokens=gen,
                             idle_timeout_s=1e9, timeout_s=1e9))
    eng = engine_mod.Engine(build_tree(1, fanout=2), store, ImageRegistry(),
                            max_len=cfg["deployment"]["max_len"])
    reqs = [Request(fn="fm", arrival_t=0.0, size=len(p), payload=p) for p in prompts]
    for r in reqs:
        eng.submit(r)
    assert all(res.ok for res in eng.run())
    inst = next(iter(eng.workers.values())).instances["fm"][0]
    jax.effects_barrier()
    pre = [lg for kind, lg in logs if kind == "prefill"][-len(prompts):]
    dec = [lg for kind, lg in logs if kind == "decode"][-(gen - 1):]
    # request i went to slot i: its first token from its prefill, the rest
    # from row i of each decode call
    return [(np.asarray(inst.generated[r.rid]),
             np.stack([pre[i][0]] + [d[i] for d in dec]).astype(np.float32))
            for i, r in enumerate(reqs)]


def _compare(monkeypatch, cfg, params):
    rng = np.random.default_rng(3)
    prompts = [rng.integers(2, cfg["vocab_size"], n, dtype=np.int32) for n in (16, 64)]
    served = _serve(monkeypatch, cfg, prompts, 8, params)
    seqs, rows = zip(*(reference.served_rows(p, s) for p, (s, _) in zip(prompts, served)))
    ref = reference.logits(cfg, SEED, list(seqs), list(rows))
    return [(lg, r) for (_, lg), r in zip(served, ref)]


def test_engine_matches_reference_f32(monkeypatch):
    """Float32 engine, the reference's weights: prefill and then decode
    through ``Engine`` give the reference's logits up to the order of float32
    sums (the engine's chunked associative scan against the reference's
    step-by-step one): 1e-4 of the logits' spread."""
    cfg = tiny_mamba("float32")
    w = jax.jit(lambda k: weights.all_weights(k, cfg))(weights.base_key(SEED))
    from repro.models import build_model
    flat, tree = jax.tree_util.tree_flatten_with_path(
        build_model(harness.model_config(cfg)).abstract_params())
    params = jax.tree_util.tree_unflatten(
        tree, [w[harness._name(p)].astype(jnp.float32) for p, _ in flat])
    for lg, ref in _compare(monkeypatch, cfg, params):
        assert lg.shape == ref.shape
        np.testing.assert_allclose(lg, ref, atol=1e-4 * ref.std(), rtol=0)


def test_engine_matches_reference_bf16(monkeypatch):
    """The configuration's bfloat16: activations and weights round to 8
    bits of mantissa at every matmul (the residual stream is float32, as
    published), so the logits differ from the float32 reference by about
    2^-8 of their size, accumulated over the layers and the decode steps:
    relative RMS error at most 3e-2, and no logit off by more than a
    quarter of the logits' spread."""
    cfg = tiny_mamba()
    from repro.models import build_model
    params = harness.seeded_params(cfg, SEED)(
        build_model(harness.model_config(cfg)), None)
    for lg, ref in _compare(monkeypatch, cfg, params):
        err = lg - ref
        assert np.sqrt(np.mean(err ** 2)) / ref.std() < 3e-2
        assert np.abs(err).max() / ref.std() < 0.25


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_tiny_run_is_correct(loop):
    res = tiny_run(loop, cfg=tiny_mamba())
    assert res["correct"], res["checks"]
    assert res["checks"]["max_logit_gap"]["value"] <= TINY_MAMBA_LIMITS["max_logit_gap"]


def _broken_mixer(monkeypatch, kind):
    """The engine's mixer without the dt/B/C normalisation, or without D."""
    from repro.models import mamba as mamba_mod
    forward, decode = mamba_mod.mamba_forward, mamba_mod.mamba_decode
    if kind == "norm":
        def fix(p, c):
            return p, dataclasses.replace(
                c, mamba=dataclasses.replace(c.mamba, mixer_rms_eps=0.0))
    else:
        def fix(p, c):
            return {**p, "D": jnp.zeros_like(p["D"])}, c
    monkeypatch.setattr(mamba_mod, "mamba_forward",
                        lambda x, p, c, **k: forward(x, *fix(p, c), **k))
    monkeypatch.setattr(mamba_mod, "mamba_decode",
                        lambda x, p, c, cache: decode(x, *fix(p, c), cache))


@pytest.mark.parametrize("kind", ["norm", "D"])
def test_broken_mixer_is_not_correct(monkeypatch, kind):
    _broken_mixer(monkeypatch, kind)
    res = tiny_run("open", cfg=tiny_mamba())
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > TINY_MAMBA_LIMITS["max_logit_gap"]


# --------------------------------------------------------------- counts
def test_counts_by_hand():
    c = falcon()
    L, D, V, DI, N, K, R = 48, 4096, 65024, 8192, 16, 4, 256
    mm = 2 * D * DI + DI * (R + 2 * N) + R * DI + DI * D
    assert counts.layer_params(c) == mm + K * DI + 3 * DI + DI * N + D == 105_312_256
    assert counts.params(c) == L * 105_312_256 + 2 * V * D + D == 5_587_668_992
    per_token = L * (2 * mm + 2 * K * DI + 6 * DI * N)
    assert counts.prefill_flops(c, 3) == 3 * per_token + 2 * D * V
    assert counts.decode_flops(c, [5, 900]) == 2 * (per_token + 2 * D * V)
    state = L * ((K - 1) * DI * 2 + DI * N * 4)
    weights_ = 2 * (L * 105_312_256 + V * D + D)
    fam = families.family(c)
    assert fam.prefill_bytes(c, 512) == weights_ + 2 * (512 * D + V) + state


def test_decode_bytes_count_only_active_slots_state():
    c = falcon()
    L, D, V, DI, N, K = 48, 4096, 65024, 8192, 16, 4
    state = L * ((K - 1) * DI * 2 + DI * N * 4)          # 0.027 GB a slot
    none = counts.decode_bytes(c, [])
    for seen in ([1], [300] * 3, [2048] * 8):
        # each active slot: its embedding row, its logits, its state read
        # and written; what a slot has seen does not matter
        assert counts.decode_bytes(c, seen) - none == len(seen) * (2 * (D + V) + 2 * state)
    assert counts.decode_bytes(c, [10] * 8) == counts.decode_bytes(c, [4000] * 8)


def _run_with(cfg, lengths, secs_per_prefill):
    """A run whose trace holds one prefill program per prompt, each taking
    the given seconds."""
    progs, t = [], 0.0
    for s in secs_per_prefill:
        progs.append(("jit_prefill", t, t + s))
        t += s + 1e-3
    steps = [harness.Step(prefills=[n]) for n in lengths]
    r = derive.Run(cfg=cfg, loop="closed", seconds=t, t_open=0.0, close=t,
                   setup_s=0.0, reqs=[], steps=steps,
                   peaks={"bf16_flop_per_s": 197e12, "hbm_bytes_per_s": 819e9},
                   compile_s=0.0)
    r.trace, r.window_s = tr.Trace(ops=[(s, e) for _, s, e in progs],
                                   programs=progs, spans=[]), t
    return r


def test_prefill_roofline_at_most_100_on_a_synthetic_trace():
    c, read = falcon(), run.reader("prefill_roofline.tput")
    fam = families.family(c)
    lengths = [128, 512, 2048]
    least = [max(counts.prefill_flops(c, n) / 197e12, fam.prefill_bytes(c, n) / 819e9)
             for n in lengths]
    # a chip that ran each prefill at its roofline reads 100%, a slower one less
    assert read(_run_with(c, lengths, least)) == pytest.approx(100.0)
    assert read(_run_with(c, lengths, [2 * s for s in least])) == pytest.approx(50.0)
    # the short prompt is bound by bytes, the long ones by operations
    assert fam.prefill_bytes(c, 128) / 819e9 > counts.prefill_flops(c, 128) / 197e12
    assert fam.prefill_bytes(c, 2048) / 819e9 < counts.prefill_flops(c, 2048) / 197e12
    # a program count that is not the prefills counted, or a family that
    # counts no prefill bytes, reads nothing
    assert read(_run_with(c, lengths, least[:2])) is None
    dense = json.loads((HERE / "configs" / "coder33b.json").read_text())
    assert read(_run_with(dense, [512], [1.0])) is None
