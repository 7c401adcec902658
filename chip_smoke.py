"""Smoke run of the serving engine on one TPU chip.

    python chip_smoke.py

Serves a DeepSeek-Coder-33B function image (arXiv:2401.14196) at every
published width, cut to 8 of its 62 layers, with random weights from a fixed
seed, through the platform's own entry points: ConfigStore, build_tree and
Engine. It then checks the instance's own prefill and decode programs against
a float32 forward over the whole sequence, and runs each Pallas kernel
compiled (``impl="pallas"``) against its oracle in ``repro.kernels.ref``.

Every phase raises on failure, and nothing catches it. The last line of a
passing run is one JSON object naming the device. When JAX finds no TPU the
script exits non-zero before any other work: there is no CPU fallback. It
runs in one process and starts no other, so it alone holds the chip.

The readings it prints (cold start, compile seconds, tokens/s, peak device
memory) are smoke readings, not benchmark metrics.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config, register  # noqa: E402
from repro.core.config_store import ConfigStore, ImageRegistry  # noqa: E402
from repro.core.router import build_tree  # noqa: E402
from repro.core.types import FunctionConfig, Request  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serving.engine import Engine, Instance  # noqa: E402
from repro.serving.kv_cache import SlotCache  # noqa: E402

SEED = 0
FN = "coder"

# Engine path vs float32 reference. The engine keeps weights, activations and
# the KV cache in bf16 (unit roundoff 2^-9) and rounds ~15 times per layer, so
# ~120 roundings over 8 layers give an expected relative RMS error of about
# sqrt(120) * 2^-9 / sqrt(3) = 1.3% on the logits. The bounds leave 4x margin:
# relative RMS error per logit vector, and the largest error over the logit
# std (the max of ~32k errors sits near 4.3 sigma of the error).
PARITY_REL_RMS = 5e-2
PARITY_MAX_OVER_STD = 0.25
# Kernel vs oracle: both compute in f32 and round the output to bf16 once,
# which is at most 2^-8 of the largest element each; 2e-2 of max|oracle|
# leaves 2.5x over those two roundings for f32 summation-order differences.
KERNEL_TOL = 2e-2


class SmokeFailure(RuntimeError):
    pass


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def check_device():
    devices = jax.devices()
    print(f"[device] {devices}", flush=True)
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform!r}",
              file=sys.stderr)
        sys.exit(2)
    return devices[0]


def coder_config():
    """DeepSeek-Coder-33B at every published width, 8 of 62 layers."""
    base = get_config("deepseek_coder_33b")
    return dataclasses.replace(base, name=f"{base.name}_8l", num_layers=8)


class CompileClock:
    """Sums XLA backend compile time and persistent-cache hits, process-wide."""

    def __init__(self):
        self.secs, self.compiles, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# ------------------------------------------------------------------ serve
def serve(cfg, *, workers: int, concurrency: int, gen_tokens: int,
          max_len: int, prompts, seed: int) -> Instance:
    """Serve one request per prompt length through the router tree; return
    an instance of the image for the parity phase."""
    register(cfg)
    store = ConfigStore()
    store.put(FunctionConfig(name=FN, arch=cfg.name, concurrency=concurrency,
                             gen_tokens=gen_tokens))
    engine = Engine(build_tree(workers, fanout=2), store, ImageRegistry(),
                    seed=seed, max_len=max_len)
    rng = np.random.default_rng(seed)
    reqs = [Request(fn=FN, arrival_t=0.0, size=n,
                    payload=rng.integers(2, cfg.vocab_size, n))
            for n in prompts]
    t0 = time.monotonic()
    for r in reqs:
        engine.submit(r)
    results = engine.run()
    wall = time.monotonic() - t0

    require(sorted(r.rid for r in results) == sorted(r.rid for r in reqs),
            f"{len(results)} results for {len(reqs)} requests")
    require(all(r.ok for r in results), "a request failed")
    instances = [i for w in engine.workers.values()
                 for il in w.instances.values() for i in il]
    generated = {rid: toks for i in instances
                 for rid, toks in i.generated.items()}
    lens = [len(generated[r.rid]) for r in reqs]
    require(lens == [gen_tokens] * len(reqs),
            f"generated {lens} tokens, want {gen_tokens} each")
    tokens = sum(lens)
    print(f"[serve] requests={len(results)} ok=all tokens={tokens} "
          f"instances={len(instances)} wall_s={wall} "
          f"tok_per_s={tokens / wall} (wall includes cold start)", flush=True)
    print(f"[serve] cold_start_s={[i.cold_start_s for i in instances]}",
          flush=True)
    return instances[0]


# ----------------------------------------------------------------- parity
def parity(inst: Instance, *, prompt_len: int, steps: int, seed: int) -> dict:
    """Prefill one prompt with the instance's own program and params and
    decode ``steps`` greedy tokens with ``LM.decode_step``, the step the
    served decode program takes the argmax of, jitted alone for its logits;
    then compare every logit vector with ``LM.forward_seq`` over the whole
    sequence in float32."""
    model, params = inst.model, inst.params
    decode = jax.jit(model.decode_step)
    rng = np.random.default_rng(seed + 1)
    seq = [int(t) for t in rng.integers(2, model.cfg.vocab_size, prompt_len)]
    logits, pcache = inst._prefill(params, {"tokens": jnp.asarray([seq])})
    kv = SlotCache(model, inst.slots, inst.kv.max_len)
    kv.admit(0, pcache, prompt_len, rid=0, decode_steps=steps)
    got = [np.asarray(logits[0], np.float32)]
    for _ in range(steps):
        seq.append(int(np.argmax(got[-1])))
        tok = np.zeros(inst.slots, np.int32)
        tok[0] = seq[-1]
        logits, kv.cache = decode(
            params, kv.cache, {"token": jnp.asarray(tok),
                               "pos": kv.positions()})
        kv.advance()
        got.append(np.asarray(logits[0], np.float32))
    got = np.stack(got)                                    # [steps + 1, V]

    # unroll=True walks the layers in Python, so only one layer's f32
    # weights need be live at a time (the whole f32 tree would not fit)
    ref_model = build_model(model.cfg, unroll=True)

    def full_forward(params, tokens):
        p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x, _ = ref_model.forward_seq(p32, {"tokens": tokens},
                                     want_cache=False, remat=False)
        return ref_model.logits(p32, x[0, prompt_len - 1:])

    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(full_forward)(params, jnp.asarray([seq])),
                          np.float32)
    require(np.isfinite(got).all(), "non-finite engine logits")
    err = got - want
    rel_rms = float(np.max(np.sqrt(np.mean(err ** 2, -1))
                           / np.sqrt(np.mean(want ** 2, -1))))
    max_over_std = float(np.abs(err).max() / want.std())
    top1 = float(np.mean(got.argmax(-1) == want.argmax(-1)))
    print(f"[parity] positions={got.shape[0]} rel_rms_max={rel_rms} "
          f"(tol {PARITY_REL_RMS}) max_abs_over_std={max_over_std} "
          f"(tol {PARITY_MAX_OVER_STD}) top1_agree={top1}", flush=True)
    require(rel_rms <= PARITY_REL_RMS, f"parity rel_rms {rel_rms}")
    require(max_over_std <= PARITY_MAX_OVER_STD,
            f"parity max_abs_over_std {max_over_std}")
    return {"rel_rms": rel_rms, "max_abs_over_std": max_over_std}


# ---------------------------------------------------------------- kernels
@dataclasses.dataclass(frozen=True)
class KernelShapes:
    heads: int          # attention: q heads, kv heads, head dim
    kv_heads: int
    head_dim: int
    seq: int            # flash attention sequence length
    batch: int          # decode attention batch and cache length
    cache_len: int
    d_inner: int        # mamba scan channels, state size and length
    d_state: int
    scan_len: int
    d_model: int        # grouped matmul: contraction dim, experts, out dim
    experts: int
    expert_ff: int
    rows: int


def full_kernel_shapes(coder) -> KernelShapes:
    mamba = get_config("falcon_mamba_7b").mamba
    return KernelShapes(
        heads=coder.num_heads, kv_heads=coder.num_kv_heads,
        head_dim=coder.head_dim, seq=2048, batch=4, cache_len=4096,
        d_inner=mamba.d_inner, d_state=mamba.d_state, scan_len=512,
        d_model=coder.d_model, experts=8, expert_ff=2048, rows=1024)


def _check(name: str, out, want) -> float:
    out = np.asarray(out, np.float32)
    want = np.asarray(want, np.float32)
    require(out.shape == want.shape, f"{name}: shape {out.shape} vs {want.shape}")
    require(np.isfinite(out).all(), f"{name}: non-finite output")
    err = float(np.abs(out - want).max() / np.abs(want).max())
    print(f"[kernel] {name} shape={out.shape} max_err/max_ref={err} "
          f"(tol {KERNEL_TOL})", flush=True)
    require(err <= KERNEL_TOL, f"{name}: error {err}")
    return err


def kernels(s: KernelShapes, *, impl: str, seed: int) -> dict:
    """Call each Pallas kernel once through ``ops`` and check its oracle."""
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    bf = jnp.bfloat16

    def normal(shape, dtype=bf):
        return jax.random.normal(next(ks), shape, dtype)

    errs = {}
    q = normal((1, s.seq, s.heads, s.head_dim))
    k = normal((1, s.seq, s.kv_heads, s.head_dim))
    v = normal((1, s.seq, s.kv_heads, s.head_dim))
    errs["flash_attention"] = _check(
        "flash_attention",
        jax.jit(functools.partial(ops.flash_attention, causal=True,
                                  impl=impl))(q, k, v),
        jax.jit(functools.partial(ref.flash_attention_ref, causal=True))(q, k, v))

    q = normal((s.batch, s.heads, s.head_dim))
    kc = normal((s.batch, s.cache_len, s.kv_heads, s.head_dim))
    vc = normal((s.batch, s.cache_len, s.kv_heads, s.head_dim))
    pos = jax.random.randint(next(ks), (s.batch,), 1, s.cache_len)
    errs["decode_attention"] = _check(
        "decode_attention",
        jax.jit(functools.partial(ops.decode_attention, impl=impl))(q, kc, vc, pos),
        jax.jit(ref.decode_attention_ref)(q, kc, vc, pos))

    # as a prefill calls it: dt, B, C, A in f32, x and D in bf16
    dt = jax.nn.softplus(normal((1, s.scan_len, s.d_inner), jnp.float32)) * 0.1
    x = normal((1, s.scan_len, s.d_inner))
    B = normal((1, s.scan_len, s.d_state), jnp.float32)
    C = normal((1, s.scan_len, s.d_state), jnp.float32)
    A = -jnp.exp(normal((s.d_inner, s.d_state), jnp.float32) * 0.2)
    D = normal((s.d_inner,))
    (y, h), (y_ref, h_ref) = (
        jax.jit(functools.partial(ops.mamba_scan, impl=impl))(dt, x, B, C, A, D),
        jax.jit(ref.mamba_scan_ref)(dt, x, B, C, A, D))
    errs["mamba_scan"] = max(_check("mamba_scan", y, y_ref),
                             _check("mamba_scan final state", h, h_ref))

    block_t = 128
    xs = normal((s.rows, s.d_model))
    w = normal((s.experts, s.d_model, s.expert_ff))
    bmap = jax.random.randint(next(ks), (s.rows // block_t,), 0, s.experts)
    errs["grouped_matmul"] = _check(
        "grouped_matmul",
        jax.jit(functools.partial(ops.grouped_matmul, block_t=block_t,
                                  impl=impl))(xs, w, bmap),
        jax.jit(functools.partial(ref.grouped_matmul_ref,
                                  block_t=block_t))(xs, w, bmap))
    return errs


def main() -> int:
    t0 = time.monotonic()
    dev = check_device()
    print(f"[device] kind={dev.device_kind}", flush=True)
    print(f"[cache] dir={enable_compile_cache()}", flush=True)
    clock = CompileClock()

    coder = coder_config()
    inst = serve(coder, workers=2, concurrency=4, gen_tokens=32, max_len=2048,
                 prompts=(512, 1024) * 4, seed=SEED)
    parity(inst, prompt_len=512, steps=8, seed=SEED)
    del inst    # frees the slot caches; the image cache keeps the weights
    kernels(full_kernel_shapes(coder), impl="pallas", seed=SEED)

    stats = dev.memory_stats() or {}
    print(f"[memory] peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"bytes_limit={stats.get('bytes_limit')}", flush=True)
    print(f"[compile] backend_compile_s={clock.secs} programs={clock.compiles} "
          f"persistent_cache_hits={clock.cache_hits}", flush=True)
    print(f"[wall] total_s={time.monotonic() - t0}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
