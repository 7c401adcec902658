"""Real serving engine: actual JAX execution, continuous batching, cold starts."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.config_store import ConfigStore, ImageRegistry
from repro.core.router import build_tree
from repro.core.types import FunctionConfig, Request
from repro.serving.engine import Engine, Worker


@pytest.fixture(scope="module")
def platform():
    store = ConfigStore()
    store.put(FunctionConfig(name="gen", arch="tiny_lm", concurrency=4,
                             gen_tokens=4, idle_timeout_s=60.0))
    return store, ImageRegistry()


@pytest.fixture(scope="module")
def engine(platform):
    store, registry = platform
    return Engine(build_tree(2, fanout=2), store, registry, max_len=64)


@pytest.mark.slow
def test_batched_requests_complete(engine):
    reqs = [Request(fn="gen", arrival_t=0.0, size=8) for _ in range(6)]
    for r in reqs:
        engine.submit(r)
    results = engine.run()
    assert len(results) == 6
    assert all(r.ok for r in results)
    assert {r.rid for r in results} == {r.rid for r in reqs}


@pytest.mark.slow
def test_cold_then_warm(engine):
    r1 = Request(fn="gen", arrival_t=0.0, size=8)
    engine.submit(r1)
    engine.run()
    r2 = Request(fn="gen", arrival_t=0.0, size=8)
    engine.submit(r2)
    res2 = engine.run()
    tel = engine.telemetry()
    cold_flags = {t.cold for t in tel}
    assert True in cold_flags         # first touch compiled
    assert res2[-1].ok


@pytest.mark.slow
def test_greedy_decode_matches_offline(platform):
    """Engine-generated tokens == offline greedy decode on the same params."""
    import jax
    import jax.numpy as jnp
    store, registry = platform
    w = Worker("w0", store, registry, max_len=64)
    req = Request(fn="gen", arrival_t=0.0, size=8)
    w.submit(req)
    results = w.drain()
    assert results and results[0].ok
    inst = w.instances["gen"][0]
    got = inst.generated[req.rid]

    # offline: same params, same prompt handling (bucket to 16 with zero pad)
    toks = np.zeros((1, 16), np.int32)
    toks[0, :8] = (np.arange(8) % 97 + 2)
    logits, cache = inst.model.prefill(inst.params, {"tokens": jnp.asarray(toks)})
    cache_w = inst.model.init_cache(1, 64)
    cache = jax.tree.map(
        lambda d, s: s if s.shape[1:] == d.shape[1:] and s.shape == d.shape
        else d.at[:, :1, :s.shape[2]].set(s.astype(d.dtype)) if d.ndim >= 3
        else d, cache_w, cache)
    exp = [int(jnp.argmax(logits[0]))]
    tok = exp[0]
    for i in range(3):
        lg, cache = inst.model.decode_step(
            inst.params, cache,
            {"token": jnp.asarray([tok]), "pos": jnp.asarray([16 + i])})
        tok = int(jnp.argmax(lg[0]))
        exp.append(tok)
    assert got[:2] == exp[:2], (got, exp)


@pytest.mark.slow
def test_within_instance_concurrency_real(platform):
    """c=1 spawns more instances than c=4 on the real engine too (RQ-A)."""
    store, registry = platform
    counts = {}
    for c in (1, 4):
        store.put(FunctionConfig(name="gen", arch="tiny_lm", concurrency=c,
                                 gen_tokens=2, idle_timeout_s=60.0))
        w = Worker(f"w-{c}", store, registry, max_len=64)
        for _ in range(4):
            w.submit(Request(fn="gen", arrival_t=0.0, size=8))
        w.drain()
        counts[c] = len(w.instances["gen"])
    store.put(FunctionConfig(name="gen", arch="tiny_lm", concurrency=4,
                             gen_tokens=4, idle_timeout_s=60.0))
    assert counts[1] == 4 and counts[4] == 1


@pytest.mark.slow
def test_telemetry_recorded(engine):
    tel = engine.telemetry()
    assert tel
    t = tel[-1]
    assert t.latency > 0 and t.fn == "gen" and len(t.features()) == 7


@pytest.mark.slow
@pytest.mark.parametrize("gen", [1, 3])
def test_request_generates_gen_tokens(platform, gen):
    """The prefill token counts: a request gets exactly gen_tokens tokens."""
    store, registry = platform
    fn = f"gen{gen}"
    store.put(FunctionConfig(name=fn, arch="tiny_lm", concurrency=2,
                             gen_tokens=gen, idle_timeout_s=60.0))
    w = Worker(f"w-{fn}", store, registry, max_len=64)
    reqs = [Request(fn=fn, arrival_t=0.0, size=8) for _ in range(3)]
    for r in reqs:
        w.submit(r)
    results = w.drain()
    assert sorted(r.rid for r in results) == sorted(r.rid for r in reqs)
    generated = {rid: toks for inst in w.instances[fn]
                 for rid, toks in inst.generated.items()}
    assert [len(generated[r.rid]) for r in reqs] == [gen] * len(reqs)
    assert all(inst.busy() == 0 for inst in w.instances[fn])


_WEIGHT_DIGEST = """
import hashlib, numpy as np, jax
from repro.models import build_model
from repro.configs import get_config
from repro.serving.engine import image_params
p = image_params(build_model(get_config("tiny_lm")), "tiny_lm")
h = hashlib.sha256()
for leaf in jax.tree.leaves(p):
    h.update(np.asarray(leaf, np.float32).tobytes())
print(h.hexdigest())
"""


def test_image_weights_same_in_every_process():
    """Weights seed from a stable digest of the arch, not Python's salted
    hash(): two processes with different hash seeds draw the same image."""
    digests = set()
    for hash_seed in ("1", "2"):
        env = dict(os.environ, PYTHONHASHSEED=hash_seed, JAX_PLATFORMS="cpu")
        out = subprocess.run([sys.executable, "-c", _WEIGHT_DIGEST], env=env,
                             capture_output=True, text=True, timeout=300,
                             check=True)
        digests.add(out.stdout.strip())
    assert len(digests) == 1


def test_positions_are_a_copy():
    """An advance() after positions() leaves what it returned unchanged. On
    the CPU backend jnp.asarray shares the memory of a NumPy array that is
    64-byte aligned, so the test gives the cache such an array."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving.kv_cache import SlotCache
    kv = SlotCache(build_model(get_config("tiny_lm")), 2, 16)
    buf = np.zeros(64 + kv.pos.nbytes, np.uint8)
    off = -buf.ctypes.data % 64
    kv.pos = buf[off:off + kv.pos.nbytes].view(np.int32)
    kv.pos[:] = [3, 5]
    kv.active[0] = True
    pos = kv.positions()
    kv.advance()
    assert np.asarray(pos).tolist() == [3, 5]
    assert kv.pos.tolist() == [4, 5]
