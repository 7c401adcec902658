"""Slot state of two kinds: a recurrent slot is replaced whole, a KV slot
padded; the bytes of one slot's state by kind; and a model with recurrent
layers refuses a prompt that would be zero-padded into its state."""
import math
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced, register
from repro.core.config_store import ConfigStore, ImageRegistry
from repro.core.router import build_tree
from repro.core.types import FunctionConfig, Request
from repro.models import build_model
from repro.serving.engine import Engine
from repro.serving.kv_cache import KV, RECURRENT, SlotCache

SLOTS, MAX_LEN, PROMPT = 3, 32, 8


@pytest.fixture(scope="module")
def hybrid():
    """A reduced Jamba: attention (KV) and Mamba (recurrent) slots side by
    side in one cache."""
    cfg = replace(reduced(get_config("jamba15_large")), dtype="float32")
    model = build_model(cfg, attn_block=8)
    params = model.init_params(jax.random.PRNGKey(1))
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, PROMPT), 0, cfg.vocab_size)
    _, pcache = jax.jit(model.prefill)(params, {"tokens": toks})
    return cfg, model, pcache


def _kinds(model):
    """(slot kind, cache leaf) pairs of the model, by hand."""
    out = []
    for sk in model.slots:
        keys = ("k", "v") if sk.kind == "attn" else ("conv", "ssm")
        out += [(sk.kind, k) for k in keys]
    return out


def test_admit_replaces_recurrent_slot_and_pads_kv_slot(hybrid):
    cfg, model, pcache = hybrid
    kv = SlotCache(model, SLOTS, MAX_LEN)
    # a slot that held another sequence: its old state must not survive
    kv.cache = jax.tree.map(lambda c: jnp.full_like(c, 7.0), kv.cache)
    kv.admit(1, pcache, PROMPT, rid=5, decode_steps=4)
    seen = set()
    for s, slot_cache in enumerate(kv.cache["slots"]):
        for name, c in slot_cache.items():
            p = np.asarray(pcache["slots"][s][name])
            c = np.asarray(c)
            np.testing.assert_array_equal(c[:, [0, 2]], 7.0)     # other slots kept
            if name in ("k", "v"):
                np.testing.assert_array_equal(c[:, 1, :PROMPT], p[:, 0])
                np.testing.assert_array_equal(c[:, 1, PROMPT:], 0.0)
            else:
                np.testing.assert_array_equal(c[:, 1], p[:, 0])
            seen.add((model.slots[s].kind, name))
    assert seen == set(_kinds(model))
    assert {"attn", "mamba"} == {k for k, _ in seen}
    assert kv.pos[1] == PROMPT and kv.active[1] and kv.remaining[1] == 4


def test_state_bytes_by_kind(hybrid):
    cfg, model, _ = hybrid
    m, K = cfg.mamba, model.num_periods
    n_attn = sum(sk.kind == "attn" for sk in model.slots)
    n_mamba = len(model.slots) - n_attn
    f32 = 4
    want = {KV: n_attn * K * 2 * MAX_LEN * cfg.num_kv_heads * cfg.head_dim * f32,
            RECURRENT: n_mamba * K * ((m.d_conv - 1) * m.d_inner * f32
                                      + m.d_inner * m.d_state * 4)}
    kv = SlotCache(model, SLOTS, MAX_LEN)
    assert kv.state_bytes == want
    total = sum(math.prod(c.shape) * c.dtype.itemsize for c in jax.tree.leaves(kv.cache))
    assert sum(want.values()) * SLOTS == total


def test_state_bytes_of_pure_kinds():
    dense = build_model(reduced(get_config("deepseek_coder_33b")))
    ssm = build_model(reduced(get_config("falcon_mamba_7b")))
    assert SlotCache(dense, 2, 64).state_bytes[RECURRENT] == 0
    assert SlotCache(dense, 2, 64).state_bytes[KV] > 0
    # a recurrent slot does not grow with max_len
    a, b = SlotCache(ssm, 2, 64).state_bytes, SlotCache(ssm, 2, 4096).state_bytes
    assert a == b and a[KV] == 0 and a[RECURRENT] > 0


def test_recurrent_model_refuses_a_padded_prompt():
    cfg = register(replace(reduced(get_config("falcon_mamba_7b")), name="tiny_mamba_refusal"))
    store = ConfigStore()
    store.put(FunctionConfig(name="fm", arch=cfg.name, concurrency=2, gen_tokens=3,
                             idle_timeout_s=60.0))
    mamba_engine = Engine(build_tree(1, fanout=2), store, ImageRegistry(), max_len=64)
    padded = Request(fn="fm", arrival_t=0.0, size=20)     # bucket 32
    exact = Request(fn="fm", arrival_t=0.0, size=16)
    mamba_engine.submit(padded)
    mamba_engine.submit(exact)
    res = {r.rid: r for r in mamba_engine.run()}
    assert not res[padded.rid].ok
    assert "ROADMAP 2.1" in res[padded.rid].error
    assert res[exact.rid].ok
    inst = next(iter(mamba_engine.workers.values())).instances["fm"][0]
    assert padded.rid not in inst.generated
    assert len(inst.generated[exact.rid]) == 3
    assert inst.state_bytes == inst.kv.state_bytes and inst.state_bytes[KV] == 0

