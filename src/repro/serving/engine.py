"""The REAL worker engine: executes registered functions as actual JAX models
on the local device, with continuous batching, measured cold starts, idle
lifecycle, and full telemetry — paper Fig. 2 step 1's "actual server".

A :class:`Worker` owns function instances; an instance is (params, compiled
prefill/decode, SlotCache). Cold start = param materialization + first-shape
jit, measured with a wall clock and charged to the triggering request — the
HyperFaaS analogue of a container pull + boot.

The :class:`Engine` glues a router tree over N workers in one process, all on
JAX's default device (the TPU where there is one; the CPU backend in the
tests). It is intentionally synchronous and deterministic; massive-load
studies use the simulator with workers emulated from THIS engine's telemetry.
"""
from __future__ import annotations

import time
import zlib
from collections import deque
from dataclasses import dataclass
from random import Random
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs import get_config
from repro.core.config_store import ConfigStore, ImageRegistry
from repro.core.router import LBNode, StateView, WorkerState
from repro.core.types import FunctionConfig, Request, RequestResult, TelemetryRecord
from repro.models import build_model
from repro.serving.kv_cache import SlotCache


def _bucket(n: int) -> int:
    b = 16
    while b < n:
        b *= 2
    return b


def _refusal(req: Request, cfg: FunctionConfig) -> str:
    """Why ``req`` cannot be served, or "". A prompt is zero-padded to its
    bucket, and in a model with recurrent layers the pads would run through
    the recurrence and change the state every later token reads: such a
    model takes only prompts whose length is a bucket."""
    if _bucket(req.size) == req.size or not get_config(cfg.arch).recurrent:
        return ""
    return (f"{cfg.arch} has recurrent layers and cannot serve a prompt of "
            f"{req.size} tokens zero-padded to {_bucket(req.size)}: it needs "
            f"per-sequence prompt lengths (ROADMAP 2.1)")


# "image layer cache": the same function image (arch, slots) yields the same
# weights and compiled programs — first pull pays the full compile cold start,
# replica instances hit the cache (exactly a container image/layer cache).
_IMAGE_CACHE: Dict[tuple, tuple] = {}


def image_params(model, arch: str):
    """An image's weights: random, seeded by a stable digest of ``arch`` (the
    same in every process), drawn by one jitted program so each leaf lands in
    the model dtype without an f32 copy of the whole tree."""
    seed = zlib.crc32(arch.encode()) % 2**31
    return jax.jit(model.init_params)(jax.random.PRNGKey(seed))


def image_programs(model):
    """The jitted (prefill, decode) programs every instance of ``model`` runs.

    The decode program is ``model.decode_step`` with greedy feedback kept on
    the device: ``decode(params, (cache, fed), active)`` with ``fed`` =
    ``{token, pos}`` per slot returns ``(token, (cache, fed))``, each slot's
    greedy token as ``int32[slots]`` and what the next call takes: the new
    cache, those tokens, and ``pos + active``. Inactive slots decode garbage
    that nobody reads, and their positions stay."""
    def decode_step(params, state, active):
        cache, fed = state
        logits, cache = model.decode_step(params, cache, fed)
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return token, (cache, {"token": token, "pos": fed["pos"] + active})

    return jax.jit(model.prefill), jax.jit(decode_step)


def upload_feedback(last_tok, pos, active) -> tuple:
    """The device's copies of a decode call's inputs, ``(fed, active)``, from
    the host's mirrors. Copies: on the CPU backend a device array may share
    the memory of the NumPy array it was made from."""
    return jax.device_put(({"token": last_tok.copy(), "pos": pos.copy()},
                           active.copy()))


class Instance:
    def __init__(self, iid: str, cfg: FunctionConfig, *, rng_seed: int = 0,
                 max_len: int = 256):
        self.iid = iid
        self.cfg = cfg
        t0 = time.monotonic()
        slots = cfg.concurrency if cfg.concurrency > 0 else cfg.max_instances_per_worker
        self.slots = slots
        key = (cfg.arch, slots, max_len)
        if key not in _IMAGE_CACHE:
            mcfg = get_config(cfg.arch)
            model = build_model(mcfg)
            params = image_params(model, cfg.arch)
            prefill, decode = image_programs(model)
            # shape warmup = the dominant cold-start cost (compile)
            kv0 = SlotCache(model, slots, max_len)
            warm = {"tokens": jnp.zeros((1, 16), jnp.int32)}
            jax.block_until_ready(prefill(params, warm)[0])
            fed, active = upload_feedback(np.zeros(slots, np.int32), kv0.pos,
                                          kv0.active)
            jax.block_until_ready(decode(params, (kv0.cache, fed), active)[0])
            _IMAGE_CACHE[key] = (model, params, prefill, decode)
        self.model, self.params, self._prefill, self._decode = _IMAGE_CACHE[key]
        self.kv = SlotCache(self.model, slots, max_len)
        self.cold_start_s = time.monotonic() - t0
        self.last_used = time.monotonic()
        self.sampler = Random(rng_seed)
        self._last_tok = np.zeros(slots, np.int32)   # greedy-decode feedback
        # the device's copies of _last_tok, kv.pos and kv.active: the last
        # decode call's outputs, or an upload after an admission or release
        self._fed = self._active = None
        self.decode_calls = 0
        self.feedback_uploads = 0
        self.state_bytes = self.kv.state_bytes      # one slot's, by kind
        self._slot_meta: Dict[int, object] = {}
        self.generated: Dict[int, list] = {}         # rid -> token ids

    def busy(self) -> int:
        return int(self.kv.active.sum())


@dataclass
class _Pending:
    req: Request
    submit_t: float


class Worker:
    def __init__(self, name: str, store: ConfigStore, registry: ImageRegistry,
                 *, max_len: int = 256):
        self.name = name
        self.store = store
        self.registry = registry
        self.max_len = max_len
        self.instances: Dict[str, List[Instance]] = {}
        self.pending: deque = deque()
        self.telemetry: List[TelemetryRecord] = []
        self._iid = 0

    # ------------------------------------------------------------- state
    def state(self) -> WorkerState:
        return WorkerState(
            worker=self.name, queue_len=len(self.pending),
            inflight=sum(i.busy() for il in self.instances.values() for i in il),
            capacity=max(sum(i.slots for il in self.instances.values()
                             for i in il), 1),
            warm_fns=frozenset(fn for fn, il in self.instances.items() if il))

    def submit(self, req: Request):
        with TraceAnnotation("engine.enqueue", rid=req.rid):
            self.pending.append(_Pending(req, time.monotonic()))

    # ---------------------------------------------------------- lifecycle
    def _has_room(self, cfg: FunctionConfig) -> bool:
        il = self.instances.get(cfg.name, [])
        return (any(inst.kv.free_slots() for inst in il)
                or len(il) < cfg.max_instances_per_worker)

    def _get_instance(self, cfg: FunctionConfig):
        """An instance of ``cfg`` with a free slot, cold-starting one where
        none has one; the caller has checked :meth:`_has_room`."""
        il = self.instances.setdefault(cfg.name, [])
        for inst in il:
            if inst.kv.free_slots():
                return inst, False
        self._iid += 1
        inst = Instance(f"{self.name}/i{self._iid}", cfg,
                        rng_seed=self._iid, max_len=self.max_len)
        il.append(inst)
        return inst, True

    def reap_idle(self):
        now = time.monotonic()
        for fn, il in self.instances.items():
            cfg = self.store.get(fn)
            for inst in list(il):
                if inst.busy() == 0 and now - inst.last_used > cfg.idle_timeout_s:
                    il.remove(inst)

    # ------------------------------------------------------------- serve
    def step(self) -> List[RequestResult]:
        """Admit pending into slots, run ONE decode step on every instance
        with active slots, and complete finished sequences."""
        with TraceAnnotation("engine.step"):
            results = []
            still = deque()
            while self.pending:
                p = self.pending.popleft()
                cfg = self.store.get(p.req.fn)
                refusal = _refusal(p.req, cfg)
                if refusal:
                    results.append(self._refused(p, refusal))
                    continue
                if not self._has_room(cfg):
                    still.append(p)
                    continue
                with TraceAnnotation("engine.admit", rid=p.req.rid):
                    self._admit(p, cfg)
            self.pending = still
            for il in self.instances.values():
                for inst in il:
                    self._complete(inst, results)   # requests done at prefill
                    if inst.busy():
                        self._decode_instance(inst)
                        self._complete(inst, results)
            return results

    def _admit(self, p: _Pending, cfg: FunctionConfig):
        """Prefill ``p`` into a free slot and take its first token."""
        inst, cold = self._get_instance(cfg)
        rid = p.req.rid
        slot = inst.kv.free_slots()[0]
        bl = _bucket(p.req.size)
        toks = np.zeros((1, bl), np.int32)
        payload = np.asarray(p.req.payload if p.req.payload is not None
                             else np.arange(p.req.size) % 97 + 2)
        toks[0, :p.req.size] = payload[:p.req.size]
        with TraceAnnotation("engine.prefill", rid=rid):
            logits, pcache = inst._prefill(inst.params,
                                           {"tokens": jnp.asarray(toks)})
            jax.block_until_ready(logits)
        # prefill yields the first of the request's gen_tokens tokens
        inst.kv.admit(slot, pcache, bl, rid, cfg.gen_tokens - 1)
        with TraceAnnotation("engine.first_token", rid=rid):
            inst._last_tok[slot] = int(jnp.argmax(logits[0]))
        inst.generated[rid] = [int(inst._last_tok[slot])]
        inst.last_used = time.monotonic()
        self.telemetry.append(TelemetryRecord(
            fn=p.req.fn, t=p.submit_t, queue_len=len(self.pending),
            inflight=inst.busy() - 1, batch_size=inst.busy(),
            cold=cold, prompt_tokens=p.req.size,
            gen_tokens=cfg.gen_tokens,
            fn_cost=get_config(cfg.arch).param_count() / 1e7,
            latency=0.0, ok=True))
        p._telemetry_idx = len(self.telemetry) - 1
        p._cold = cold
        inst._slot_meta[slot] = p

    def _refused(self, p: _Pending, error: str) -> RequestResult:
        now = time.monotonic()
        return RequestResult(
            rid=p.req.rid, fn=p.req.fn, ok=False, arrival_t=p.submit_t,
            start_t=now, finish_t=now, cold_start=False, worker=self.name,
            instance="", error=error)

    def _decode_instance(self, inst: Instance):
        """One decode step over every slot of ``inst``: one program, which
        feeds each slot's greedy token back on the device, and one pull of
        those tokens. The host's mirrors go up first only where an admission
        or a release changed them since the last call."""
        with TraceAnnotation("engine.decode"):
            inst.decode_calls += 1
            if inst.kv.changed:
                inst._fed, inst._active = upload_feedback(
                    inst._last_tok, inst.kv.pos, inst.kv.active)
                inst.kv.changed = False
                inst.feedback_uploads += 1
            tok, (inst.kv.cache, inst._fed) = inst._decode(
                inst.params, (inst.kv.cache, inst._fed), inst._active)
            with TraceAnnotation("engine.sample"):
                nxt = np.asarray(tok)
                for s in range(inst.slots):
                    if inst.kv.active[s]:
                        inst._last_tok[s] = nxt[s]
                        rid = int(inst.kv.rid[s])
                        if rid in inst.generated:
                            inst.generated[rid].append(int(nxt[s]))
                inst.kv.advance()
        inst.last_used = time.monotonic()

    def _complete(self, inst: Instance, results: List[RequestResult]):
        for slot in inst.kv.finished_slots():
            p = inst._slot_meta.pop(slot)
            with TraceAnnotation("engine.complete", rid=p.req.rid):
                inst.kv.release(slot)
                now = time.monotonic()
                rec = self.telemetry[p._telemetry_idx]
                rec.latency = now - p.submit_t
                results.append(RequestResult(
                    rid=p.req.rid, fn=p.req.fn, ok=True,
                    arrival_t=p.submit_t, start_t=p.submit_t,
                    finish_t=now, cold_start=p._cold,
                    worker=self.name, instance=inst.iid))

    def drain(self) -> List[RequestResult]:
        out = []
        while self.pending or any(i.busy() for il in self.instances.values()
                                  for i in il):
            out.extend(self.step())
        return out


class Engine:
    """Router tree over real in-process workers."""

    def __init__(self, tree: LBNode, store: ConfigStore,
                 registry: ImageRegistry, *, seed: int = 0, max_len: int = 256):
        self.tree = tree
        self.store = store
        self.view = StateView()
        self.rng = Random(seed)
        self.workers = {w: Worker(w, store, registry, max_len=max_len)
                        for w in tree.all_workers()}
        for w in self.workers.values():
            self.view.update(w.state())

    def submit(self, req: Request):
        with TraceAnnotation("engine.route", rid=req.rid):
            wid, _ = self.tree.route(req, self.view, self.rng, time.monotonic())
        self.workers[wid].submit(req)
        self.view.update(self.workers[wid].state())

    def run(self) -> List[RequestResult]:
        results = []
        while True:
            progressed = False
            for w in self.workers.values():
                r = w.step()
                if r or w.pending:
                    progressed = True
                results.extend(r)
                self.view.update(w.state())
            if not progressed and not any(
                    i.busy() for w in self.workers.values()
                    for il in w.instances.values() for i in il):
                break
        return results

    def telemetry(self) -> List[TelemetryRecord]:
        out = []
        for w in self.workers.values():
            out.extend(w.telemetry)
        return out
