"""Drive the serving engine through one cell and record what a client sees.

The harness builds the platform's own entry (``Engine`` over a router tree
of workers, one instance each), hands it weights drawn from the seed, warms
every prompt bucket the mix can send, then opens the window. Requests go in
through ``Engine.submit`` when they come due; the harness steps each
``Worker`` itself and stamps every output token when the step that made it
returns. Nothing here changes what the engine computes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Optional

import jax
import numpy as np

from bench import traffic, weights
from bench.families import family

FN = "bench"
DRAIN_S = 60.0          # longest wait after the window for due requests


@dataclasses.dataclass
class Req:
    idx: int
    prompt: np.ndarray
    due: float                      # perf_counter seconds
    rid: int = -1
    submit_t: float = 0.0
    route_s: float = 0.0
    token_t: list = dataclasses.field(default_factory=list)
    done_t: Optional[float] = None
    served: Optional[np.ndarray] = None

    @property
    def P(self) -> int:
        return len(self.prompt)


@dataclasses.dataclass
class Step:
    """One worker step: what it prefilled and what it decoded."""
    prefills: list = dataclasses.field(default_factory=list)  # prompt lengths
    decoded: list = dataclasses.field(default_factory=list)   # keys each token saw


def model_config(cfg: dict):
    """The engine's ModelConfig for a configuration file, registered: the
    fields every family shares here, the rest from the family's file."""
    from repro.configs import get_config, register
    fields = family(cfg).engine_fields(cfg)
    mc = dataclasses.replace(
        get_config(cfg["arch"]), name=f"bench_{cfg['name']}",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        vocab_size=cfg["vocab_size"], tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"], **fields)
    return register(mc)


def _name(path) -> str:
    return ".".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def seeded_params(cfg: dict, seed: int):
    """``image_params`` for the engine: the weights of ``seed`` in the
    engine's tree, made on the device by one jitted program. Each weight's
    name, shape and dtype must be the family's."""
    def make(model, arch):
        flat, treedef = jax.tree_util.tree_flatten_with_path(model.abstract_params())
        got = {_name(p): (tuple(a.shape), str(a.dtype)) for p, a in flat}
        want = weights.layout(cfg)
        if got != want:
            raise RuntimeError(f"engine parameter tree {got} is not {want}")
        names = [_name(p) for p, _ in flat]

        def build(key):
            w = weights.all_weights(key, cfg)
            return jax.tree_util.tree_unflatten(treedef, [w[n] for n in names])
        return jax.jit(build)(weights.base_key(seed))
    return make


def pow2_at_least(n: int) -> int:
    b = 16
    while b < n:
        b *= 2
    return b


class Harness:
    def __init__(self, cfg: dict, mix: traffic.Mix, seed: int):
        from repro.core.config_store import ConfigStore, ImageRegistry
        from repro.core.router import build_tree
        from repro.core.types import FunctionConfig
        from repro.serving import engine as engine_mod

        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.clock = time.perf_counter
        dep = cfg["deployment"]
        self.slots, self.max_len = dep["slots"], dep["max_len"]
        self.fn_cfg = FunctionConfig(
            name=FN, arch=model_config(cfg).name, concurrency=self.slots,
            max_instances_per_worker=dep["instances_per_worker"],
            gen_tokens=mix.output_tokens, idle_timeout_s=1e9, timeout_s=1e9)
        self._image_params = engine_mod.image_params
        engine_mod.image_params = seeded_params(cfg, seed)
        self.store = ConfigStore()
        self.engine = engine_mod.Engine(
            build_tree(dep["workers"], fanout=2), self.store, ImageRegistry(),
            seed=seed, max_len=self.max_len)
        self.workers = list(self.engine.workers.values())
        self.reqs: list = []
        self.steps: list = []
        self.inflight: dict = {}      # rid -> Req
        self.where: dict = {}         # rid -> instance
        self.span = lambda name: contextlib.nullcontext()

    # ------------------------------------------------------------ warm-up
    def buckets(self) -> list:
        lengths = traffic.prompt_lengths(self.mix, self.seed, self.mix.block)
        return sorted({pow2_at_least(int(n)) for n in lengths})

    def warm_up(self) -> None:
        """Run every shape the window can use through the engine: all slots
        of every instance, and every prompt bucket of the mix."""
        self.store.put(dataclasses.replace(self.fn_cfg, gen_tokens=2))
        rng = np.random.default_rng([self.seed, 9])
        sizes = [self.buckets()[0]] * (len(self.workers) * self.slots)
        sizes += [b for b in self.buckets() for _ in self.workers]
        for group in (sizes[:len(self.workers) * self.slots],
                      sizes[len(self.workers) * self.slots:]):
            for n in group:
                self._submit(Req(-1, rng.integers(2, self.cfg["vocab_size"], n,
                                                  dtype=np.int32), 0.0))
            while self.inflight:
                self._step_all(record=False)
        for w in self.workers:
            il = w.instances.get(FN, [])
            if len(il) != 1 or il[0].slots != self.slots:
                raise RuntimeError(f"{w.name}: {len(il)} instances after warm-up")
        self.store.put(self.fn_cfg)
        self.reqs, self.inflight, self.where = [], {}, {}

    def instances(self):
        return [w.instances[FN][0] for w in self.workers]

    # ------------------------------------------------------------- serving
    def _submit(self, r: Req) -> None:
        from repro.core.types import Request
        t = self.clock()
        req = Request(fn=FN, arrival_t=t, size=r.P, payload=r.prompt)
        with self.span("bench.submit"):
            self.engine.submit(req)
        r.rid, r.submit_t, r.route_s = req.rid, t, self.clock() - t
        self.inflight[r.rid] = r
        if r.idx >= 0:
            self.reqs.append(r)

    def _observe(self, w, t: float, step: Step) -> None:
        """Stamp every token the worker's instances made since the last look."""
        for inst in w.instances.get(FN, []):
            gen = inst.generated
            for rid, r in self.inflight.items():
                toks = gen.get(rid)
                if toks is None or len(toks) == len(r.token_t):
                    continue
                self.where[rid] = inst
                for j in range(len(r.token_t), len(toks)):
                    r.token_t.append(t)
                    if j == 0:
                        step.prefills.append(r.P)
                    else:
                        step.decoded.append(r.P + j)

    def _step_all(self, record: bool = True) -> list:
        """One step of every worker; returns the requests completed."""
        done = []
        for w in self.workers:
            with self.span("bench.step"):
                results = w.step()
            t1 = self.clock()
            step = Step()
            with self.span("bench.observe"):
                self._observe(w, t1, step)
                for res in results:
                    r = self.inflight.pop(res.rid)
                    inst = self.where.pop(res.rid)
                    r.done_t = t1
                    r.served = np.asarray(inst.generated[res.rid], np.int32)
                    done.append(r)
                self.engine.view.update(w.state())
            if record:
                self.steps.append(step)
        return done

    def _request(self, idx: int, n: int, due: float) -> Req:
        return Req(idx, traffic.prompt_tokens(self.seed, idx, int(n),
                                              self.cfg["vocab_size"]), due)

    def run(self, t_open: float, seconds: float, on_open=None) -> float:
        """The measured window from ``t_open``; returns when it closes.
        ``on_open`` runs as it opens (the profiler starts there)."""
        close = t_open + seconds
        mix = self.mix
        if mix.loop == "open":
            dues = t_open + traffic.arrival_times(mix, self.seed, seconds)
            lengths = traffic.prompt_lengths(mix, self.seed, len(dues))
        else:
            lengths = traffic.prompt_lengths(mix, self.seed, 1 << 16)
        nxt = 0
        if on_open:
            on_open()
        with self.span("bench.window"):
            if mix.loop == "closed":
                for _ in range(mix.clients):
                    self._submit(self._request(nxt, lengths[nxt], self.clock()))
                    nxt += 1
            while True:
                now = self.clock()
                if now >= close:
                    break
                if mix.loop == "open":
                    while nxt < len(dues) and dues[nxt] <= now:
                        self._submit(self._request(nxt, lengths[nxt], dues[nxt]))
                        nxt += 1
                    if not self.inflight:
                        wake = dues[nxt] if nxt < len(dues) else close
                        with self.span("bench.wait"):
                            time.sleep(max(0.0, min(wake, close) - self.clock()))
                        continue
                for r in self._step_all():
                    if mix.loop == "closed" and self.clock() < close:
                        self._submit(self._request(nxt, lengths[nxt], self.clock()))
                        nxt += 1
        return close

    def drain(self) -> None:
        """Serve the requests due in the window to their end, a minute at
        most; the closed loop has none to wait for."""
        if self.mix.loop != "open":
            return
        end = self.clock() + DRAIN_S
        while self.inflight and self.clock() < end:
            self._step_all(record=False)

    def free(self) -> None:
        """Drop every array the engine holds, weights included, and give the
        engine back its own ``image_params``."""
        from repro.serving import engine as engine_mod
        self.engine = self.workers = self.where = None
        engine_mod._IMAGE_CACHE.clear()
        engine_mod.image_params = self._image_params
