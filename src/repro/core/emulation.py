"""RQ-B: emulating worker nodes (paper §III.B, Fig. 2).

Pipeline, exactly as the figure prescribes:
  1. put a REAL worker under artificial load (``repro.serving.engine`` or the
     synthetic ground-truth sim) and save invocation telemetry;
  2. fit a model of the worker — "a simple linear regression model, or a more
     complicated model using machine learning": we provide closed-form ridge
     regression (jnp.linalg) and a small MLP trained with the framework's own
     AdamW;
  3. serve many emulated workers from the model (:class:`EmulatedServiceModel`
     plugs into the simulator as a service-time source);
  4. evaluate fidelity by replaying the step-1 load and comparing latency
     distributions (:func:`fidelity_report`).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import FunctionConfig, TelemetryRecord


def telemetry_matrix(records: Sequence[TelemetryRecord]):
    X = np.array([r.features() for r in records], np.float32)
    y = np.array([r.latency for r in records], np.float32)
    ok = np.array([r.ok for r in records], np.float32)
    return X, y, ok


# ---------------------------------------------------------------------------
# Models
# ---------------------------------------------------------------------------

@dataclass
class RidgeWorkerModel:
    """Closed-form ridge on standardized features; log-latency target."""
    w: np.ndarray = None
    mu: np.ndarray = None
    sd: np.ndarray = None
    resid_std: float = 0.05
    fail_rate: float = 0.0

    @staticmethod
    def fit(X: np.ndarray, y: np.ndarray, ok: np.ndarray, lam: float = 1e-3):
        mu, sd = X.mean(0), X.std(0) + 1e-8
        Xs = jnp.asarray((X - mu) / sd)
        Xs = jnp.concatenate([Xs, jnp.ones((Xs.shape[0], 1))], 1)
        ty = jnp.log(jnp.asarray(y) + 1e-6)
        A = Xs.T @ Xs + lam * jnp.eye(Xs.shape[1])
        w = jnp.linalg.solve(A, Xs.T @ ty)
        resid = np.asarray(ty - Xs @ w)
        return RidgeWorkerModel(w=np.asarray(w), mu=mu, sd=sd,
                                resid_std=float(resid.std()),
                                fail_rate=float(1 - ok.mean()))

    def predict(self, feats: np.ndarray, rng: np.random.Generator):
        xs = (feats - self.mu) / self.sd
        xs = np.append(xs, 1.0)
        ly = float(xs @ self.w) + rng.normal(0, self.resid_std)
        return float(np.exp(ly)), rng.random() >= self.fail_rate


@dataclass
class MLPWorkerModel:
    """2-hidden-layer MLP on standardized features, trained with repro's AdamW.
    The "more complicated model using machine learning" of the paper."""
    params: dict = None
    mu: np.ndarray = None
    sd: np.ndarray = None
    resid_std: float = 0.05
    fail_rate: float = 0.0

    @staticmethod
    def _fwd(params, x, xp=jnp):
        h = xp.tanh(x @ params["w1"] + params["b1"])
        h = xp.tanh(h @ params["w2"] + params["b2"])
        return (h @ params["w3"] + params["b3"])[..., 0]

    @staticmethod
    def fit(X: np.ndarray, y: np.ndarray, ok: np.ndarray, *, hidden: int = 32,
            steps: int = 400, lr: float = 3e-3, seed: int = 0):
        from repro.train.optimizer import AdamW
        mu, sd = X.mean(0), X.std(0) + 1e-8
        Xs = jnp.asarray((X - mu) / sd)
        ty = jnp.log(jnp.asarray(y) + 1e-6)
        k = jax.random.split(jax.random.PRNGKey(seed), 3)
        d = X.shape[1]
        params = {
            "w1": 0.5 * jax.random.normal(k[0], (d, hidden)) / np.sqrt(d),
            "b1": jnp.zeros(hidden),
            "w2": 0.5 * jax.random.normal(k[1], (hidden, hidden)) / np.sqrt(hidden),
            "b2": jnp.zeros(hidden),
            "w3": 0.5 * jax.random.normal(k[2], (hidden, 1)) / np.sqrt(hidden),
            "b3": jnp.zeros(1),
        }
        opt = AdamW(lr=lr)
        state = opt.init(params)

        @jax.jit
        def step(params, state):
            def loss(p):
                return jnp.mean((MLPWorkerModel._fwd(p, Xs) - ty) ** 2)
            l, g = jax.value_and_grad(loss)(params)
            params, state = opt.update(g, state, params)
            return params, state, l

        for _ in range(steps):
            params, state, l = step(params, state)
        resid = np.asarray(MLPWorkerModel._fwd(params, Xs) - ty)
        return MLPWorkerModel(params=jax.tree.map(np.asarray, params), mu=mu,
                              sd=sd, resid_std=float(resid.std()),
                              fail_rate=float(1 - ok.mean()))

    def predict(self, feats: np.ndarray, rng: np.random.Generator):
        # numpy, not jax: the simulator calls this per request, also inside
        # forked partitions (repro.parallel), which must not touch JAX
        xs = (feats - self.mu) / self.sd
        ly = float(self._fwd(self.params, xs[None], xp=np)[0])
        ly += rng.normal(0, self.resid_std)
        return float(np.exp(ly)), rng.random() >= self.fail_rate


# ---------------------------------------------------------------------------
# Simulator adapter + fidelity
# ---------------------------------------------------------------------------

class EmulatedServiceModel:
    """Plugs a fitted worker model into the Simulator (Fig. 2 step 3):
    'whenever a function is called on this emulated worker, it should have
    the same kind of answer within the same timeframes with a comparable
    failure rate.'"""

    def __init__(self, model, seed: int = 0):
        self.model = model
        self.rng = np.random.default_rng(seed)

    def sample(self, cfg: FunctionConfig, *, batch_size: int, queue_len: int,
               prompt: int, cold: bool, fn_cost: float):
        feats = np.array([queue_len, max(batch_size - 1, 0), batch_size,
                          1.0 if cold else 0.0, prompt, cfg.gen_tokens,
                          fn_cost], np.float32)
        lat, ok = self.model.predict(feats, self.rng)
        # clip to the function timeout: an unclipped lognormal tail on a noisy
        # fit can otherwise stall the event loop with day-long service times
        return min(lat, cfg.timeout_s), ok


def fidelity_report(real: np.ndarray, emulated: np.ndarray,
                    real_fail: float = 0.0, emu_fail: float = 0.0) -> dict:
    """Distribution closeness of latencies: percentile errors + KS distance."""
    qs = [50, 90, 95, 99]
    rep = {}
    for q in qs:
        r, e = np.percentile(real, q), np.percentile(emulated, q)
        rep[f"p{q}_rel_err"] = abs(e - r) / max(r, 1e-9)
    rep["mean_rel_err"] = abs(emulated.mean() - real.mean()) / max(real.mean(), 1e-9)
    # two-sample KS statistic
    allv = np.sort(np.concatenate([real, emulated]))
    cdf_r = np.searchsorted(np.sort(real), allv, side="right") / len(real)
    cdf_e = np.searchsorted(np.sort(emulated), allv, side="right") / len(emulated)
    rep["ks"] = float(np.abs(cdf_r - cdf_e).max())
    rep["fail_rate_err"] = abs(real_fail - emu_fail)
    return rep
