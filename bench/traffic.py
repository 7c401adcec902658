"""One general traffic generator; each mix is a data file ``traffic/<name>.json``.

A mix gives the loop (``open``: Poisson arrivals at ``rate_per_s``; ``closed``:
``clients`` that each send their next request when the last one completes),
the prompt-length distribution and the output length. Every seed gets the
same work in another order: lengths and inter-arrival gaps are the quantiles
of their distributions at ``(i + 0.5) / block``, shuffled block by block, so
any whole number of blocks holds the same multiset of sizes and gaps. The
seed also draws every prompt's token ids.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    loop: str                  # open | closed
    prompt: dict               # dist, median, sigma, min, max, round
    output_tokens: int
    block: int = 32
    rate_per_s: float = 0.0    # open loop
    clients: int = 0           # closed loop


def load(name: str, directory: Path = HERE / "traffic") -> Mix:
    raw = json.loads((directory / f"{name}.json").read_text())
    mix = Mix(name=name, loop=raw["loop"], prompt=raw["prompt_tokens"],
              output_tokens=int(raw["output_tokens"]),
              block=int(raw.get("block", 32)),
              rate_per_s=float(raw.get("rate_per_s", 0.0)),
              clients=int(raw.get("clients", 0)))
    if mix.loop == "open" and mix.rate_per_s <= 0:
        raise ValueError(f"{name}: an open loop needs rate_per_s > 0")
    if mix.loop == "closed" and mix.clients <= 0:
        raise ValueError(f"{name}: a closed loop needs clients > 0")
    if mix.loop not in ("open", "closed"):
        raise ValueError(f"{name}: loop must be open or closed")
    return mix


def _quantiles(block: int) -> np.ndarray:
    return (np.arange(block) + 0.5) / block


def _length(spec: dict, u: float) -> int:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = spec["median"] * math.exp(spec["sigma"] * NormalDist().inv_cdf(u))
    if spec.get("round", "none") == "pow2":
        x = 2.0 ** round(math.log2(x))
    return int(min(max(round(x), spec["min"]), spec["max"]))


def _blocks(seed: int, tag: int, values: np.ndarray, n: int) -> np.ndarray:
    out = []
    for k in range(-(-n // len(values))):
        rng = np.random.default_rng([seed, tag, k])
        out.append(values[rng.permutation(len(values))])
    return np.concatenate(out)[:n]


def prompt_lengths(mix: Mix, seed: int, n: int) -> np.ndarray:
    """Prompt lengths of the first ``n`` requests."""
    values = np.asarray([_length(mix.prompt, u) for u in _quantiles(mix.block)])
    return _blocks(seed, 1, values, n).astype(np.int64)


def arrival_times(mix: Mix, seed: int, horizon_s: float) -> np.ndarray:
    """Open loop: due times in seconds after the window opens, up to the
    horizon. Gaps are exponential quantiles with mean ``1 / rate_per_s``."""
    gaps = -np.log1p(-_quantiles(mix.block)) / mix.rate_per_s
    n = mix.block
    while True:
        t = np.cumsum(_blocks(seed, 2, gaps, n))
        if t[-1] >= horizon_s:
            return t[t < horizon_s]
        n *= 2


def prompt_tokens(seed: int, idx: int, n: int, vocab: int) -> np.ndarray:
    """Token ids of request ``idx``: uniform over the vocabulary, ids 0 and
    1 left out."""
    rng = np.random.default_rng([seed, 3, idx])
    return rng.integers(2, vocab, n, dtype=np.int32)
