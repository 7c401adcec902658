"""Whether what the timed path served is correct, against the plain reference.

After the window, a sample of the finished requests, drawn from the seed and
always holding the longest, is run through the float32 reference over each
prompt and its served tokens. Each served token is greedy, so its reference
logit should be the reference's best but for rounding: the number compared is
the widest gap by which a served token's reference logit lies below the
reference's best. A second number counts finished requests that served
another number of tokens than the function asks for; its limit is 0.

The limits of a cell are in ``limits/<cell>.json``, set from readings of
sound runs and of the fp8 control (``limits/<cell>.json`` and PERF.md give
the readings).
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from bench import reference

HERE = Path(__file__).resolve().parent
SAMPLE_TOKENS = 256        # served tokens compared, at least
SAMPLE_REQUESTS = 3        # requests compared, at least


def limits(cell: str) -> dict:
    return json.loads((HERE / "limits" / f"{cell}.json").read_text())["limits"]


def sample(finished: list, seed: int) -> list:
    """The longest finished request, then others in an order drawn from the
    seed, until the sample serves ``SAMPLE_TOKENS`` tokens."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-(r.P + len(r.served)), r.idx))
    rest = order[1:]
    rng = np.random.default_rng([seed, 4])
    picked = [order[0]] + [rest[i] for i in rng.permutation(len(rest))]
    out, tokens = [], 0
    for r in picked:
        out.append(r)
        tokens += len(r.served)
        if tokens >= SAMPLE_TOKENS and len(out) >= SAMPLE_REQUESTS:
            break
    return out


def gaps(cfg: dict, seed: int, reqs: list, quant: str = "none") -> tuple:
    """Reference gaps of the served tokens of ``reqs``; with a ``quant``
    control, the gaps of the tokens the control puts first instead."""
    seqs, rows = zip(*(reference.served_rows(r.prompt, r.served) for r in reqs))
    ref = reference.logits(cfg, seed, list(seqs), list(rows))
    if quant == "none":
        picks = [r.served for r in reqs]
    else:
        ctl = reference.logits(cfg, seed, list(seqs), list(rows), quant=quant)
        picks = [c.argmax(-1) for c in ctl]
    return np.concatenate([reference.gap(lg, t) for lg, t in zip(ref, picks)])


def numbers(cfg: dict, seed: int, finished: list, output_tokens: int) -> dict:
    """The numbers compared with their limits."""
    picked = sample(finished, seed)
    wrong_length = sum(len(r.served) != output_tokens for r in finished)
    g = gaps(cfg, seed, picked) if picked else np.asarray([np.inf])
    return {"max_logit_gap": float(g.max()), "wrong_length": float(wrong_length),
            "tokens_compared": len(g)}


def verdict(nums: dict, lim: dict) -> bool:
    return all(nums[k] <= v for k, v in lim.items())
