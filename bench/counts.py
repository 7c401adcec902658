"""Operations and bytes of the served programs, counted by the model's family.

Counted from the configuration's shapes, as the algorithm needs them: a
multiply-add is two operations, causal attention counts only the keys a
query sees, and bytes are those a step has to move at the least (each weight
read once, the state of valid positions read once, the new state and the
logits written once). Padding, masked positions and copies that the program
makes beyond that are waste and are not counted, so a share of the roofline
built on these counts cannot pass 100%. Each family counts in its own file
(``families/<reference>.py``); these functions hand the configuration to it.
"""
from __future__ import annotations

from bench.families import family


def layer_params(cfg: dict) -> int:
    return family(cfg).layer_params(cfg)


def params(cfg: dict) -> int:
    return family(cfg).params(cfg)


def prefill_flops(cfg: dict, n: int) -> int:
    """One prompt of ``n`` tokens, logits of its last token only."""
    return family(cfg).prefill_flops(cfg, n)


def decode_flops(cfg: dict, seen: list) -> int:
    """One decode step; ``seen[s]`` is the number of positions active slot
    ``s`` has seen, its new token included."""
    return family(cfg).decode_flops(cfg, seen)


def decode_bytes(cfg: dict, seen: list) -> int:
    """One decode step over the active slots: every weight once, the batch's
    embedding rows, the state of valid positions read once, the new state
    and the logits written once."""
    return family(cfg).decode_bytes(cfg, seen)
