"""The fp8 control fails the output check where sound serving passes, at
test size on the CPU (the chip readings at the cells' size are in PERF.md)."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import check, harness, traffic
from bench.test_harness import TINY_LIMITS

HERE = Path(__file__).resolve().parent


def served(seed, n=24):
    """Serve ``n`` requests of the tiny open mix, all sent at once, to the end."""
    cfg = json.loads((HERE / "testdata" / "tiny.json").read_text())
    mix = traffic.load("tiny_open", HERE / "testdata")
    h = harness.Harness(cfg, mix, seed)
    h.warm_up()
    for i, n_tok in enumerate(traffic.prompt_lengths(mix, seed, n)):
        h._submit(h._request(i, n_tok, 0.0))
    while h.inflight:
        h._step_all()
    done = list(h.reqs)
    h.free()
    return cfg, check.sample(done, seed)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_fp8_control_fails_where_sound_serving_passes(seed):
    cfg, picked = served(seed)
    def nums(quant):
        return {"max_logit_gap": check.gaps(cfg, seed, picked, quant=quant).max(),
                "wrong_length": 0}
    assert check.verdict(nums("none"), TINY_LIMITS)
    assert not check.verdict(nums("fp8"), TINY_LIMITS)
    assert np.all(check.gaps(cfg, seed, picked) >= 0)
