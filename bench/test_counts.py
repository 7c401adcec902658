"""Operation and byte counts against hand counts for both configurations."""
import json
from pathlib import Path

import pytest

from bench import counts

HERE = Path(__file__).resolve().parent


def cfg(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


# per layer: wq + wo (D*q each), wk + wv (D*kv each), SwiGLU (3*D*F), 2 norms
CODER_LAYER = 2 * 7168 * 7168 + 2 * 7168 * 1024 + 3 * 7168 * 19200 + 2 * 7168
MISTRAL_LAYER = 2 * 12288 * 12288 + 2 * 12288 * 1024 + 3 * 12288 * 28672 + 2 * 12288


@pytest.mark.parametrize("name,layer,total", [
    ("coder33b", CODER_LAYER, 8 * CODER_LAYER + 2 * 32256 * 7168 + 7168),
    ("mistral123b", MISTRAL_LAYER, 4 * MISTRAL_LAYER + 2 * 32768 * 12288 + 12288),
])
def test_params(name, layer, total):
    assert counts.layer_params(cfg(name)) == layer
    assert counts.params(cfg(name)) == total


def test_param_totals_as_published_widths_give():
    assert counts.layer_params(cfg("coder33b")) == 530_331_648
    assert counts.params(cfg("coder33b")) == 4_705_082_368
    assert counts.layer_params(cfg("mistral123b")) == 1_384_144_896
    assert counts.params(cfg("mistral123b")) == 6_341_898_240


def test_params_match_the_engine():
    from bench.harness import model_config
    for name in ("coder33b", "mistral123b"):
        assert counts.params(cfg(name)) == model_config(cfg(name)).param_count()


def test_prefill_flops_by_hand():
    c = cfg("coder33b")
    mm = 8 * 2 * (CODER_LAYER - 2 * 7168)        # matmul weights, 2 flops each
    # one token: matmuls, QK and PV over 1 key, one logit row
    assert counts.prefill_flops(c, 1) == mm + 8 * 4 * 7168 + 2 * 7168 * 32256
    # 3 tokens see 1 + 2 + 3 keys
    assert counts.prefill_flops(c, 3) == 3 * mm + 8 * 4 * 7168 * 6 + 2 * 7168 * 32256


def test_decode_by_hand():
    c = cfg("mistral123b")
    L, D, V, q, kv = 4, 12288, 32768, 12288, 1024
    mm = L * 2 * (MISTRAL_LAYER - 2 * D)
    seen = [10, 100]
    assert counts.decode_flops(c, seen) == (
        2 * (mm + 2 * D * V) + L * 4 * q * 110)
    weights = L * MISTRAL_LAYER + V * D + D
    kv_read = L * 2 * kv * (9 + 99)
    kv_write = 2 * L * 2 * kv
    assert counts.decode_bytes(c, seen) == 2 * (
        weights + 2 * D + kv_read + kv_write + 2 * V)


def test_decode_of_no_slot_moves_weights_only():
    c = cfg("coder33b")
    assert counts.decode_flops(c, []) == 0
    assert counts.decode_bytes(c, []) == 2 * (8 * CODER_LAYER + 32256 * 7168 + 7168)
