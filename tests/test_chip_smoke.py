"""chip_smoke.py off the chip: it refuses a CPU-only JAX, and its phases pass
on the CPU at a tiny size with the Pallas kernels interpreted."""
import dataclasses
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_config

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod       # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def test_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_phases_at_tiny_size(smoke):
    cfg = dataclasses.replace(
        get_config("deepseek_coder_33b"), name="deepseek_coder_tiny",
        num_layers=2, d_model=256, num_heads=8, num_kv_heads=2, head_dim=32,
        d_ff=512, vocab_size=512)
    inst = smoke.serve(cfg, workers=2, concurrency=2, gen_tokens=4,
                       max_len=64, prompts=(16, 32) * 2, seed=0)
    errs = smoke.parity(inst, prompt_len=16, steps=3, seed=0)
    assert errs["rel_rms"] <= smoke.PARITY_REL_RMS
    shapes = smoke.KernelShapes(
        heads=8, kv_heads=2, head_dim=128, seq=256, batch=2, cache_len=256,
        d_inner=256, d_state=16, scan_len=64, d_model=256, experts=4,
        expert_ff=256, rows=256)
    errs = smoke.kernels(shapes, impl="interpret", seed=0)
    assert set(errs) == {"flash_attention", "decode_attention", "mamba_scan",
                         "grouped_matmul"}


def test_parity_catches_a_wrong_program(smoke):
    """The parity phase fails when the served program is not the model."""
    cfg = dataclasses.replace(
        get_config("deepseek_coder_33b"), name="deepseek_coder_tiny_bad",
        num_layers=1, d_model=128, num_heads=4, num_kv_heads=2, head_dim=32,
        d_ff=256, vocab_size=256)
    inst = smoke.serve(cfg, workers=1, concurrency=2, gen_tokens=2,
                       max_len=64, prompts=(16,), seed=0)
    prefill = inst._prefill
    inst._prefill = lambda p, b: (lambda lg, c: (lg * 1.5, c))(*prefill(p, b))
    with pytest.raises(smoke.SmokeFailure):
        smoke.parity(inst, prompt_len=16, steps=2, seed=0)
