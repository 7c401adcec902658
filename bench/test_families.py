"""Family files: the dense GQA family gives what the harness gave before it
was split by family, a family added as a file alone is picked up, and a
missing one is named before anything compiles."""
import hashlib
import json
import shutil
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import counts, families, harness, reference, run, traffic, weights
from bench.test_harness import TINY_LIMITS, _broken, tiny_cfg, tiny_run

HERE = Path(__file__).resolve().parent
# pinned from the harness as it was before family files, by the same calls
PINS = json.loads((HERE / "testdata" / "families_pins.json").read_text())
LOGITS = np.load(HERE / "testdata" / "families_logits.npz")
N = [1, 3, 16, 100, 512, 1000, 2048]
SEEN = [[], [1], [10, 100], [2048] * 8, list(range(1, 17)), [4096] * 16]
CONFIGS = ["tiny", "coder33b", "mistral123b"]


def cfg_of(name):
    if name == "tiny":
        return tiny_cfg()
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", CONFIGS)
def test_engine_config_as_before(name):
    got = json.loads(harness.model_config(cfg_of(name)).to_json())
    assert got == PINS[name]["model_config"]


@pytest.mark.parametrize("name", CONFIGS)
def test_weight_layout_as_before(name):
    got = {n: [list(s), d] for n, (s, d) in weights.layout(cfg_of(name)).items()}
    assert got == PINS[name]["layout"]


@pytest.mark.parametrize("name", CONFIGS)
def test_counts_as_before(name):
    c, want = cfg_of(name), PINS[name]
    assert counts.layer_params(c) == want["layer_params"]
    assert counts.params(c) == want["params"]
    assert {str(n): counts.prefill_flops(c, n) for n in N} == want["prefill_flops"]
    assert [counts.decode_flops(c, s) for s in SEEN] == want["decode_flops"]
    assert [counts.decode_bytes(c, s) for s in SEEN] == want["decode_bytes"]


@pytest.mark.parametrize("seed", ["7", "2147495993"])
def test_tiny_weights_as_before(seed):
    c = tiny_cfg()
    w = jax.jit(lambda k: weights.all_weights(k, c))(weights.base_key(int(seed)))
    h = hashlib.sha256()
    for n in weights.layout(c):
        h.update(n.encode())
        h.update(np.asarray(w[n]).tobytes())
    assert h.hexdigest() == PINS["tiny"]["weights_sha256"][seed]


@pytest.mark.parametrize("quant", ["none", "fp8", "int8"])
def test_tiny_reference_logits_as_before(quant):
    seqs = [LOGITS["seq_0"], LOGITS["seq_1"]]
    rows = [LOGITS["rows_0"], LOGITS["rows_1"]]
    got = reference.logits(tiny_cfg(), 2147495993, seqs, rows, quant=quant)
    for i, g in enumerate(got):
        np.testing.assert_allclose(g, LOGITS[f"{quant}_{i}"], rtol=1e-5, atol=1e-5)


@pytest.fixture
def copied_family(monkeypatch, tmp_path):
    """A tiny configuration whose family is a copy of the dense GQA file
    under another name, in a families directory that holds only it."""
    shutil.copy(families.DIR / "dense_gqa.py", tmp_path / "copied_gqa.py")
    monkeypatch.setattr(families, "DIR", tmp_path)
    return {**tiny_cfg(), "name": "tiny_copied", "reference": "copied_gqa"}


def test_family_added_as_a_file_serves_correctly(copied_family):
    res = tiny_run("open", cfg=copied_family)
    assert res["correct"], res["checks"]
    assert families.family(copied_family).__file__.endswith("copied_gqa.py")


@pytest.mark.parametrize("kind", ["state", "token"])
def test_family_added_as_a_file_catches_a_broken_decode(monkeypatch, copied_family, kind):
    from repro.serving import engine as engine_mod
    monkeypatch.setattr(engine_mod, "image_programs", _broken(kind))
    res = tiny_run("open", cfg=copied_family)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > TINY_LIMITS["max_logit_gap"]


def test_missing_family_is_named_before_anything_compiles():
    cfg = {**tiny_cfg(), "reference": "no_such_family"}
    clock = run.CompileClock()
    with pytest.raises(FileNotFoundError, match="no_such_family.py"):
        harness.Harness(cfg, traffic.load("tiny_open", HERE / "testdata"), 7)
    assert clock.compiles == 0
    for call in (counts.params, weights.layout, harness.model_config):
        with pytest.raises(FileNotFoundError, match="no_such_family.py"):
            call(cfg)
