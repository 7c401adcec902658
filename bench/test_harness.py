"""A whole run at test size on the CPU: served correctly, and caught when
the timed path is broken underneath (chip check skipped)."""
import json
from pathlib import Path

import jax
import pytest

from bench import run, traffic

HERE = Path(__file__).resolve().parent
# at this size sound serving read max_logit_gap <= 0.026 (7 timed runs) and
# <= 0.0171 (seeds 1-15, every request sent at once); the fp8 control read
# >= 0.063 on those 15 seeds. The test limit sits between.
TINY_LIMITS = {"max_logit_gap": 0.04, "wrong_length": 0}
BENCH = {
    "end_to_end": [
        {"name": "ttft_p95_s", "unit": "s", "workloads": ["tiny.open"]},
        {"name": "itl_p95_ms", "unit": "ms"},
        {"name": "out_tok_per_s", "unit": "tokens/s", "workloads": ["tiny.closed"]},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [{"name": "compile_s", "unit": "s", "moves": "setup_s"}]}


def tiny_cfg():
    return json.loads((HERE / "testdata" / "tiny.json").read_text())


def tiny_run(loop, seed=7, trace=False, cfg=None):
    cfg = cfg or tiny_cfg()
    mix = traffic.load(f"tiny_{loop}", HERE / "testdata")
    return run.run_cell({"name": f"tiny.{loop}", "chips": 1}, cfg, mix, seed=seed,
                        seconds=1.5, trace=trace, bench=BENCH, limits=TINY_LIMITS,
                        peaks={"bf16_flop_per_s": 1e12, "hbm_bytes_per_s": 1e11},
                        device=jax.devices()[0], clock=run.CompileClock())["result"]


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_sound_run_is_correct(loop):
    res = tiny_run(loop)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {"itl_p95_ms", "setup_s"} | (
        {"ttft_p95_s"} if loop == "open" else {"out_tok_per_s"})
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"


def test_traced_run_reports_per_layer_metrics():
    res = tiny_run("closed", trace=True)
    assert res["correct"]
    assert set(res["metrics"]) == {"compile_s"}


def _broken(kind):
    from repro.serving import engine as engine_mod
    real = engine_mod.image_programs

    def programs(model):
        prefill, decode = real(model)

        def bad_decode(params, cache, batch):
            logits, new = decode(params, cache, batch)
            if kind == "state":
                return logits, cache
            return jax.numpy.roll(logits, 1, axis=-1), new
        return prefill, jax.jit(bad_decode)
    return programs


@pytest.mark.parametrize("kind", ["state", "token"])
def test_broken_decode_is_not_correct(monkeypatch, kind):
    from repro.serving import engine as engine_mod
    monkeypatch.setattr(engine_mod, "image_programs", _broken(kind))
    res = tiny_run("open")
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > TINY_LIMITS["max_logit_gap"]
