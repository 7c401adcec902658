"""benchmarks/run.py main(): a probe that raises leaves an _ERROR row, the
other probes still run, and the run exits non-zero."""
import importlib.util
import json
from pathlib import Path

import pytest

import repro.launch.compile_cache as compile_cache

ROOT = Path(__file__).resolve().parent.parent


def test_probe_error_fails_the_run(tmp_path, monkeypatch):
    spec = importlib.util.spec_from_file_location("bench_run",
                                                  ROOT / "benchmarks" / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)

    def bench_raises():
        raise RuntimeError("probe broke")

    def bench_fine():
        run._row("bench_fine", 1.0)

    monkeypatch.setattr(run, "BENCHES", [bench_raises, bench_fine])
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path))
    # no persistent compile cache from a test
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    with pytest.raises(SystemExit) as exit_info:
        run.main([])
    assert exit_info.value.code == 1
    rows = json.loads((tmp_path / "results.json").read_text())["rows"]
    assert [r["name"] for r in rows] == ["bench_raises_ERROR", "bench_fine"]
