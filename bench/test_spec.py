"""Every name in BENCHMARK.json leads to its file."""
import json

from bench import check, families, run, traffic


def test_every_cell_and_metric_has_its_files():
    bench = run.spec()
    for w in bench["workloads"]:
        cell, cfg = run.cell_of(bench, w["name"])
        assert cfg["name"] == cell["config"]
        traffic.load(cell["traffic"])
        assert set(check.limits(cell["name"])) == {"max_logit_gap", "wrong_length"}
        assert run.metrics_of(bench, cell["name"], trace=False)
        assert run.metrics_of(bench, cell["name"], trace=True)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(run.reader(m["name"]))


def test_config_files_hold_the_sizes_they_claim():
    bench = run.spec()
    for c in bench["configs"]:
        cfg = json.loads((run.ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])


def test_every_config_names_a_family_file_that_keeps_the_contract():
    bench = run.spec()
    for c in bench["configs"]:
        cfg = json.loads((run.ROOT / c["file"]).read_text())
        fam = families.family(cfg)
        assert all(callable(getattr(fam, f)) for f in families.REQUIRED)
