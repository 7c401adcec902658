"""One traced run of a cell, with where the device waited inside the engine.

    python bench/span_report.py --workload <cell> --seed <n> --seconds <s>

Runs ``bench/run.py``'s traced run of the cell, with the cell's end-to-end
metrics in its line too (against an untraced run on the same seed they give
the cost of tracing), then reduces the run's trace and prints it as the last
line: device-idle seconds by the harness's innermost span (the result line's
``breakdown.idle_gaps``) and by the engine's innermost span, and the share of
the idle under ``bench.step`` and ``bench.admit`` that lies inside an engine
span (``covered``) and inside one other than ``engine.step`` alone
(``covered_by_a_child``).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

UNDER = {"bench.step", "bench.admit"}


def report(path: str) -> dict:
    """The reduction of one trace file of a traced run."""
    from bench import engine_spans
    from bench import trace as tr
    full = tr.load(path)
    (_, t0, t1), = [s for s in full.spans if s[0] == engine_spans.WINDOW]
    trace = tr.clip(full, t0, t1)
    spans = engine_spans.load(path)[1]
    harness = tr.idle_by_span(trace, t0, t1)
    under = engine_spans.idle_by_innermost(trace, spans, t0, t1, under=UNDER)
    total = sum(v for k, v in harness.items() if k in UNDER)
    outside = under.get("no engine span", 0.0)
    counts = {n: sum(1 for sp in spans if sp[0] == n and t0 <= sp[1] <= t1)
              for n in ("engine.admit", "engine.decode")}
    return {
        "window_s": t1 - t0, "busy_s": tr.busy(trace),
        "idle_by_harness_span": harness,
        "idle_by_engine_span": engine_spans.idle_by_innermost(trace, spans, t0, t1),
        "idle_under_step_and_admit": {"total": total, "by_engine_span": under},
        "covered": 1.0 - outside / total if total else None,
        "covered_by_a_child": (1.0 - (outside + under.get("engine.step", 0.0)) / total
                               if total else None),
        "spans_started_in_window": counts}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--seconds", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(Path(__file__).resolve().parent.parent)]
    from bench import run
    from bench import trace as tr
    bench = run.spec()
    # the end-to-end metrics as if per-layer, so that the traced line has them
    traced = dict(bench, per_layer=bench["per_layer"] + [
        dict(m, moves=m["name"]) for m in bench["end_to_end"]])
    run.spec = lambda: traced
    rc = run.main(["--workload", args.workload, "--seed", args.seed,
                   "--seconds", args.seconds, "--trace", "1"])
    path = tr.find(str(run.CACHE / "trace" / args.workload))
    print(json.dumps({"span_report": report(path)}))
    return rc


if __name__ == "__main__":
    sys.exit(main())
