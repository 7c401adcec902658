"""Find the knee of an open-loop cell: the highest rate it sustains.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 2,4,6

Sets the cell up once, then serves one window per rate, each from an idle
engine, and prints one JSON line per rate: requests due and finished, the
backlog left at the close, and the tails. A rate is sustained while the
backlog at the close stays near what one arrival gap holds and the tails do
not grow with the window. Needs a TPU, like ``run.py``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

from run import CACHE, ROOT, cell_of, require_chips, spec  # noqa: I001


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell, cfg = cell_of(spec(), args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    os.environ.setdefault("TPU_LOG_DIR", str(CACHE / "tpu_logs"))
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    device = require_chips(cell["chips"])
    from repro.launch.compile_cache import enable_compile_cache
    from bench import harness, traffic
    enable_compile_cache()
    mix = traffic.load(cell["traffic"])
    if mix.loop != "open":
        raise SystemExit("a sweep needs an open-loop mix")
    h = harness.Harness(cfg, mix, args.seed)
    h.warm_up()
    for rate in (float(r) for r in args.rates.split(",")):
        h.mix = dataclasses.replace(mix, rate_per_s=rate)
        h.reqs, h.steps = [], []
        t_open = time.perf_counter()
        close = h.run(t_open, args.seconds)
        backlog = len(h.inflight)
        h.drain()
        ttft = [r.token_t[0] - r.due for r in h.reqs if r.token_t]
        itl = [b - a for r in h.reqs for a, b in zip(r.token_t, r.token_t[1:])
               if b <= close]
        done = sum(r.done_t is not None and r.done_t <= close for r in h.reqs)
        print(json.dumps({
            "rate_per_s": rate, "due": len(h.reqs), "done_in_window": done,
            "backlog_at_close": backlog,
            "ttft_p50_s": float(np.percentile(ttft, 50)),
            "ttft_p95_s": float(np.percentile(ttft, 95)),
            "itl_p95_ms": 1e3 * float(np.percentile(itl, 95)),
            "submit_lag_p95_ms": 1e3 * float(np.percentile(
                [r.submit_t - r.due for r in h.reqs], 95))}), flush=True)
    stats = device.memory_stats() or {}
    print(json.dumps({"memory_peak_bytes": stats.get("peak_bytes_in_use")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
